"""Training C ABI tests (ref: src/c_api/c_api.cc create/train entry points
+ cpp-package/example/mlp.cpp — a non-Python caller must be able to train).

The artifact/introspection half runs everywhere; PJRT execution needs a
plugin exposing GetPjrtApi (set MXTPU_PJRT_PLUGIN) and is skipped without
one.  Numeric correctness of the exported program itself is proven in
Python via deploy.TrainerArtifact (the same StableHLO the C runtime runs)
against the live fused.GluonTrainStep."""
import ctypes
import os
import subprocess

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import deploy, fused, gluon
from incubator_mxnet_tpu._native import train_lib


def _make_net(seed=0):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    return net


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    net = _make_net()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    prefix = str(tmp_path_factory.mktemp("train_artifact") / "mlp")
    deploy.export_trainer(prefix, net, lambda n, x, y: L(n(x), y), opt,
                          (8, 5), (8,))
    return prefix


def test_mxt_artifact_written(artifact):
    path = artifact + "-train.mxt"
    assert os.path.exists(path)
    with open(path, "rb") as f:
        assert f.read(8) == b"MXTPU002"


def test_python_replay_trains(artifact):
    tr = deploy.TrainerArtifact(artifact)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 5).astype(np.float32)
    y = rng.randint(0, 3, 8).astype(np.float32)
    losses = [tr.step(x, y) for _ in range(60)]
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_artifact_matches_live_train_step(artifact):
    """The exported program must compute the SAME step as the live
    GluonTrainStep it was exported from (deterministic net: PRNG unused)."""
    net = _make_net()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    step = fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)

    rng = np.random.RandomState(3)
    x = rng.rand(8, 5).astype(np.float32)
    y = rng.randint(0, 3, 8).astype(np.float32)

    tr = deploy.TrainerArtifact(artifact)
    for i in range(3):
        live_loss = float(step(mx.nd.array(x), mx.nd.array(y)).asscalar())
        art_loss = tr.step(x, y)
        np.testing.assert_allclose(art_loss, live_loss, rtol=1e-5,
                                   err_msg=f"step {i}")
    step.sync_params()
    # block auto-naming counters differ between the two nets; params are
    # positionally identical (same architecture, same init seed)
    params = [p for _, p in net.collect_params().items()]
    for i, p in enumerate(params):
        np.testing.assert_allclose(
            tr.get_state(tr.state_names[i]), p.data().asnumpy(),
            rtol=1e-5, atol=1e-6, err_msg=tr.state_names[i])


def test_c_loader_introspection(artifact):
    lib = train_lib()
    assert lib is not None, "toolchain should be available in this image"
    h = ctypes.c_void_p()
    rc = lib.MXTpuTrainerCreate((artifact + "-train.mxt").encode(), None,
                                ctypes.byref(h))
    assert rc == 0, lib.MXTpuLastError()
    try:
        n = ctypes.c_int()
        lib.MXTpuTrainerNumInputs(h, ctypes.byref(n))
        assert n.value == 2  # x, y (auto-managed scalars excluded)
        names = []
        for i in range(n.value):
            nm = ctypes.c_char_p()
            lib.MXTpuTrainerInputName(h, i, ctypes.byref(nm))
            names.append(nm.value.decode())
        assert names == ["x", "y"]
        dims = ctypes.POINTER(ctypes.c_int64)()
        ndim = ctypes.c_int()
        lib.MXTpuTrainerInputShape(h, 0, ctypes.byref(dims),
                                   ctypes.byref(ndim))
        assert [dims[i] for i in range(ndim.value)] == [8, 5]
        lib.MXTpuTrainerNumStates(h, ctypes.byref(n))
        assert n.value == 8  # 4 params + 4 momentum slots
        nm = ctypes.c_char_p()
        lib.MXTpuTrainerStateName(h, 0, ctypes.byref(nm))
        assert nm.value.decode().startswith("param:")
        # Step without a plugin must fail cleanly, not crash
        loss = ctypes.c_float()
        assert lib.MXTpuTrainerStep(h, ctypes.byref(loss)) != 0
        assert b"artifact-only" in lib.MXTpuLastError()
    finally:
        lib.MXTpuTrainerFree(h)


def test_c_get_state_initial_values(artifact):
    """Artifact-only GetState returns the exported initial parameters.

    The first param state is the first Dense weight, but its NAME depends on
    the process-global gluon auto-naming counters (denseN_weight under full
    suite order) — read it from the artifact instead of hardcoding."""
    lib = train_lib()
    tr = deploy.TrainerArtifact(artifact)
    wname = tr.state_names[0]
    assert wname.startswith("param:") and wname.endswith("_weight")
    h = ctypes.c_void_p()
    assert lib.MXTpuTrainerCreate((artifact + "-train.mxt").encode(), None,
                                  ctypes.byref(h)) == 0
    try:
        ref = tr.get_state(wname)
        got = np.zeros_like(ref)
        rc = lib.MXTpuTrainerGetState(
            h, wname.encode(),
            got.ctypes.data_as(ctypes.c_void_p), got.nbytes)
        assert rc == 0, lib.MXTpuLastError()
        np.testing.assert_array_equal(got, ref)
        # wrong name / short buffer fail cleanly
        assert lib.MXTpuTrainerGetState(h, b"param:nope",
                                        got.ctypes.data_as(ctypes.c_void_p),
                                        got.nbytes) != 0
        assert lib.MXTpuTrainerGetState(h, wname.encode(),
                                        got.ctypes.data_as(ctypes.c_void_p),
                                        3) != 0
    finally:
        lib.MXTpuTrainerFree(h)


def test_c_set_state_roundtrip(artifact):
    lib = train_lib()
    tr = deploy.TrainerArtifact(artifact)
    wname = tr.state_names[0]  # first Dense weight, whatever its auto-name
    h = ctypes.c_void_p()
    assert lib.MXTpuTrainerCreate((artifact + "-train.mxt").encode(), None,
                                  ctypes.byref(h)) == 0
    try:
        new_w = np.full(tr.get_state(wname).shape, 0.25, np.float32)
        assert lib.MXTpuTrainerSetState(
            h, wname.encode(),
            new_w.ctypes.data_as(ctypes.c_void_p), new_w.nbytes) == 0
        got = np.zeros_like(new_w)
        assert lib.MXTpuTrainerGetState(
            h, wname.encode(),
            got.ctypes.data_as(ctypes.c_void_p), got.nbytes) == 0
        np.testing.assert_array_equal(got, new_w)
    finally:
        lib.MXTpuTrainerFree(h)


def test_nd_api():
    lib = train_lib()
    dims = (ctypes.c_int64 * 2)(2, 3)
    h = ctypes.c_void_p()
    data = np.arange(6, dtype=np.float32)
    assert lib.MXTpuNDCreate(0, 2, dims,
                             data.ctypes.data_as(ctypes.c_void_p),
                             ctypes.byref(h)) == 0
    try:
        sz = ctypes.c_size_t()
        lib.MXTpuNDSize(h, ctypes.byref(sz))
        assert sz.value == 24
        dt = ctypes.c_int()
        lib.MXTpuNDDType(h, ctypes.byref(dt))
        assert dt.value == 0
        out = np.zeros(6, np.float32)
        assert lib.MXTpuNDCopyTo(h, out.ctypes.data_as(ctypes.c_void_p),
                                 out.nbytes) == 0
        np.testing.assert_array_equal(out, data)
        newd = data * 2
        assert lib.MXTpuNDCopyFrom(h, newd.ctypes.data_as(ctypes.c_void_p),
                                   newd.nbytes) == 0
        assert lib.MXTpuNDCopyTo(h, out.ctypes.data_as(ctypes.c_void_p),
                                 out.nbytes) == 0
        np.testing.assert_array_equal(out, newd)
        # size mismatch fails cleanly
        assert lib.MXTpuNDCopyFrom(h, newd.ctypes.data_as(ctypes.c_void_p),
                                   7) != 0
    finally:
        lib.MXTpuNDFree(h)
    # zero-filled creation
    assert lib.MXTpuNDCreate(0, 2, dims, None, ctypes.byref(h)) == 0
    out = np.ones(6, np.float32)
    lib.MXTpuNDCopyTo(h, out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
    assert (out == 0).all()
    lib.MXTpuNDFree(h)


def _usable_pjrt_plugin():
    cand = os.environ.get("MXTPU_PJRT_PLUGIN")
    if cand and os.path.exists(cand):
        return cand
    return None


@pytest.mark.skipif(_usable_pjrt_plugin() is None,
                    reason="no usable PJRT plugin (set MXTPU_PJRT_PLUGIN)")
def test_c_trainer_trains_on_plugin(artifact):
    """Full C-side training loop: loss must drop on the real device."""
    lib = train_lib()
    h = ctypes.c_void_p()
    rc = lib.MXTpuTrainerCreate((artifact + "-train.mxt").encode(),
                                _usable_pjrt_plugin().encode(),
                                ctypes.byref(h))
    assert rc == 0, lib.MXTpuLastError()
    try:
        rng = np.random.RandomState(0)
        x = rng.rand(8, 5).astype(np.float32)
        y = rng.randint(0, 3, 8).astype(np.float32)
        loss = ctypes.c_float()
        losses = []
        for _ in range(60):
            assert lib.MXTpuTrainerSetInput(
                h, b"x", x.ctypes.data_as(ctypes.c_void_p), x.nbytes) == 0
            assert lib.MXTpuTrainerSetInput(
                h, b"y", y.ctypes.data_as(ctypes.c_void_p), y.nbytes) == 0
            assert lib.MXTpuTrainerStep(h, ctypes.byref(loss)) == 0, \
                lib.MXTpuLastError()
            losses.append(loss.value)
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    finally:
        lib.MXTpuTrainerFree(h)


def test_cpp_training_example_builds_and_introspects(artifact, tmp_path):
    """examples/c_train/train_mlp.cpp (the cpp-package mlp.cpp role)
    compiles against mxtpu.h and introspects the artifact; with a plugin
    it trains (exercised by the plugin-gated test tier)."""
    assert train_lib() is not None  # lazy native build
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "examples", "c_train", "train_mlp.cpp")
    exe = str(tmp_path / "train_mlp")
    libdir = os.path.join(repo, "incubator_mxnet_tpu", "_native")
    build = subprocess.run(
        ["g++", "-std=c++17", src, "-I" + os.path.join(repo, "include"),
         "-L" + libdir, "-lmxtpu_train", "-Wl,-rpath," + libdir,
         "-o", exe],
        capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run([exe, artifact + "-train.mxt"],
                         capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-1000:]
    assert "inputs: 2 states: 8" in run.stdout
    assert "input x shape [ 8 5 ]" in run.stdout
    assert "introspection-only" in run.stdout

    plugin = _usable_pjrt_plugin()
    if plugin:
        run = subprocess.run([exe, artifact + "-train.mxt", plugin, "100"],
                             capture_output=True, text=True, timeout=600)
        assert run.returncode == 0, (run.stdout[-500:], run.stderr[-1000:])
        assert "TRAINED" in run.stdout


def test_set_input_nd_checks_shape_dtype(artifact):
    lib = train_lib()
    h = ctypes.c_void_p()
    assert lib.MXTpuTrainerCreate((artifact + "-train.mxt").encode(), None,
                                  ctypes.byref(h)) == 0
    try:
        # same byte count, wrong shape (5,8) vs spec (8,5): must be rejected
        dims = (ctypes.c_int64 * 2)(5, 8)
        nd_h = ctypes.c_void_p()
        assert lib.MXTpuNDCreate(0, 2, dims, None, ctypes.byref(nd_h)) == 0
        assert lib.MXTpuTrainerSetInputND(h, b"x", nd_h) != 0
        assert b"shape mismatch" in lib.MXTpuLastError()
        lib.MXTpuNDFree(nd_h)
        # right shape: accepted
        dims = (ctypes.c_int64 * 2)(8, 5)
        assert lib.MXTpuNDCreate(0, 2, dims, None, ctypes.byref(nd_h)) == 0
        assert lib.MXTpuTrainerSetInputND(h, b"x", nd_h) == 0
        lib.MXTpuNDFree(nd_h)
    finally:
        lib.MXTpuTrainerFree(h)


def test_corrupt_artifact_fails_cleanly(tmp_path):
    """A truncated/corrupt .mxt must return nonzero, never crash."""
    lib = train_lib()
    bad = str(tmp_path / "bad.mxt")
    # huge bogus size fields after a valid magic
    with open(bad, "wb") as f:
        f.write(b"MXTPU002")
        f.write(b"\xff" * 40)
    h = ctypes.c_void_p()
    assert lib.MXTpuTrainerCreate(bad.encode(), None, ctypes.byref(h)) != 0
    assert lib.MXTpuLastError()
    # truncated mid-args
    with open(bad, "wb") as f:
        f.write(b"MXTPU002")
        import struct as _s
        f.write(_s.pack("<IIQQ", 3, 1, 10, 10))
        f.write(_s.pack("<fI", 0.1, 0))
        f.write(b"\x01\x00\x02\x00")  # one arg header, then EOF
    assert lib.MXTpuTrainerCreate(bad.encode(), None, ctypes.byref(h)) != 0


def test_perl_trainer_fits(artifact, tmp_path):
    """The Perl binding drives the .mxt train ABI: build the XS module
    (predict + train surfaces), create a trainer, read artifact-only
    state, and verify the no-plugin step fails cleanly. With a usable
    PJRT plugin (MXTPU_PJRT_PLUGIN) it goes on to fit() batches and
    requires the loss to drop (reference role: perl-package/AI-MXNet's
    fit loop)."""
    import shutil
    import subprocess

    if shutil.which("perl") is None or shutil.which("make") is None:
        pytest.skip("perl/make unavailable")
    from incubator_mxnet_tpu._native import imperative_lib, predict_lib

    from common import build_perl_pkg

    # the XS module links ALL THREE native libs; build them before make
    assert (predict_lib() is not None and train_lib() is not None
            and imperative_lib() is not None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    build, env = build_perl_pkg(tmp_path, repo)
    plugin = _usable_pjrt_plugin()
    plugin_pl = f'"{plugin}"' if plugin else "undef"
    script = f"""
use blib;
use AI::MXTpu;
srand(7);
my $t = AI::MXTpu::Trainer->new("{artifact}-train.mxt", {plugin_pl});
# artifact-only state read: discover the first param by introspection,
# read its exported initial value back intact
my ($wname) = grep {{ /^param:.*_weight$/ }} @{{ $t->state_names }};
die "no param:*_weight state" unless $wname;
my $shape = $t->state_shape(
    (grep {{ $t->state_name($_) eq $wname }} 0 .. $t->num_states - 1)[0]);
my $count = 1; $count *= $_ for @$shape;
my $w = $t->get_state($wname);
die "bad state size" unless scalar(@$w) == $count;
my $nz = grep {{ abs($_) > 1e-8 }} @$w;
die "state all zeros" unless $nz > 0;
my @batches;
for my $b (0 .. 5) {{
  my (@x, @y);
  for my $i (0 .. 7) {{
    my $c = int(rand(3));
    push @y, $c;
    for my $j (0 .. 4) {{ push @x, 0.2 * (($c + $j) % 5) + 0.1 * rand(); }}
  }}
  push @batches, [ \\@x, \\@y ];
}}
if ({1 if plugin else 0}) {{
  my $losses = $t->fit(\\@batches, 8);
  printf "first=%.4f last=%.4f\n", $losses->[0], $losses->[-1];
  die "loss did not drop" unless $losses->[-1] < $losses->[0];
  print "PERL FIT OK\n";
}} else {{
  # no PJRT plugin in this image: the step must fail CLEANLY with the
  # artifact-only message, not crash
  $t->set_input("x", @{{ $batches[0][0] }});
  $t->set_input("y", @{{ $batches[0][1] }});
  my $ok = eval {{ $t->step; 1 }};
  die "step unexpectedly succeeded" if $ok;
  die "wrong error: $@" unless $@ =~ /artifact-only/;
  print "PERL TRAINER ABI OK (plugin-gated step skipped)\n";
}}
"""
    out = subprocess.run(["perl", "-e", script], cwd=build, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-1500:])
    assert ("PERL FIT OK" in out.stdout
            or "PERL TRAINER ABI OK" in out.stdout)


def test_perl_xs_uses_only_real_abi_symbols():
    """Every MXTpu* symbol the XS glue calls must exist in the native
    runtimes' sources (catches ABI drift without perl)."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    xs = open(os.path.join(repo, "perl-package", "AI-MXTpu",
                           "MXTpu.xs")).read()
    used = set(re.findall(r"\b(MXTpu\w+)\(", xs))
    impl = ""
    for src in ("imperative.cc", "train.cc", "predict.cc"):
        impl += open(os.path.join(repo, "src", src)).read()
    defined = set(re.findall(r"\b(MXTpu\w+)\(", impl))
    missing = used - defined
    assert not missing, f"XS references unknown ABI symbols: {sorted(missing)}"


def test_perl_symbol_executor_trains(tmp_path):
    """Graph-level execution from Perl: a symbol JSON composed in Perl
    binds through the embedded runtime (one jitted XLA program per
    forward) and trains with forward(1)/backward/sgd_update — the
    AI::MXNet Symbol/Executor role, third consumer of the same natives
    as the C++ SymbolExecutor and JVM CompiledExecutor."""
    import shutil
    import subprocess

    if shutil.which("perl") is None or shutil.which("make") is None:
        pytest.skip("perl/make unavailable")
    from incubator_mxnet_tpu._native import imperative_lib, predict_lib

    from common import build_perl_pkg

    # the XS module links all three native libs; build them before make
    assert (predict_lib() is not None and train_lib() is not None
            and imperative_lib() is not None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    build, env = build_perl_pkg(tmp_path, repo)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    script = r"""
$| = 1;
use blib;
use AI::MXTpu;
my $json = <<'JSON';
{
  "nodes": [
    {"op": "null", "name": "x", "attrs": {}, "inputs": []},
    {"op": "null", "name": "w1", "attrs": {}, "inputs": []},
    {"op": "null", "name": "b1", "attrs": {}, "inputs": []},
    {"op": "FullyConnected", "name": "fc1", "attrs": {"num_hidden": "16"},
     "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0]]},
    {"op": "Activation", "name": "relu1", "attrs": {"act_type": "relu"},
     "inputs": [[3, 0, 0]]},
    {"op": "null", "name": "w2", "attrs": {}, "inputs": []},
    {"op": "null", "name": "b2", "attrs": {}, "inputs": []},
    {"op": "FullyConnected", "name": "fc2", "attrs": {"num_hidden": "3"},
     "inputs": [[4, 0, 0], [5, 0, 0], [6, 0, 0]]},
    {"op": "null", "name": "label", "attrs": {}, "inputs": []},
    {"op": "softmax_cross_entropy", "name": "loss", "attrs": {},
     "inputs": [[7, 0, 0], [8, 0, 0]]}
  ],
  "arg_nodes": [0, 1, 2, 5, 6, 8],
  "heads": [[9, 0, 0]],
  "attrs": {"framework": "incubator_mxnet_tpu", "version": "0.1"}
}
JSON
srand(11);
my $batch = 16; my $in = 8;
my (@x, @y);
for my $i (0 .. $batch - 1) {
  my $c = $i % 3;
  push @y, $c;
  for my $j (0 .. $in - 1) {
    push @x, 0.3 * (($c + $j) % 4) + 0.1 * rand();
  }
}
my %nd = (
  x     => AI::MXTpu::NDArray->from_floats([$batch, $in], @x),
  w1    => AI::MXTpu::NDArray->from_floats([16, $in],
             map { 0.3 * (rand() - 0.5) } 1 .. 16 * $in),
  b1    => AI::MXTpu::NDArray->from_floats([16], (0) x 16),
  w2    => AI::MXTpu::NDArray->from_floats([3, 16],
             map { 0.3 * (rand() - 0.5) } 1 .. 3 * 16),
  b2    => AI::MXTpu::NDArray->from_floats([3], (0) x 3),
  label => AI::MXTpu::NDArray->from_floats([$batch], @y),
);
my @names = qw(x w1 b1 w2 b2 label);
my @params = qw(w1 b1 w2 b2);
my $ex = AI::MXTpu::SymbolExecutor->new(
    $json, \@names, [map { $nd{$_} } @names], \@params);
my ($first, $last);
my $attrs = sprintf '{"lr":0.1,"rescale_grad":%.6f}', 1.0 / $batch;
for my $step (1 .. 40) {
  my $outs = $ex->forward(1);
  my $l = $outs->[0]->values->[0] / $batch;
  $first = $l if $step == 1;
  $last = $l;
  $ex->backward;
  for my $p (@params) {
    my $updated = AI::MXTpu::SymbolExecutor->sgd_update(
        $nd{$p}, $ex->grad_of($p), $attrs);
    $ex->set_arg($p, $updated);
    $nd{$p} = $updated;
  }
}
printf "first=%.4f last=%.4f\n", $first, $last;
die "loss did not drop" unless $last < $first * 0.8;
print "PERL_SYMBOL_TRAINED\n";
"""
    out = subprocess.run(["perl", "-e", script], cwd=build, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-1800:])
    assert "PERL_SYMBOL_TRAINED" in out.stdout
