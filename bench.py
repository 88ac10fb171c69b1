#!/usr/bin/env python
"""Headline benchmark: ResNet-50 v1 training throughput (images/sec) on one
TPU chip, matching the reference's measurement protocol
(ref: example/image-classification/train_imagenet.py + docs/faq/perf.md:225 —
synthetic data, SGD momentum, batch 128, fp32 baseline 363.69 img/s on V100).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "platform",
"device_kind", "device_count", ...extras}.

The measurement needs a TPU. With none it exits non-zero naming the
platforms jax found, and prints no throughput. One process per chip: this
parent never imports jax; each dtype is measured by one child process
(BENCH_CHILD=1) that holds the chip alone, bf16 then fp32, and a child
that fails fails the run.

The CPU-side count gates (--dispatch-overhead, --observatory,
--recommender, --cold-start, --sharding) are separate modes that measure
no speed.
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_FP32 = 363.69  # MXNet-CUDA ResNet-50 v1 fp32 bs128 on V100 (perf.md:225)
# ResNet-50 fwd FLOPs at 224x224 ~ 4.09 GFLOP/img; training ~ 3x fwd.
FLOPS_PER_IMAGE_TRAIN = 3 * 4.09e9

# Published per-chip peaks, keyed by jax's `device_kind`. A device that is
# not in the table is an error, never a default: a utilization against the
# wrong chip's peak is worse than none. No fp32 matmul peak is published
# for these chips, so none is listed and no fp32 MFU is reported.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e' system "
                  "architecture, per-chip figures",
    },
}


def device_peaks(device_kind):
    """The DEVICE_PEAKS row for `device_kind`; ValueError when unknown."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add the chip to bench.DEVICE_PEAKS "
            f"with its source before reporting a utilization") from None


def require_tpu():
    """jax.devices() when the default backend is a TPU; exits otherwise
    with a line naming the platforms found."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        found = sorted({d.platform for d in devices})
        sys.exit(f"{os.path.basename(sys.argv[0] or 'bench.py')} measures "
                 f"on a TPU; jax found only platforms {found}")
    return devices


def build_train_step(dtype, batch_size, layout="NHWC", device=None,
                     mesh=None, shard_policy=None, remat=False,
                     remat_policy=None):
    """The measured configuration: model-zoo ResNet-50 v1 (1000 classes)
    under SGD-momentum, fused into one GluonTrainStep. chip_smoke.py's
    train and mesh phases build theirs here too, so what the smoke proves
    is what the benchmark measures."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fused, gluon
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    # build + initialize on the host: avoids hundreds of tiny per-param
    # device programs; GluonTrainStep moves the weights once at build
    with jax.default_device(jax.devices("cpu")[0]):
        net = vision.resnet50_v1(classes=1000, layout=layout)
        net.initialize(mx.init.Xavier())
        if dtype == "bfloat16":
            net.cast("bfloat16")
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4,
                           rescale_grad=1.0 / batch_size)
    return fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt,
                                device=device, mesh=mesh,
                                shard_policy=shard_policy, remat=remat,
                                remat_policy=remat_policy)


def synthetic_batch(seed, dtype, batch_size, image_size, layout="NHWC",
                    lead=()):
    """Seeded host batch (x, y) as NDArrays; `lead` prepends scan axes."""
    import numpy as np
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd

    rng = np.random.RandomState(seed)
    shape = ((batch_size, image_size, image_size, 3) if layout == "NHWC"
             else (batch_size, 3, image_size, image_size))
    x = jnp.asarray(rng.rand(*lead, *shape).astype(np.float32),
                    jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, size=(*lead, batch_size))
                    .astype(np.float32))
    # from_jax keeps the dtype; nd.array() would force-cast bf16 to f32
    return nd.from_jax(x), nd.from_jax(y)


def child_main():
    import numpy as np
    import jax

    devices = require_tpu()
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import compile_cache

    compile_cache.enable_jax_cache()
    batch_size = int(os.environ.get("BENCH_BATCH", "128"))
    image_size = int(os.environ.get("BENCH_IMAGE", "224"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "30"))
    dtype = os.environ.get("BENCH_DTYPE", "float32")
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")  # NHWC = TPU-native
    target = devices[0]

    # BENCH_REMAT_POLICY (set by --remat-policy) selects a named
    # jax.checkpoint_policies tier; unset falls back to MXTPU_REMAT_POLICY
    remat_policy = os.environ.get("BENCH_REMAT_POLICY") or None
    # BENCH_SHARD_POLICY (set by --shard-policy): ZeRO-shard optimizer
    # state (+ masters) over a 1-axis 'data' mesh spanning every visible
    # chip; telemetry is switched on so the final line can report the
    # per-role per-device HBM ledger bytes
    shard_policy = os.environ.get("BENCH_SHARD_POLICY") or None
    mesh = None
    if shard_policy and shard_policy != "replicated":
        mesh = jax.sharding.Mesh(np.array(devices), axis_names=("data",))
        mx.telemetry.enable()
    step = build_train_step(dtype, batch_size, layout, device=target,
                            mesh=mesh, shard_policy=shard_policy,
                            remat=os.environ.get("BENCH_REMAT") == "1",
                            remat_policy=remat_policy)

    # staged once on the default device (the chip), outside the timed loops
    x, y = synthetic_batch(0, dtype, batch_size, image_size, layout)

    # every timed region ends in a value fetch of the LAST loss:
    # executions on one device are stream-ordered (and the steps chain
    # through donated params), so the fetch closes the whole region
    t0 = time.perf_counter()
    compile_s = 0.0
    print(f"[bench] init done ({dtype}), compiling...", file=sys.stderr, flush=True)
    for i in range(warmup):
        loss = step(x, y)
        if i == 0:
            loss.asnumpy()
            compile_s = time.perf_counter() - t0
            print(f"[bench] first step (compile) {compile_s:.1f}s",
                  file=sys.stderr, flush=True)
    loss.asnumpy()

    start = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss.asnumpy()
    elapsed = time.perf_counter() - start
    ips = batch_size * iters / elapsed

    # scan mode: K steps per device program (fused.scan_steps) — device
    # throughput free of per-step dispatch latency (the bulked-exec analog)
    scan_k = int(os.environ.get("BENCH_SCAN", "8"))
    scan_ips = 0.0
    if scan_k > 1:
        xs, ys = synthetic_batch(1, dtype, batch_size, image_size, layout,
                                 lead=(scan_k,))
        t0 = time.perf_counter()
        step.scan_steps(xs, ys).asnumpy()  # compile + warm
        print(f"[bench] scan compile {time.perf_counter()-t0:.1f}s",
              file=sys.stderr, flush=True)
        reps = max(1, iters // scan_k)
        t0 = time.perf_counter()
        for _ in range(reps):
            losses = step.scan_steps(xs, ys)
        losses.asnumpy()
        scan_ips = batch_size * scan_k * reps / (time.perf_counter() - t0)

    # bytes/step from XLA's cost model on the single-step program — the
    # HBM-traffic number reported next to img/s (BENCH_BYTES=0 skips the
    # extra abstract compile; it reuses the persistent XLA cache)
    bytes_per_step = 0.0
    if os.environ.get("BENCH_BYTES", "1") != "0":
        bytes_per_step = step.cost_stats(x, y).get("bytes_accessed", 0.0)

    out = {
        "ips": round(ips, 2),
        "scan_ips": round(scan_ips, 2),
        "scan_k": scan_k,
        "layout": layout,
        "dtype": dtype,
        "platform": target.platform,
        "device_kind": target.device_kind,
        "device_count": len(devices),
        # chips the step actually ran on: the mesh's, or the one target
        "chips": len(mesh.devices.flat) if mesh is not None else 1,
        "compile_s": round(compile_s, 1),
        "loss": float(loss.asscalar()),
        "bytes_per_step": round(bytes_per_step),
        "remat_policy": step.remat_policy,
        "fused_epilogue": os.environ.get("MXTPU_FUSED_EPILOGUE", "0")
        not in ("", "0", "false", "off"),
    }
    if mesh is not None:
        # per-device (addressable-shard) HBM ledger bytes by role — the
        # ZeRO saving shows up as optimizer_state shrinking by ~mesh size
        from incubator_mxnet_tpu.telemetry import ledger as _ledger
        out["shard_policy"] = step.shard_policy
        for role in ("params", "grads", "optimizer_state"):
            out[f"ledger_{role}_bytes"] = int(_ledger.live_bytes(role))
    print(json.dumps(out), flush=True)


def _run_child(dtype):
    """Measure one dtype in a child that holds the chip alone. The child's
    stderr passes through; a child that fails ends the run with its code."""
    env = dict(os.environ, BENCH_CHILD="1", BENCH_DTYPE=dtype)
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       stdout=subprocess.PIPE, text=True, timeout=3600,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
    if p.returncode != 0:
        sys.exit(p.returncode)
    return json.loads(p.stdout.strip().splitlines()[-1])


def dispatch_overhead_main(assert_mode=False):
    """Eager Trainer dispatch-overhead microbench: a ~200-parameter dense
    stack stepped with aggregated multi-tensor updates vs the per-param
    loop (MXNET_OPTIMIZER_AGGREGATION_SIZE=0). Dispatch counts come from
    the mxtpu_trainer_dispatches_total counter; --assert additionally
    requires strictly fewer aggregated dispatches AND identical final
    weights (the CI aggregation smoke tier)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd, telemetry
    from incubator_mxnet_tpu.gluon import nn

    n_layers = int(os.environ.get("BENCH_DISPATCH_LAYERS", "100"))
    width = int(os.environ.get("BENCH_DISPATCH_WIDTH", "8"))
    steps = int(os.environ.get("BENCH_DISPATCH_STEPS", "5"))
    telemetry.enable()

    def build():
        net = nn.Sequential()
        for _ in range(n_layers):
            net.add(nn.Dense(width))
        net.initialize(mx.init.Xavier())
        net(nd.ones((2, width)))
        rng = np.random.RandomState(7)
        for p in net.collect_params().values():
            p.set_data(nd.array(
                rng.uniform(-0.05, 0.05, size=p.shape).astype("float32")))
        return net

    def run(agg):
        os.environ["MXNET_OPTIMIZER_AGGREGATION_SIZE"] = \
            "4096" if agg else "0"
        net = build()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        rng = np.random.RandomState(11)
        xs = [nd.array(rng.uniform(-1, 1, size=(4, width)).astype("float32"))
              for _ in range(steps)]

        def one_epoch():
            for x in xs:
                with autograd.record():
                    loss = (net(x) ** 2).sum()
                loss.backward()
                tr.step(4)
            loss.asnumpy()  # close the async chain before timing

        one_epoch()  # warmup: compiles every program involved
        c = telemetry.counter("mxtpu_trainer_dispatches_total")
        path = "aggregated" if agg else "per_param"
        before = c.value(kind="optimizer_update", path=path)
        t0 = time.perf_counter()
        one_epoch()
        dt = time.perf_counter() - t0
        dispatches = c.value(kind="optimizer_update", path=path) - before
        weights = np.concatenate([p.data().asnumpy().ravel()
                                  for p in net.collect_params().values()])
        return dt, dispatches, weights, len(list(net.collect_params()))

    eager_s, eager_n, eager_w, n_params = run(agg=False)
    agg_s, agg_n, agg_w, _ = run(agg=True)
    match = bool(np.allclose(eager_w, agg_w, rtol=1e-5, atol=1e-7))
    out = {
        "metric": "trainer_dispatch_overhead",
        "value": round(eager_s / agg_s, 3) if agg_s > 0 else 0.0,
        "unit": "x_step_speedup_aggregated_vs_per_param",
        "params": n_params,
        "steps": steps,
        "per_param_dispatches": int(eager_n),
        "aggregated_dispatches": int(agg_n),
        "per_param_s": round(eager_s, 4),
        "aggregated_s": round(agg_s, 4),
        "weights_match": match,
    }
    print(json.dumps(out), flush=True)
    if assert_mode:
        assert agg_n < eager_n, (
            f"aggregation did not reduce dispatches: {agg_n} vs {eager_n}")
        assert agg_n <= steps * max(1, n_params // 50), (
            f"aggregated path issued {agg_n} dispatches for {steps} steps — "
            "expected O(num_buckets) per step")
        assert match, "aggregated and per-param weights diverged"


def observatory_main(assert_mode=False):
    """Performance-observatory bench: a small dense net trained for two
    epochs with full telemetry on. Reports the per-phase step breakdown
    (sum must track total step time), the HBM peak with span attribution,
    and the retrace count over the steady-shape second epoch (must be 0).
    --assert turns those properties into hard failures (the CI perf-gate
    tier runs this mode)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd, telemetry
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.telemetry import stepstats, ledger, compilereg

    n_layers = int(os.environ.get("BENCH_OBS_LAYERS", "4"))
    width = int(os.environ.get("BENCH_OBS_WIDTH", "32"))
    batch = int(os.environ.get("BENCH_OBS_BATCH", "32"))
    n_batches = int(os.environ.get("BENCH_OBS_BATCHES", "8"))
    telemetry.enable()
    stepstats.reset()
    ledger.reset()
    compilereg.reset()

    # explicit in_units: params materialize (and get ledger-tracked) now
    net = nn.Sequential()
    for _ in range(n_layers):
        net.add(nn.Dense(width, in_units=width))
    net.add(nn.Dense(1, in_units=width))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9})
    rng = np.random.RandomState(3)
    x = rng.uniform(-1, 1, size=(batch * n_batches, width)).astype("float32")
    y = rng.uniform(-1, 1, size=(batch * n_batches, 1)).astype("float32")
    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(nd.array(x), nd.array(y)),
        batch_size=batch)
    loss_fn = gluon.loss.L2Loss()

    def retraces():
        total = 0.0
        c = telemetry.REGISTRY.get("mxtpu_retraces_total")
        if c is not None:
            total = sum(child.value for _, child in c.series())
        return total

    def one_epoch():
        for bx, by in loader:
            with autograd.record():
                # forward/backward issue async XLA work: dispatch phase
                with stepstats.phase("dispatch"):
                    loss = loss_fn(net(bx), by)
            with stepstats.phase("dispatch"):
                loss.backward()
            tr.step(batch)  # optimizer_update phase + step_end inside
            with stepstats.phase("device_sync"):
                loss.asnumpy()

    one_epoch()
    r1 = retraces()
    one_epoch()
    r2 = retraces()

    snap = stepstats.snapshot()
    peak = ledger.peak_info()
    out = {
        "metric": "perf_observatory",
        "value": round(snap.get("coverage") or 0.0, 4),
        "unit": "phase_coverage_of_step_total",
        "steps": snap["steps"],
        "phases": {name: {"p50": round(q["p50"], 6), "p99": round(q["p99"], 6)}
                   for name, q in snap["phases"].items()},
        "hbm_peak_bytes": int(peak["peak_bytes"]),
        "hbm_peak_span": peak["span"],
        "retraces_epoch1": int(r1),
        "retraces_epoch2": int(r2 - r1),
        "anomalies": int(snap["anomalies"]),
        "compiled_fns": len(compilereg.snapshot()),
    }
    print(json.dumps(out), flush=True)
    if assert_mode:
        cov = snap.get("coverage") or 0.0
        assert 0.9 <= cov <= 1.1, (
            f"phase sum diverged from step total: coverage={cov:.3f}")
        assert peak["peak_bytes"] > 0 and peak["span"], (
            f"HBM peak lacks span attribution: {peak}")
        assert r2 - r1 == 0, (
            f"steady-shape second epoch retraced {r2 - r1} time(s)")


def recommender_main(assert_mode=False):
    """Terascale sparse-embedding bench: a DLRM-style model whose
    per-field tables live row-sharded on an in-process PS shard fleet,
    trained on a seeded zipfian id trace in two configurations —

      naive: per-key blocking pulls (one RPC per table per shard), no nnz
             bucketing, no prefetch overlap;
      opt:   deduped bucket-padded pulls batched into ONE multi-table RPC
             per shard server, pull/forward overlap on the service's
             ordered background worker.

    Reports pull RPCs per step for both, steady-state (second-epoch)
    retraces for the opt path, worker-resident embedding bytes vs the
    full table, and whether the two configurations' final weights (every
    shard's rows + the dense towers) are BIT-identical — the levers must
    change wall time and wire shape, never math. --assert turns the
    acceptance contract into hard failures:
      opt pull RPCs/step <= num shard servers, steady retraces == 0,
      weights_match == 1, worker embedding bytes < full table bytes.
    """
    import hashlib

    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd, telemetry
    from incubator_mxnet_tpu import embedding as emb
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.models import DLRM
    from incubator_mxnet_tpu.telemetry import stepstats, ledger, compilereg

    fields = int(os.environ.get("BENCH_REC_FIELDS", "3"))
    vocab = int(os.environ.get("BENCH_REC_VOCAB", "200"))
    shards = int(os.environ.get("BENCH_REC_SHARDS", "2"))
    batch = int(os.environ.get("BENCH_REC_BATCH", "32"))
    n_batches = int(os.environ.get("BENCH_REC_BATCHES", "6"))
    epochs = 2
    field_vocabs = [vocab + 17 * i for i in range(fields)]
    telemetry.enable()

    # one seeded zipfian trace shared by both configurations: hot ids
    # repeat heavily inside a batch, which is exactly what the dedup
    # lever monetizes
    rng = np.random.RandomState(11)
    trace = []
    for _ in range(epochs * n_batches):
        xd = rng.rand(batch, 4).astype("float32")
        ids = np.stack([(rng.zipf(1.3, size=batch) - 1) % v
                        for v in field_vocabs], axis=1)
        y = rng.randint(0, 2, (batch, 1)).astype("float32")
        trace.append((xd, ids, y))
    raw_per_step = batch * fields
    uniq_per_step = float(np.mean(
        [sum(len(np.unique(ids[:, f])) for f in range(fields))
         for _, ids, _ in trace]))

    def counter_total(name):
        fam = telemetry.REGISTRY.get(name)
        return sum(ch.value for _, ch in fam.series()) if fam else 0.0

    def counter_val(name, **labels):
        fam = telemetry.REGISTRY.get(name)
        return fam.value(**labels) if fam else 0.0

    def run(mode):
        os.environ["MXTPU_SPARSE_NNZ_BUCKETING"] = \
            "1" if mode == "opt" else "0"
        os.environ["MXTPU_SPARSE_PREFETCH"] = "1" if mode == "opt" else "0"
        stepstats.reset()
        ledger.reset()
        compilereg.reset()
        c0 = {
            "batched": counter_val(emb.PULL_RPCS_TOTAL, path="batched"),
            "per_key": counter_val(emb.PULL_RPCS_TOTAL, path="per_key"),
            "retraces": counter_total("mxtpu_retraces_total"),
            "ready": counter_val(emb.PREFETCH_HITS_TOTAL, outcome="ready"),
        }
        servers, svc = emb.launch_local_fleet(shards)
        try:
            mx.random.seed(42)
            model = DLRM(field_vocabs, num_dense=4, embed_dim=8,
                         service=svc, per_key=(mode == "naive"), seed=5)
            model.initialize(mx.init.Xavier())
            svc.set_optimizer(opt_mod.SGD(learning_rate=0.05))
            tr = gluon.Trainer(model.collect_params(), "sgd",
                               {"learning_rate": 0.05})
            tr.attach_sparse_service(svc)
            loss_fn = gluon.loss.SigmoidBinaryCrossEntropyLoss()

            emb_peak = 0
            retr_e1 = None
            t0 = time.perf_counter()
            model.prefetch(trace[0][1])
            for i, (xd, ids, y) in enumerate(trace):
                with autograd.record():
                    out = model(nd.array(xd), ids)
                    loss = loss_fn(out, nd.array(y)).mean()
                loss.backward()
                tr.step(1)  # pushes embedding grads behind dense work
                # prefetch N+1 AFTER step N's push enqueued: the ordered
                # worker preserves push(N) < pull(N+1)
                if i + 1 < len(trace):
                    model.prefetch(trace[i + 1][1])
                loss.asnumpy()
                emb_peak = max(emb_peak, ledger.live_bytes("embedding"))
                if i + 1 == n_batches:
                    svc.flush()
                    retr_e1 = counter_total("mxtpu_retraces_total")
            svc.flush()
            dt = time.perf_counter() - t0
            retr_total = counter_total("mxtpu_retraces_total")

            # final weights: every shard's rows + the dense towers
            h = hashlib.sha256()
            for i in range(fields):
                h.update(svc.full_table(f"dlrm_f{i}").tobytes())
            for _, p in sorted(model.collect_params().items()):
                h.update(np.asarray(p.data().asnumpy()).tobytes())
            steps = len(trace)
            return {
                "pull_rpcs_batched": counter_val(
                    emb.PULL_RPCS_TOTAL, path="batched") - c0["batched"],
                "pull_rpcs_per_key": counter_val(
                    emb.PULL_RPCS_TOTAL, path="per_key") - c0["per_key"],
                "steady_retraces": retr_total - (retr_e1
                                                 if retr_e1 is not None
                                                 else 0.0),
                "prefetch_ready": counter_val(
                    emb.PREFETCH_HITS_TOTAL, outcome="ready") - c0["ready"],
                "sparse_pull_p50": (stepstats.snapshot()["phases"]
                                    .get("sparse_pull", {}).get("p50", 0.0)),
                "steps_per_s": steps / dt,
                "worker_embedding_bytes": int(emb_peak),
                "weights_sha": h.hexdigest(),
                "steps": steps,
            }
        finally:
            svc.close()
            for s in servers:
                try:
                    s.shutdown()
                except Exception:
                    pass

    naive = run("naive")
    opt_r = run("opt")

    full_table_bytes = int(sum(v * 8 * 4 for v in field_vocabs))
    rpc_per_step = (opt_r["pull_rpcs_batched"]
                    + opt_r["pull_rpcs_per_key"]) / opt_r["steps"]
    rpc_per_step_naive = (naive["pull_rpcs_batched"]
                          + naive["pull_rpcs_per_key"]) / naive["steps"]
    out = {
        "metric": "recommender",
        "value": round(opt_r["steps_per_s"], 3),
        "unit": "steps_per_s",
        "rpc_per_step": rpc_per_step,
        "rpc_per_step_naive": rpc_per_step_naive,
        "steady_retraces": int(opt_r["steady_retraces"]),
        "weights_match": int(naive["weights_sha"] == opt_r["weights_sha"]),
        "dedup_factor": round(raw_per_step / uniq_per_step, 3),
        "prefetch_ready": int(opt_r["prefetch_ready"]),
        "sparse_pull_p50_opt": round(opt_r["sparse_pull_p50"], 6),
        "sparse_pull_p50_naive": round(naive["sparse_pull_p50"], 6),
        "worker_embedding_bytes": opt_r["worker_embedding_bytes"],
        "full_table_bytes": full_table_bytes,
        "throughput_naive": round(naive["steps_per_s"], 3),
        "num_servers": shards,
        "num_tables": fields,
    }
    print(json.dumps(out), flush=True)
    if assert_mode:
        assert rpc_per_step <= shards + 1e-9, (
            f"opt path issued {rpc_per_step} pull RPCs/step; the whole "
            f"model must cost <= {shards} (one per shard server)")
        assert rpc_per_step_naive > shards, (
            f"naive per-key baseline issued only {rpc_per_step_naive} "
            "RPCs/step — no contrast to measure")
        assert out["steady_retraces"] == 0, (
            f"bucketed steady state retraced {out['steady_retraces']} "
            "time(s) in epoch 2")
        assert out["weights_match"] == 1, (
            "deduped+bucketed+overlapped weights diverged from the naive "
            f"blocking path: {naive['weights_sha'][:12]} vs "
            f"{opt_r['weights_sha'][:12]}")
        assert 0 < out["worker_embedding_bytes"] < full_table_bytes, (
            f"worker held {out['worker_embedding_bytes']}B of embedding "
            f"rows vs full table {full_table_bytes}B — not O(batch)")
        assert out["prefetch_ready"] >= 0


def _cold_start_child():
    """One fresh-process training run against the persistent compile cache
    (BENCH_COLD_CHILD=1; MXTPU_COMPILE_CACHE_DIR set by the parent).

    Builds a small dense net + GluonTrainStep with fixed seeds, measures
    time-to-first-step from process entry (imports + build + compile or
    cache load + first synced step), runs a few more steps, and prints one
    JSON line with the compile-event count (compilereg entries that
    actually compiled, i.e. not served from the cache), the
    mxtpu_compile_seconds observation count, the cache hit/miss/eviction
    stats, and a sha256 of the final weights — the cold, warm, and
    corrupt-cache legs must produce the identical digest."""
    import hashlib

    t0 = time.perf_counter()
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, fused, gluon, telemetry, compile_cache
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.telemetry import compilereg

    t_imports = time.perf_counter()
    width = int(os.environ.get("BENCH_COLD_WIDTH", "64"))
    layers = int(os.environ.get("BENCH_COLD_LAYERS", "8"))
    batch = int(os.environ.get("BENCH_COLD_BATCH", "16"))
    steps = int(os.environ.get("BENCH_COLD_STEPS", "4"))
    telemetry.enable()
    compilereg.reset()
    compile_cache.reset_stats()

    mx.random.seed(0)
    # deep enough that trace+compile dominates build_first_step_s on the
    # cold leg — the gated warm/cold ratio needs real compile work to
    # shrink, not just the fixed net-build/device-init floor
    net = nn.Sequential()
    for _ in range(layers):
        net.add(nn.Dense(width, in_units=width, activation="relu"))
    net.add(nn.Dense(1, in_units=width))
    net.initialize(mx.init.Xavier())
    L = gluon.loss.L2Loss()
    opt = mx.optimizer.SGD(learning_rate=0.05, momentum=0.9,
                           rescale_grad=1.0 / batch)
    step = fused.GluonTrainStep(net, lambda n, a, b: L(n(a), b), opt)
    rng = np.random.RandomState(7)
    xs = rng.uniform(-1, 1, size=(steps, batch, width)).astype("float32")
    ys = rng.uniform(-1, 1, size=(steps, batch, 1)).astype("float32")

    loss = step(nd.array(xs[0]), nd.array(ys[0]))
    first = float(loss.asnumpy())  # sync: first step has fully executed
    ttfs = time.perf_counter() - t0
    for i in range(1, steps):
        loss = step(nd.array(xs[i]), nd.array(ys[i]))
    loss.asnumpy()
    total_s = time.perf_counter() - t0

    step.sync_params()
    weights = np.concatenate([p.data().asnumpy().ravel()
                              for p in net.collect_params().values()])
    compiled = cached = 0
    for rec in compilereg.snapshot().values():
        for info in rec["entries"]:
            if info.get("cached"):
                cached += 1
            else:
                compiled += 1
    obs = 0
    h = telemetry.REGISTRY.get("mxtpu_compile_seconds")
    if h is not None:
        obs = sum(child.count for _, child in h.series())
    print(json.dumps({
        "metric": "cold_start_child",
        "ttfs_s": round(ttfs, 4),
        # ttfs minus the interpreter/jax import block, which is identical
        # in every leg: this is the part the cache can actually shrink
        # (trace+compile vs deserialize), so the gated warm/cold ratio
        # uses it instead of drowning the signal in import noise
        "build_first_step_s": round(ttfs - (t_imports - t0), 4),
        "total_s": round(total_s, 4),
        "steps": steps,
        "first_loss": first,
        "compile_events": compiled,
        "cached_events": cached,
        "compile_seconds_obs": int(obs),
        "cache": compile_cache.stats(),
        "weights_sha256": hashlib.sha256(weights.tobytes()).hexdigest(),
    }), flush=True)


def cold_start_main(assert_mode=False):
    """Cold-start bench (satellite of the persistent compile cache): run
    the same single-step training child three times against one
    MXTPU_COMPILE_CACHE_DIR —

      1. cold    — empty cache; every jit compiles and persists,
      2. warm    — fresh process, populated cache; MUST perform zero
                   compiles (compilereg shows only cached entries, the
                   mxtpu_compile_seconds histogram records nothing),
      3. corrupt — every cache entry's bytes are flipped first; the load
                   must fall back to a fresh compile, evict the bad
                   entries, and still produce bit-identical weights.

    Reports warm/cold time-to-first-step plus the cache counters as one
    JSON line for tools/perf_gate.py; --assert turns the structural
    properties into hard failures (the CI cold-start tier runs this)."""
    import tempfile

    legs = {}
    with tempfile.TemporaryDirectory(prefix="mxtpu-coldstart-") as cdir:
        env = dict(os.environ)
        env.pop("BENCH_COLD_START", None)
        env["BENCH_COLD_CHILD"] = "1"
        env["MXTPU_COMPILE_CACHE_DIR"] = cdir

        def run_leg(name):
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=600)
            if p.returncode != 0:
                raise RuntimeError(
                    f"cold-start {name} leg failed "
                    f"(rc={p.returncode}):\n{p.stderr[-2000:]}")
            line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
            legs[name] = json.loads(line)

        run_leg("cold")
        run_leg("warm")
        for fname in os.listdir(cdir):
            if fname.endswith(".exe"):
                path = os.path.join(cdir, fname)
                with open(path, "rb") as f:
                    data = f.read()
                with open(path, "wb") as f:
                    f.write(bytes(b ^ 0xFF for b in data))
        run_leg("corrupt")
        entries = len([f for f in os.listdir(cdir) if f.endswith(".exe")])

    cold, warm, corrupt = legs["cold"], legs["warm"], legs["corrupt"]
    hashes = {leg["weights_sha256"] for leg in legs.values()}
    ratio = (warm["build_first_step_s"] / cold["build_first_step_s"]
             if cold["build_first_step_s"] > 0 else 0.0)
    out = {
        "metric": "cold_start",
        "value": round(ratio, 4),
        "unit": "x_warm_over_cold_build_first_step",
        "cold_ttfs_s": cold["ttfs_s"],
        "warm_ttfs_s": warm["ttfs_s"],
        "cold_build_first_step_s": cold["build_first_step_s"],
        "warm_build_first_step_s": warm["build_first_step_s"],
        "cold_compile_events": cold["compile_events"],
        "warm_compile_events": warm["compile_events"],
        "warm_cached_events": warm["cached_events"],
        "warm_compile_seconds_obs": warm["compile_seconds_obs"],
        "warm_cache_hits": warm["cache"]["hits"],
        "warm_saved_seconds": round(warm["cache"]["saved_seconds"], 4),
        "corrupt_evictions": corrupt["cache"]["evictions"],
        "corrupt_recompiles": corrupt["cache"]["misses"],
        "weights_match": len(hashes) == 1,
        "cache_entries": entries,
    }
    print(json.dumps(out), flush=True)
    if assert_mode:
        assert cold["compile_events"] > 0, (
            "cold leg compiled nothing — the cache wrapper is not wired "
            f"into the train step: {cold}")
        assert warm["compile_events"] == 0, (
            f"warm process still compiled {warm['compile_events']} "
            "executable(s) — persistent cache missed")
        assert warm["compile_seconds_obs"] == 0, (
            "warm process recorded mxtpu_compile_seconds observations")
        assert warm["cache"]["hits"] > 0, (
            f"warm process hit nothing in the cache: {warm['cache']}")
        assert corrupt["cache"]["evictions"] > 0, (
            f"corrupt entries were not evicted: {corrupt['cache']}")
        assert corrupt["cache"]["misses"] > 0, (
            f"corrupt leg did not fall back to a fresh compile: "
            f"{corrupt['cache']}")
        assert len(hashes) == 1, (
            f"weights diverged across legs: "
            f"{ {k: v['weights_sha256'][:12] for k, v in legs.items()} }")
        assert ratio < 1.0, (
            f"warm time-to-first-step not better than cold: {out}")


def sharding_main(assert_mode=False):
    """ZeRO-sharding gate (CI `sharding` tier): on a forced 8-device CPU
    mesh, train the same bf16 multi-precision model under replicated /
    zero1 / zero2 and require the final weights to match BITWISE, measure
    the per-device optimizer-state (+ f32 master) HBM ledger bytes under
    each policy, and prove the knob-off contract — a meshless job with
    MXTPU_SHARD_POLICY exported lowers to the byte-identical program of
    one without it. Emits one JSON line for tools/perf_gate.py; --assert
    turns every property into a hard failure."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("MXTPU_SHARD_POLICY", None)  # policies passed explicitly

    import numpy as np
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, fused, gluon, telemetry
    from incubator_mxnet_tpu.telemetry import ledger

    n_dev = len(jax.devices())
    steps = int(os.environ.get("BENCH_SHARDING_STEPS", "6"))
    L = gluon.loss.SoftmaxCrossEntropyLoss()

    def fresh_net(prefix="shb_"):
        mx.random.seed(0)
        net = gluon.nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(gluon.nn.Dense(64, activation="relu", in_units=64))
            net.add(gluon.nn.Dense(64, activation="relu", in_units=64))
            net.add(gluon.nn.Dense(8, in_units=64))
        net.initialize(mx.init.Xavier())
        return net

    rng = np.random.RandomState(1)
    xs = rng.rand(steps, 16, 64).astype(np.float32)
    ys = rng.randint(0, 8, size=(steps, 16)).astype(np.float32)

    telemetry.enable()

    def run(policy):
        ledger.reset()
        net = fresh_net()
        net.cast("bfloat16")
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               multi_precision=True, rescale_grad=1.0 / 16)
        mesh = jax.sharding.Mesh(np.array(jax.devices()),
                                 axis_names=("data",))
        step = fused.GluonTrainStep(net, lambda n, a, b: L(n(a), b), opt,
                                    mesh=mesh, shard_policy=policy)
        losses = []
        for i in range(steps):
            mx.random.seed(100 + i)
            losses.append(float(step(nd.array(xs[i]),
                                     nd.array(ys[i])).asscalar()))
        opt_bytes = int(ledger.live_bytes("optimizer_state"))
        step.sync_params()
        weights = [np.asarray(d) for d in step._params]
        placements = step.shard_placements()
        return losses, weights, opt_bytes, placements

    results = {p: run(p) for p in ("replicated", "zero1", "zero2")}
    l_rep, w_rep, b_rep, _ = results["replicated"]
    weights_match = all(
        results[p][0] == l_rep
        and all(np.array_equal(a, b) for a, b in zip(results[p][1], w_rep))
        for p in ("zero1", "zero2"))
    b_z1 = results["zero1"][2]
    reduction = b_rep / max(b_z1, 1)
    placements = results["zero1"][3]
    spec_leaves = [s for specs in placements.values() for s in specs]
    n_sharded = sum(1 for s in spec_leaves if any(a for a in s))
    n_repl = len(spec_leaves) - n_sharded

    # knob-off contract: a meshless build with the env knob exported must
    # lower to the byte-identical program of one without it (fixed
    # prefixes keep parameter names, hence program text, deterministic)
    def lowered_meshless(prefix):
        net = fresh_net(prefix=prefix)
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               rescale_grad=1.0 / 16)
        step = fused.GluonTrainStep(net, lambda n, a, b: L(n(a), b), opt)
        x = nd.array(xs[0]); y = nd.array(ys[0])
        step._build(x, y)
        return jax.jit(step._step_fn).lower(
            step._params, step._states, x._data, y._data,
            jax.random.PRNGKey(0), jnp.asarray(0.1, jnp.float32),
            jnp.asarray(1.0, jnp.float32)).as_text()

    text_unset = lowered_meshless("ko_")
    os.environ["MXTPU_SHARD_POLICY"] = "zero1"
    try:
        text_knob = lowered_meshless("ko_")
    finally:
        os.environ.pop("MXTPU_SHARD_POLICY", None)
    knob_off_identical = text_unset == text_knob

    out = {
        "metric": "sharding",
        "value": round(reduction, 2),
        "unit": "x_opt_state_bytes_replicated_over_zero1",
        "devices": n_dev,
        "steps": steps,
        "weights_match": weights_match,
        "opt_state_bytes_replicated": b_rep,
        "opt_state_bytes_zero1": b_z1,
        "opt_state_bytes_zero2": results["zero2"][2],
        "opt_bytes_reduction_x": round(reduction, 2),
        "knob_off_identical": knob_off_identical,
        "placements_sharded": n_sharded,
        "placements_replicated": n_repl,
    }
    print(json.dumps(out), flush=True)
    if assert_mode:
        assert n_dev >= 8, f"expected a forced 8-device CPU mesh, got {n_dev}"
        assert weights_match, (
            "final weights diverged across shard policies — the ZeRO "
            "programs are not bit-identical to the replicated one")
        assert reduction >= 6.0, (
            f"zero1 cut optimizer-state bytes/device only {reduction:.2f}x "
            f"(replicated={b_rep}, zero1={b_z1}); need >= 6x on 8 devices")
        assert knob_off_identical, (
            "MXTPU_SHARD_POLICY exported on a meshless job changed the "
            "lowered train-step program — the knob-off contract is broken")
        assert n_sharded > 0, f"no tensor was sharded: {placements}"


def main():
    # HBM-traffic lever axes (satellite flags; env inheritance carries
    # them into the measurement children)
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a.startswith("--remat-policy"):
            val = (a.split("=", 1)[1] if "=" in a
                   else (argv[i + 1] if i + 1 < len(argv) else ""))
            os.environ["BENCH_REMAT_POLICY"] = val
        elif a.startswith("--shard-policy"):
            val = (a.split("=", 1)[1] if "=" in a
                   else (argv[i + 1] if i + 1 < len(argv) else ""))
            os.environ["BENCH_SHARD_POLICY"] = val
        elif a == "--fused-epilogue":
            os.environ["MXTPU_FUSED_EPILOGUE"] = "1"
        elif a == "--stochastic-rounding":
            os.environ["MXTPU_STOCHASTIC_ROUNDING"] = "1"
    if "--dispatch-overhead" in sys.argv or os.environ.get("BENCH_DISPATCH"):
        dispatch_overhead_main(assert_mode="--assert" in sys.argv)
        return
    if "--observatory" in sys.argv or os.environ.get("BENCH_OBSERVATORY"):
        observatory_main(assert_mode="--assert" in sys.argv)
        return
    if "--sharding" in sys.argv or os.environ.get("BENCH_SHARDING"):
        sharding_main(assert_mode="--assert" in sys.argv)
        return
    if "--recommender" in sys.argv or os.environ.get("BENCH_RECOMMENDER"):
        recommender_main(assert_mode="--assert" in sys.argv)
        return
    if os.environ.get("BENCH_COLD_CHILD"):
        _cold_start_child()
        return
    if "--cold-start" in sys.argv or os.environ.get("BENCH_COLD_START"):
        cold_start_main(assert_mode="--assert" in sys.argv)
        return
    if os.environ.get("BENCH_CHILD"):
        child_main()
        return

    # bf16 first: it is the headline TPU path
    bf16 = _run_child("bfloat16")
    fp32 = _run_child("float32")

    def best(r):
        """Best per-chip throughput a child measured (per-step or scan)."""
        return round(max(r["ips"], r["scan_ips"]) / r["chips"], 2)

    # headline = the framework's best number (the reference's headline was
    # likewise its best path — cuDNN + bulked exec); dtype is labelled
    primary = max((bf16, fp32), key=best)
    out = {
        "metric": "resnet50_v1_train_images_per_sec",
        "value": best(primary),
        "unit": "images/sec/chip",
        "vs_baseline": round(best(primary) / BASELINE_FP32, 3),
        "dtype": primary["dtype"],
        "platform": primary["platform"],
        "device_kind": primary["device_kind"],
        "device_count": primary["device_count"],
        "chips": primary["chips"],
        "layout": primary["layout"],
        "compile_s": primary["compile_s"],
        "mode": "scan" if primary["scan_ips"] > primary["ips"] else "per-step",
        # HBM traffic next to throughput: XLA cost-model bytes of the
        # single-step program, plus which traffic levers were armed
        "bytes_per_step": primary["bytes_per_step"],
        "remat_policy": primary["remat_policy"],
        "fused_epilogue": primary["fused_epilogue"],
        "bf16_ips": best(bf16),
        "bf16_vs_fp32_baseline": round(best(bf16) / BASELINE_FP32, 3),
        "bf16_mfu": round(best(bf16) * FLOPS_PER_IMAGE_TRAIN
                          / device_peaks(bf16["device_kind"])["bf16_flops"],
                          3),
        "fp32_ips": best(fp32),
    }
    if out["mode"] == "scan":
        out["scan_k"] = primary["scan_k"]
        out["per_step_ips"] = primary["ips"]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
