"""C++ op-level API tests (ref: cpp-package/include/mxnet-cpp/op.h generated
wrappers + cpp-package/example/mlp.cpp — a C++ user composes and trains a
model from op calls).

The runtime is src/imperative.cc (embedded CPython over the op registry /
autograd tape / XLA dispatch); the user surface is the generated
include/mxtpu_ops.hpp. The example runs in a SUBPROCESS so it embeds its
own interpreter — the ctypes checks here exercise the same ABI in-process
(Py_IsInitialized path)."""
import ctypes
import json
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

from incubator_mxnet_tpu._native import imperative_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lib():
    lib = imperative_lib()
    assert lib is not None, "toolchain should be available in this image"
    assert lib.MXTpuImpInit() == 0, lib.MXTpuImpError()
    return lib


def _nd_from(lib, arr):
    arr = np.ascontiguousarray(arr)
    dims = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    code = {"float32": 0, "int32": 2}[str(arr.dtype)]
    rc = lib.MXTpuImpNDCreate(code, arr.ndim, dims,
                              arr.ctypes.data_as(ctypes.c_void_p),
                              ctypes.byref(h))
    assert rc == 0, lib.MXTpuImpError()
    return h


def _nd_to_np(lib, h, shape, dtype=np.float32):
    out = np.zeros(shape, dtype)
    rc = lib.MXTpuImpNDCopyTo(h, out.ctypes.data_as(ctypes.c_void_p),
                              out.nbytes)
    assert rc == 0, lib.MXTpuImpError()
    return out


def _invoke(lib, name, handles, attrs=None):
    ins = (ctypes.c_void_p * max(1, len(handles)))(*[h.value for h in handles])
    outs = (ctypes.c_void_p * 8)()
    n_out = ctypes.c_int()
    rc = lib.MXTpuImpInvoke(
        name.encode(), ins, len(handles),
        json.dumps(attrs).encode() if attrs else None, outs, 8,
        ctypes.byref(n_out))
    assert rc == 0, lib.MXTpuImpError()
    return [ctypes.c_void_p(outs[i]) for i in range(n_out.value)]


def test_invoke_relu(lib):
    x = np.array([[-1.0, 2.0], [3.0, -4.0]], np.float32)
    h = _nd_from(lib, x)
    (r,) = _invoke(lib, "relu", [h])
    np.testing.assert_array_equal(_nd_to_np(lib, r, (2, 2)),
                                  np.maximum(x, 0))
    lib.MXTpuImpNDFree(r)
    lib.MXTpuImpNDFree(h)


def test_invoke_with_attrs(lib):
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    h = _nd_from(lib, x)
    (r,) = _invoke(lib, "sum", [h], {"axis": [1], "keepdims": True})
    np.testing.assert_allclose(_nd_to_np(lib, r, (2, 1)),
                               x.sum(axis=1, keepdims=True))
    lib.MXTpuImpNDFree(r)
    lib.MXTpuImpNDFree(h)


def test_unknown_op_fails_cleanly(lib):
    x = _nd_from(lib, np.zeros((2,), np.float32))
    ins = (ctypes.c_void_p * 1)(x.value)
    outs = (ctypes.c_void_p * 8)()
    n_out = ctypes.c_int()
    rc = lib.MXTpuImpInvoke(b"definitely_not_an_op", ins, 1, None, outs, 8,
                            ctypes.byref(n_out))
    assert rc != 0
    assert b"unknown op" in lib.MXTpuImpError()
    lib.MXTpuImpNDFree(x)


def test_autograd_roundtrip(lib):
    """record -> forward -> backward -> grad through the C ABI."""
    w = _nd_from(lib, np.array([2.0, 3.0], np.float32))
    assert lib.MXTpuImpAttachGrad(w) == 0, lib.MXTpuImpError()
    assert lib.MXTpuImpRecordBegin(1) == 0
    (sq,) = _invoke(lib, "square", [w])
    (loss,) = _invoke(lib, "sum", [sq])
    assert lib.MXTpuImpRecordEnd() == 0
    assert lib.MXTpuImpBackward(loss) == 0, lib.MXTpuImpError()
    g = ctypes.c_void_p()
    assert lib.MXTpuImpGrad(w, ctypes.byref(g)) == 0, lib.MXTpuImpError()
    np.testing.assert_allclose(_nd_to_np(lib, g, (2,)), [4.0, 6.0])
    for h in (g, loss, sq, w):
        lib.MXTpuImpNDFree(h)


def test_generated_header_current():
    """include/mxtpu_ops.hpp must be regenerated when the registry changes.
    Compares CONTENT before/after regeneration (git state would flag
    legitimately uncommitted work)."""
    target = os.path.join(REPO, "include", "mxtpu_ops.hpp")
    before = open(target).read()
    try:
        gen = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "gen_cpp_api.py")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert gen.returncode == 0, gen.stderr[-800:]
        after = open(target).read()
        assert before == after, "stale header — run tools/gen_cpp_api.py"
    finally:
        # never leave the working tree mutated (a stale file regenerated
        # in-place would make a CI retry pass spuriously)
        with open(target, "w") as f:
            f.write(before)


def _build_and_run_cpp_example(tmp_path, example_dir, exe_name, epochs):
    """Compile one examples/<dir>/<name>.cpp against the generated header +
    embedded runtime and run it with the repo on PYTHONPATH."""
    assert imperative_lib() is not None  # builds the .so lazily
    libdir = os.path.join(REPO, "incubator_mxnet_tpu", "_native")
    pylibdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or "3.12"
    exe = str(tmp_path / exe_name)
    build = subprocess.run(
        ["g++", "-std=c++17",
         os.path.join(REPO, "examples", example_dir, exe_name + ".cpp"),
         "-I" + os.path.join(REPO, "include"),
         "-I" + sysconfig.get_paths()["include"],
         "-L" + libdir, "-lmxtpu_imperative",
         "-L" + pylibdir, f"-lpython{ver}",
         "-Wl,-rpath," + libdir, "-Wl,-rpath," + pylibdir,
         "-o", exe],
        capture_output=True, text=True, timeout=240)
    assert build.returncode == 0, build.stderr[-2000:]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run([exe, str(epochs)], capture_output=True, text=True,
                         timeout=600, env=env)
    assert run.returncode == 0, (run.stdout[-800:], run.stderr[-1500:])
    assert "TRAINED" in run.stdout, run.stdout[-800:]


def test_cpp_mlp_trains(tmp_path):
    """The flagship check: a C++ MNIST-shaped MLP composes ops from the
    generated header and TRAINS (loss halves) via the embedded runtime."""
    _build_and_run_cpp_example(tmp_path, "cpp_mlp", "mlp", 40)


def test_cpp_lenet_trains(tmp_path):
    """Conv counterpart of the MLP check: Convolution/Pooling/Flatten
    compose and differentiate from C++ (ref: cpp-package/example/lenet.cpp)."""
    _build_and_run_cpp_example(tmp_path, "cpp_lenet", "lenet", 25)


def _sym_bind(lib, json_str, named, grad_names):
    names = [n for n, _ in named]
    c_names = (ctypes.c_char_p * len(names))(*[n.encode() for n in names])
    handles = (ctypes.c_void_p * len(named))(*[h.value for _, h in named])
    c_grads = (ctypes.c_char_p * max(1, len(grad_names)))(
        *[g.encode() for g in grad_names])
    ex = ctypes.c_void_p()
    rc = lib.MXTpuImpSymBind(json_str.encode(), c_names, handles,
                             len(named), c_grads, len(grad_names),
                             ctypes.byref(ex))
    assert rc == 0, lib.MXTpuImpError()
    return ex


_TINY_SYMBOL = json.dumps({
    "nodes": [
        {"op": "null", "name": "x", "attrs": {}, "inputs": []},
        {"op": "null", "name": "w", "attrs": {}, "inputs": []},
        {"op": "FullyConnected", "name": "fc",
         "attrs": {"num_hidden": "3", "no_bias": "True"},
         "inputs": [[0, 0, 0], [1, 0, 0]]},
        {"op": "sum", "name": "s", "attrs": {}, "inputs": [[2, 0, 0]]},
    ],
    "arg_nodes": [0, 1],
    "heads": [[3, 0, 0]],
    "attrs": {"framework": "incubator_mxnet_tpu", "version": "0.1"},
})


def test_sym_bind_forward_backward(lib):
    """Graph-level ABI (ref: c_api_executor.cc MXExecutorSimpleBind +
    GraphExecutor): bind a symbol JSON, run the compiled graph, take
    ones-seeded gradients — cross-checked against numpy."""
    rng = np.random.RandomState(0)
    x = rng.rand(4, 5).astype(np.float32)
    w = rng.rand(3, 5).astype(np.float32)
    hx, hw = _nd_from(lib, x), _nd_from(lib, w)
    ex = _sym_bind(lib, _TINY_SYMBOL, [("x", hx), ("w", hw)], ["w"])

    outs = (ctypes.c_void_p * 8)()
    n_out = ctypes.c_int()
    rc = lib.MXTpuImpExecForward(ex, 1, outs, 8, ctypes.byref(n_out))
    assert rc == 0, lib.MXTpuImpError()
    assert n_out.value == 1
    got = _nd_to_np(lib, ctypes.c_void_p(outs[0]), ())
    np.testing.assert_allclose(got, (x @ w.T).sum(), rtol=1e-5)

    rc = lib.MXTpuImpExecBackward(ex)
    assert rc == 0, lib.MXTpuImpError()
    g = ctypes.c_void_p()
    rc = lib.MXTpuImpExecGrad(ex, b"w", ctypes.byref(g))
    assert rc == 0, lib.MXTpuImpError()
    # d/dw sum(x @ w.T) = column-sums of x broadcast over rows of w
    want = np.tile(x.sum(axis=0), (3, 1))
    np.testing.assert_allclose(_nd_to_np(lib, g, (3, 5)), want, rtol=1e-5)

    # feeding new data through SetArg changes the next forward
    x2 = rng.rand(4, 5).astype(np.float32)
    hx2 = _nd_from(lib, x2)
    rc = lib.MXTpuImpExecSetArg(ex, b"x", hx2)
    assert rc == 0, lib.MXTpuImpError()
    rc = lib.MXTpuImpExecForward(ex, 0, outs, 8, ctypes.byref(n_out))
    assert rc == 0, lib.MXTpuImpError()
    got2 = _nd_to_np(lib, ctypes.c_void_p(outs[0]), ())
    np.testing.assert_allclose(got2, (x2 @ w.T).sum(), rtol=1e-5)
    assert lib.MXTpuImpExecFree(ex) == 0


def test_sym_bind_errors_are_clean(lib):
    """Missing args, NULL handles, and unknown grad names fail with
    messages, not crashes."""
    hx = _nd_from(lib, np.zeros((4, 5), np.float32))
    hw = _nd_from(lib, np.zeros((3, 5), np.float32))
    ex = ctypes.c_void_p()
    # missing argument 'w'
    names1 = (ctypes.c_char_p * 1)(b"x")
    handles1 = (ctypes.c_void_p * 1)(hx.value)
    grads0 = (ctypes.c_char_p * 1)()
    rc = lib.MXTpuImpSymBind(_TINY_SYMBOL.encode(), names1, handles1, 1,
                             grads0, 0, ctypes.byref(ex))
    assert rc != 0
    assert "missing" in lib.MXTpuImpError().decode()
    # NULL handle = not supplied -> same clean missing-argument error
    names2 = (ctypes.c_char_p * 2)(b"x", b"w")
    handles_null = (ctypes.c_void_p * 2)(hx.value, None)
    rc = lib.MXTpuImpSymBind(_TINY_SYMBOL.encode(), names2, handles_null, 2,
                             grads0, 0, ctypes.byref(ex))
    assert rc != 0
    assert "missing" in lib.MXTpuImpError().decode()
    # unknown grad name, ALL args present (exercises the grad validation)
    handles2 = (ctypes.c_void_p * 2)(hx.value, hw.value)
    grads1 = (ctypes.c_char_p * 1)(b"nope")
    rc = lib.MXTpuImpSymBind(_TINY_SYMBOL.encode(), names2, handles2, 2,
                             grads1, 1, ctypes.byref(ex))
    assert rc != 0
    assert "nope" in lib.MXTpuImpError().decode()


def test_imperative_hpp_decls_match_cc():
    """Every extern-C MXTpuImp* declared in the public header must be
    defined in src/imperative.cc (and vice versa) — the hand-written
    header must not drift from the runtime."""
    import re

    hpp = open(os.path.join(REPO, "include", "mxtpu_imperative.hpp")).read()
    cc = open(os.path.join(REPO, "src", "imperative.cc")).read()
    declared = set(re.findall(r"\b(MXTpuImp\w+)\(", hpp))
    defined = set(re.findall(r"^(?:int|const char\*|size_t) (MXTpuImp\w+)\(",
                             cc, re.M))
    assert declared == defined, (
        f"hpp-only={sorted(declared - defined)}, "
        f"cc-only={sorted(defined - declared)}")


def test_cpp_symbol_executor_trains(tmp_path):
    """Whole-graph compiled execution from C++: symbol JSON -> bind ->
    forward(train)/backward/sgd_update drives the loss down
    (ref: the cpp-package Symbol/Executor user contract)."""
    _build_and_run_cpp_example(tmp_path, "cpp_symbol", "symbol_mlp", 60)
