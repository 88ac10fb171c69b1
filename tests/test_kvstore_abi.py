"""KVStore over the embed ABI (ref: src/c_api/c_api.cc MXKVStoreCreate/
Init/PushEx/PullEx — the comm surface the reference's scala-package core
KVStore and its spark/ integration train through).

Three layers, mirroring the graph-ABI test split:
- shim-level semantics (capi_imperative.kv_*) — accumulate/allreduce-reset/
  update-on-kvstore behaviors on a 'local' store;
- ctypes against the REAL natives (marshalling, pull-into-handle identity,
  clean error paths);
- the 2-process C++ worker (examples/cpp_dist/dist_mlp.cpp) under the local
  launcher: gradient allreduce across a real process boundary from C++,
  the spark-integration role, runs always (g++ is in the CI image).
"""
import ctypes
import os
import socket
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

from incubator_mxnet_tpu import capi_imperative as capi
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu._native import imperative_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shim-level semantics
# ---------------------------------------------------------------------------


def test_kv_local_accumulate_and_pull():
    kv = capi.kv_create("local")
    assert capi.kv_type(kv) == "local"
    capi.kv_init(kv, "w", nd.zeros((2, 3)))
    capi.kv_push(kv, "w", nd.ones((2, 3)))
    capi.kv_push(kv, "w", nd.ones((2, 3)) * 2)
    out = nd.zeros((2, 3))
    capi.kv_pull(kv, "w", out)
    np.testing.assert_allclose(out.asnumpy(), 3.0)
    rank, size = capi.kv_rank_size(kv)
    assert (rank, size) == (0, 1)
    assert capi.kv_num_dead(kv) == 0
    capi.kv_barrier(kv)  # no-op single process, must not raise


def test_kv_pushpull_resets_accumulator():
    """pushPull without an optimizer = per-step allreduce: the store's
    accumulator must NOT leak into the next step."""
    kv = capi.kv_create("local")
    kv.init("g", nd.zeros((4,)))
    for step in range(3):
        out = nd.zeros((4,))
        capi.kv_pushpull(kv, "g", nd.ones((4,)) * (step + 1), out)
        np.testing.assert_allclose(out.asnumpy(), step + 1)


def test_kv_set_optimizer_applies_update():
    """After kv_set_optimizer, push APPLIES the update to the stored weight
    (update_on_kvstore semantics; ref: kvstore_dist_server.h:346
    ApplyUpdates runs the optimizer server-side)."""
    kv = capi.kv_create("local")
    w0 = np.full((3,), 5.0, np.float32)
    capi.kv_init(kv, "w", nd.array(w0))
    capi.kv_set_optimizer(kv, "sgd", '{"learning_rate": 0.5}')
    capi.kv_push(kv, "w", nd.ones((3,)))
    out = nd.zeros((3,))
    capi.kv_pull(kv, "w", out)
    np.testing.assert_allclose(out.asnumpy(), w0 - 0.5 * 1.0, rtol=1e-6)


def test_kv_set_optimizer_unknown_name_raises():
    kv = capi.kv_create("local")
    with pytest.raises(Exception):
        capi.kv_set_optimizer(kv, "definitely_not_an_optimizer", "")


# ---------------------------------------------------------------------------
# ctypes against the natives
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lib():
    lib = imperative_lib()
    assert lib is not None, "toolchain should be available in this image"
    lib.MXTpuImpError.restype = ctypes.c_char_p
    assert lib.MXTpuImpInit() == 0, lib.MXTpuImpError()
    return lib


def _mk(lib, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    dims = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    rc = lib.MXTpuImpNDCreate(0, arr.ndim, dims,
                              arr.ctypes.data_as(ctypes.c_void_p),
                              ctypes.byref(h))
    assert rc == 0, lib.MXTpuImpError()
    return h


def _readback(lib, h, shape):
    out = np.empty(shape, np.float32)
    rc = lib.MXTpuImpNDCopyTo(h, out.ctypes.data_as(ctypes.c_void_p),
                              out.nbytes)
    assert rc == 0, lib.MXTpuImpError()
    return out


def test_native_kv_roundtrip(lib):
    kv = ctypes.c_void_p()
    assert lib.MXTpuImpKVCreate(b"local", ctypes.byref(kv)) == 0, \
        lib.MXTpuImpError()
    w = _mk(lib, np.zeros((2, 2)))
    assert lib.MXTpuImpKVInit(kv, b"k", w) == 0, lib.MXTpuImpError()
    g = _mk(lib, np.full((2, 2), 1.5))
    assert lib.MXTpuImpKVPush(kv, b"k", g) == 0, lib.MXTpuImpError()
    out = _mk(lib, np.zeros((2, 2)))
    assert lib.MXTpuImpKVPull(kv, b"k", out) == 0, lib.MXTpuImpError()
    np.testing.assert_allclose(_readback(lib, out, (2, 2)), 1.5)

    rank = ctypes.c_int(-1)
    size = ctypes.c_int(-1)
    assert lib.MXTpuImpKVRankSize(kv, ctypes.byref(rank),
                                  ctypes.byref(size)) == 0
    assert (rank.value, size.value) == (0, 1)
    assert lib.MXTpuImpKVBarrier(kv) == 0
    ndead = ctypes.c_int(-1)
    assert lib.MXTpuImpKVNumDead(kv, ctypes.byref(ndead)) == 0
    assert ndead.value == 0
    for h in (w, g, out):
        lib.MXTpuImpNDFree(h)
    assert lib.MXTpuImpKVFree(kv) == 0


def test_native_kv_pushpull_and_optimizer(lib):
    kv = ctypes.c_void_p()
    assert lib.MXTpuImpKVCreate(b"local", ctypes.byref(kv)) == 0
    w = _mk(lib, np.full((3,), 2.0))
    assert lib.MXTpuImpKVInit(kv, b"w", w) == 0, lib.MXTpuImpError()
    # allreduce mode first
    g = _mk(lib, np.ones((3,)))
    out = _mk(lib, np.zeros((3,)))
    assert lib.MXTpuImpKVPushPull(kv, b"w2", g, out) == 0, \
        lib.MXTpuImpError()
    np.testing.assert_allclose(_readback(lib, out, (3,)), 1.0)
    # then update-on-kvstore
    assert lib.MXTpuImpKVSetOptimizer(
        kv, b"sgd", b'{"learning_rate": 0.25}') == 0, lib.MXTpuImpError()
    assert lib.MXTpuImpKVPush(kv, b"w", g) == 0, lib.MXTpuImpError()
    assert lib.MXTpuImpKVPull(kv, b"w", out) == 0, lib.MXTpuImpError()
    np.testing.assert_allclose(_readback(lib, out, (3,)), 2.0 - 0.25)
    for h in (w, g, out):
        lib.MXTpuImpNDFree(h)
    lib.MXTpuImpKVFree(kv)


def test_native_kv_pull_unknown_key_fails_cleanly(lib):
    kv = ctypes.c_void_p()
    assert lib.MXTpuImpKVCreate(b"local", ctypes.byref(kv)) == 0
    out = _mk(lib, np.zeros((1,)))
    rc = lib.MXTpuImpKVPull(kv, b"never_initialized", out)
    assert rc != 0
    assert b"never_initialized" in lib.MXTpuImpError()
    lib.MXTpuImpNDFree(out)
    lib.MXTpuImpKVFree(kv)


# ---------------------------------------------------------------------------
# 2-process C++ workers under the local launcher (the spark role)
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cpp_dist_mlp_two_workers(tmp_path):
    """Two C++ worker processes allreduce gradients through the embed-ABI
    KVStore (dist_sync over the launcher's communicator) and keep
    bit-identical weights — the data-parallel invariant the reference's
    spark integration relies on, proven from C++ in-suite."""
    assert imperative_lib() is not None  # builds the .so lazily
    libdir = os.path.join(REPO, "incubator_mxnet_tpu", "_native")
    pylibdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or "3.12"
    exe = str(tmp_path / "dist_mlp")
    build = subprocess.run(
        ["g++", "-std=c++17",
         os.path.join(REPO, "examples", "cpp_dist", "dist_mlp.cpp"),
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
    env.pop("XLA_FLAGS", None)  # no virtual-device override across processes
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local",
         "--coordinator", f"127.0.0.1:{_free_port()}",
         "--", exe, "15"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    log = run.stdout + run.stderr
    assert run.returncode == 0, log[-3000:]
    assert log.count("TRAINED dist_mlp") == 2, log[-3000:]
    assert "world=2" in log, log[-3000:]
