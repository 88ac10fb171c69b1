"""Example smoke tests: the training entry points, distributed and parallel
runs.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
import os
import subprocess
import sys

from common import REPO, run_example as _run


def test_cifar10_dist_two_workers():
    """cifar10_dist.py under the local launcher with 2 workers and
    kvstore='dist_sync' (ref: example/distributed_training/cifar10_dist.py)."""
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local",
         "--coordinator", f"127.0.0.1:{free_port()}",
         "--", sys.executable, os.path.join(REPO, "examples", "cifar10_dist.py"),
         "--ctx", "cpu", "--num-epochs", "1", "--batch-size", "32"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    log = out.stdout + out.stderr
    assert log.count("worker") >= 2 and "Epoch[0]" in log, log[-2000:]


def test_train_imagenet_synthetic_benchmark():
    """Benchmark mode on synthetic data (the reference's own smoke shape
    for train_imagenet.py) at toy scale."""
    log = _run("train_imagenet.py", "--num-layers", "20", "--batch-size", "8",
               "--num-classes", "10", "--image-shape", "3,32,32",
               "--num-batches", "4", "--kv-store", "local", timeout=560)
    assert "Epoch[0]" in log


def test_train_mnist():
    """The reference's flagship entry point (ref:
    example/image-classification/train_mnist.py:97): one epoch over the
    synthetic-MNIST fallback must reach high accuracy, proving the
    Module.fit + iterator + metric path end-to-end."""
    import re

    log = _run("train_mnist.py", "--ctx", "cpu", "--num-epochs", "1",
               "--batch-size", "50")
    m = re.search(r"final validation \[\('accuracy', ([0-9.]+)\)\]", log)
    assert m, log[-1500:]
    assert float(m.group(1)) > 0.9, log[-1500:]


def test_gluon_mnist():
    """Two epochs: epoch-0 accuracy is cumulative (includes the untrained
    early batches), so the bar is on epoch 1."""
    import re

    log = _run("gluon_mnist.py", "--epochs", "2", timeout=520)
    m = re.search(r"epoch 1 loss [0-9.]+ acc ([0-9.]+)", log)
    assert m, log[-1500:]
    assert float(m.group(1)) > 0.85, log[-1500:]


def test_gluon_mnist_hybridized():
    log = _run("gluon_mnist.py", "--epochs", "1", "--hybridize")
    assert "epoch 0" in log


def test_large_scale_training():
    log = _run("large_scale_training.py", "--updates", "8", timeout=520)
    assert "large_scale_training OK" in log


def test_mixed_precision():
    log = _run("mixed_precision.py", "--steps", "40", timeout=520)
    assert "mixed_precision OK" in log


def test_multi_axis_parallel():
    log = _run("multi_axis_parallel.py", timeout=520)
    assert "multi_axis_parallel OK" in log


def test_long_context_ring():
    log = _run("long_context_ring.py", "--seq-len", "256", "--sp", "8")
    assert "long_context_ring OK" in log


def test_long_context_ring_causal():
    log = _run("long_context_ring.py", "--seq-len", "256", "--sp", "4",
               "--causal")
    assert "long_context_ring OK" in log
