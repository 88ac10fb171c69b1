"""Example smoke tests: GANs, autoencoders, style transfer, super-resolution.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
from common import run_example as _run


def test_dcgan():
    log = _run("dcgan.py", "--iters", "8", "--batch-size", "8")
    assert "dcgan OK" in log


def test_wgan_gp():
    log = _run("wgan_gp.py", "--iters", "150", timeout=600)
    assert "wgan_gp OK" in log


def test_sn_gan():
    log = _run("sn_gan.py", "--iters", "300", timeout=520)
    assert "sn_gan OK" in log


def test_vae_gan():
    log = _run("vae_gan.py", "--iters", "40", timeout=520)
    assert "vae_gan OK" in log


def test_neural_style():
    log = _run("neural_style.py", "--iters", "25", "--size", "48")
    assert "neural_style OK" in log


def test_autoencoder():
    log = _run("autoencoder.py", "--epochs", "3")
    assert "autoencoder OK" in log


def test_super_resolution():
    log = _run("super_resolution.py", "--epochs", "4")
    assert "super_resolution OK" in log
