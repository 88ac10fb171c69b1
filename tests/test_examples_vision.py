"""Example smoke tests: detection, segmentation, classifier tricks, int8.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
from common import run_example as _run


def test_rcnn_proposal():
    log = _run("rcnn_proposal.py", timeout=560)
    assert "rcnn_proposal OK" in log


def test_stochastic_depth():
    log = _run("stochastic_depth.py", "--steps", "300", timeout=520)
    assert "stochastic_depth OK" in log


def test_capsnet():
    log = _run("capsnet.py", "--steps", "150")
    assert "capsnet OK" in log


def test_fcn_segmentation():
    log = _run("fcn_segmentation.py", "--steps", "200")
    assert "fcn_segmentation OK" in log


def test_captcha_multidigit():
    log = _run("captcha_multidigit.py", "--steps", "250")
    assert "captcha_multidigit OK" in log


def test_adversarial_fgsm():
    log = _run("adversarial_fgsm.py", "--epochs", "4")
    assert "adversarial_fgsm OK" in log


def test_kaggle_dsb(tmp_path):
    log = _run("kaggle_dsb.py", "--epochs", "5", "--train-size", "480",
               "--test-size", "64", "--out-dir", str(tmp_path),
               timeout=520)
    assert "kaggle_dsb OK" in log


def test_quantized_inference():
    log = _run("quantized_inference.py", "--num-epochs", "2",
               "--calib-batches", "2", timeout=520)
    assert "quantized inference OK" in log


def test_svm_mnist():
    log = _run("svm_mnist.py", "--steps", "80", "--samples", "384")
    assert "svm_mnist OK" in log


def test_dsd_pruning():
    log = _run("dsd_pruning.py", "--steps", "150", timeout=520)
    assert "dsd_pruning OK" in log


def test_embedding_learning():
    log = _run("embedding_learning.py", "--epochs", "25", timeout=520)
    assert "embedding_learning OK" in log
