"""Example smoke tests: Bayesian and energy models, clustering, RL.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
from common import run_example as _run


def test_bayes_by_backprop():
    log = _run("bayes_by_backprop.py", "--steps", "600", timeout=500)
    assert "bayes_by_backprop OK" in log


def test_rl_reinforce():
    log = _run("rl_reinforce.py", "--episodes", "150", "--target", "60",
               timeout=600)
    assert "rl_reinforce OK" in log


def test_actor_critic():
    log = _run("actor_critic.py", "--episodes", "200", timeout=520)
    assert "actor_critic OK" in log


def test_rbm():
    log = _run("rbm_mnist.py", "--steps", "300")
    assert "rbm OK" in log


def test_deep_embedded_clustering():
    log = _run("deep_embedded_clustering.py")
    assert "deep_embedded_clustering OK" in log
