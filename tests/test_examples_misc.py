"""Example smoke tests: recommenders, regression, forecasting, the framework
demos (profiler, module chain, custom op), and the invariant that every
example has a smoke test in one of these files.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
import os

from common import REPO, run_example as _run


def test_matrix_factorization():
    log = _run("matrix_factorization.py", "--epochs", "2",
               "--samples", "1024", "--num-users", "128",
               "--num-items", "64")
    assert "matrix_factorization OK" in log
    assert "sparse rows/step" in log


def test_recommender_bpr():
    log = _run("recommender_bpr.py", "--steps", "300")
    assert "recommender_bpr OK" in log


def test_house_prices():
    log = _run("house_prices.py", "--samples", "300", "--epochs", "30",
               "--k", "3", timeout=520)
    assert "house_prices OK" in log


def test_multi_task():
    log = _run("multi_task.py", "--steps", "150")
    assert "multi_task OK" in log


def test_svrg_regression():
    log = _run("svrg_regression.py", "--epochs", "6", "--samples", "256")
    assert "svrg_regression OK" in log


def test_time_series_forecast():
    log = _run("time_series_forecast.py", "--steps", "300", timeout=500)
    assert "time_series_forecast OK" in log


def test_profiler_demo():
    log = _run("profiler_demo.py", "--steps", "12")
    assert "profiler_demo OK" in log


def test_module_chain():
    log = _run("module_chain.py", "--epochs", "6")
    assert "module_chain OK" in log


def test_custom_op_numpy():
    log = _run("custom_op_numpy.py", "--steps", "200")
    assert "custom_op_numpy OK" in log


def test_every_example_has_a_smoke_test():
    """Completeness invariant: every examples/*.py must be exercised by
    some test file (a test_examples_<family>.py, or test_sparse.py /
    test_ssd.py which drive sparse_linear.py and train_ssd.py;
    c_train/c_predict/cpp_* dirs are driven by the C-ABI test files)."""
    import glob
    import re

    tests = os.path.join(REPO, "tests")
    covered = {"cifar10_dist.py"}  # launcher-driven in test_examples_training
    for path in glob.glob(os.path.join(tests, "test_examples_*.py")):
        covered |= set(re.findall(r'_run\("(\w+\.py)"',
                                  open(path).read()))
    for extra in ("test_sparse.py", "test_ssd.py"):
        src = open(os.path.join(tests, extra)).read()
        covered |= set(re.findall(r'examples[/"], "(\w+\.py)"', src))
        covered |= {m + ".py" for m in re.findall(r'examples/(\w+)\.py', src)}
        covered |= {m + ".py"
                    for m in re.findall(r'from examples\.(\w+) import', src)}
        covered |= set(re.findall(r'"(\w+\.py)"', src)) & {
            "sparse_linear.py", "train_ssd.py"}
    missing = sorted(
        f for f in os.listdir(os.path.join(REPO, "examples"))
        if f.endswith(".py") and f not in covered)
    assert not missing, f"examples without smoke tests: {missing}"
