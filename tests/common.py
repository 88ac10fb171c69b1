"""Shared test helpers (ref: tests/python/unittest/common.py)."""
import functools
import logging
import os
import random
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def with_seed(seed=None):
    """Seed decorator that logs the seed on failure (ref: common.py with_seed)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import incubator_mxnet_tpu as mx

            this_seed = seed if seed is not None else np.random.randint(0, 2**31)
            np.random.seed(this_seed)
            random.seed(this_seed)
            mx.random.seed(this_seed)
            try:
                return fn(*args, **kwargs)
            except Exception:
                logging.error("test failed with seed %d", this_seed)
                raise

        return wrapper

    return deco


def build_perl_pkg(tmp_path, repo):
    """Copy perl-package/AI-MXTpu to tmp and build it (perl Makefile.PL;
    make). One shared recipe so the predict and trainer tests can't drift.
    Returns the build dir and the env to run perl with."""
    import os
    import shutil
    import subprocess

    pkg = os.path.join(repo, "perl-package", "AI-MXTpu")
    build = str(tmp_path / "perlbuild")
    shutil.copytree(pkg, build)
    env = dict(os.environ, MXTPU_REPO=repo)
    for cmd in (["perl", "Makefile.PL"], ["make"]):
        out = subprocess.run(cmd, cwd=build, env=env, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode == 0, (cmd, out.stdout[-1500:],
                                     out.stderr[-1500:])
    return build, env


_EXAMPLE_DRIVER = """
import sys, runpy
import jax
jax.config.update("jax_platforms", "cpu")
script = sys.argv[1]
sys.argv = sys.argv[1:]
runpy.run_path(script, run_name="__main__")
"""


def run_example(example, *args, timeout=420):
    """Run examples/<example> at toy scale in its own process, on the CPU,
    and return what it printed (the reference CI runs example scripts the
    same way, ref: ci/docker/runtime_functions.sh example sections).

    Its callers are tests/test_examples_<family>.py, one file per family
    of examples, because the driver's `--dist loadfile` hands a whole
    file to one worker: a file that takes over ~300 s alone is split
    before it is the wall of tier 1 (ROADMAP.md D13)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-c", _EXAMPLE_DRIVER,
         os.path.join(REPO, "examples", example), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    # logging-based examples (train_mnist & co) report on stderr
    return out.stdout + out.stderr
