"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's pattern of retargeting the suite at a device via
default_context (ref: tests/python/unittest/common.py); multi-chip sharding
tests use the 8 virtual devices (xla_force_host_platform_device_count).
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax

# the suite always runs on the virtual CPU mesh, whatever the caller's
# JAX_PLATFORMS says; the chip is reached only through chip_smoke.py
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_all():
    import incubator_mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield


@pytest.fixture
def profiled_spans(tmp_path):
    """profiled_spans(body): runs `body` under a jax.profiler session
    started directly (not through mx.profiler) and returns the program's
    registered spans found in the .xplane.pb, one list per thread line:
    [(name, start_ns, end_ns, {stat: value})] by start, outermost first."""
    def run(body):
        import glob

        import jax
        from jax.profiler import ProfileData

        from incubator_mxnet_tpu import telemetry

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        lines = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                events = sorted(
                    (e.start_ns, -e.duration_ns, e.name, dict(e.stats))
                    for e in line.events
                    if telemetry.is_registered_span(e.name))
                if events:
                    lines.append([(n, s, s - d, st)
                                  for s, d, n, st in events])
        return lines
    return run
