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
