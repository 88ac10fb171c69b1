"""Device contexts.

TPU-native equivalent of the reference's Context (ref: include/mxnet/base.h
`Context`, python/mxnet/context.py). A Context names a JAX device; `tpu()` is
the first-class accelerator, `gpu()` aliases to the accelerator so reference
scripts run unchanged, `cpu()` is the host.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


def _accel_platform():
    """The accelerator platform jax found, or None on a CPU-only host."""
    platforms = {d.platform for d in jax.devices()}
    for p in ("tpu", "gpu", "cuda", "rocm"):
        if p in platforms:
            return p
    return None


class Context:
    """A device context: (device_type, device_id) naming one JAX device.

    Unlike the reference (where Context routes to per-device engine worker
    queues and storage managers), a Context here resolves to a `jax.Device`;
    placement/async scheduling are delegated to XLA's runtime.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 5}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            self.device_type = device_type
            self.device_id = device_id
        if self.device_type not in self.devstr2str():
            raise ValueError(f"unknown device type {self.device_type}")

    @classmethod
    def devstr2str(cls):
        return cls.devstr2type

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    # -- JAX resolution ---------------------------------------------------
    def jax_device(self):
        """Resolve to the backing jax.Device.

        Always a process-LOCAL device: under jax.distributed, jax.devices()
        includes other processes' (non-addressable) devices, and a Context
        must never place data there (the reference's Context is likewise
        process-local; cross-process movement is the kvstore's job).
        """
        dt = self.device_type
        if dt in ("cpu", "cpu_pinned"):
            try:  # CPU backend devices even when an accelerator is default
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = jax.local_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        # gpu and tpu both map onto the available accelerator — and onto
        # nothing else: a host without one is an error, never the CPU
        plat = _accel_platform()
        if plat is None:
            found = sorted({d.platform for d in jax.devices()})
            raise RuntimeError(
                f"context {self} needs an accelerator, but jax found only "
                f"platforms {found}; use mx.cpu() to run on the host")
        devs = jax.local_devices(backend=plat)
        if self.device_id >= len(devs):
            raise ValueError(
                f"device_id {self.device_id} out of range: {len(devs)} {plat} device(s)"
            )
        return devs[self.device_id]

    # -- dunder -----------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return Context("cpu", 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias context for the accelerator (kept for reference-API parity)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """The first-class accelerator context (the north-star `mx.tpu()`)."""
    return Context("tpu", device_id)


def current_context():
    return Context.default_ctx()


def num_gpus():
    return num_tpus()


def num_tpus():
    plat = _accel_platform()
    if plat is None:
        return 0
    return len(jax.devices(plat))
