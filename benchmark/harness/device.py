"""The chips a run is on, as JAX reports them, and what compiled on them."""
import sys

# jax.monitoring's event for one backend compile — or, in its place, one
# load from the persistent cache. Either inside the window is a fault.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def require_chips(chips, rehearse=False):
    """The first `chips` devices of the default backend. Exits with code 2
    and no result when that backend is no accelerator or has fewer chips
    — unless this is a rehearsal, which is what the CPU is for."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" and not rehearse:
        sys.exit(f"benchmark/run.py measures on an accelerator; jax found "
                 f"only platforms {sorted({d.platform for d in devices})}")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chip(s); jax found "
                 f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def peak_bytes(device):
    """Peak memory of one chip. The TPU runtime counts live buffers
    (`peak_bytes_in_use`) and the temporaries it reserves for the programs
    it runs (`peak_bytes_reserved`) apart; what the chip held at its
    fullest is both. (Measured on the v5e, PR 22: a program with 1 GiB of
    temporaries left peak_bytes_in_use at its 1 GiB argument and
    peak_bytes_reserved at 1 GiB.)"""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def describe(used):
    """The result line's `device`: platform, kind and count as JAX reports
    them, and the peak memory of the fullest chip used."""
    import jax

    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(peak_bytes(d) for d in used)}


class CompileCounter:
    """Counts executables built or loaded since construction."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name == _BACKEND_COMPILE:
            self.count += 1
