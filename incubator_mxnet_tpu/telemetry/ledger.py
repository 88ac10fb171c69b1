"""HBM memory ledger: role-tagged live-bytes accounting for NDArrays.

The reference tracks allocations through its storage managers
(ref: src/storage/pooled_storage_manager.h) and can answer "what is
resident and why"; under JAX the buffers belong to PJRT, so this ledger
reconstructs the framework-side view: every tracked NDArray contributes
its bytes to a per-role total (params / grads / optimizer_state /
activations / kv_buffers), release is automatic via weakref death (or
explicit, for buffers donated to XLA before the Python object dies).

Three consumers ride the accounting:

- gauges `mxtpu_ledger_live_bytes{role=}` and `mxtpu_ledger_peak_bytes`,
  with peak attribution: `peak_info()` names the span (and phase tag)
  active when the high-watermark was set — the "what allocated at the
  peak" answer ROADMAP's bandwidth work needs.
- a leak heuristic: `step_sample()` (driven from the Trainer step
  boundary every `MXNET_TELEMETRY_LEDGER_INTERVAL` steps) fires a
  `memory_leak_suspect` flight event after `MXNET_TELEMETRY_LEAK_WINDOW`
  monotonically growing samples; any non-growing sample re-arms it, so
  a steady-state loop never trips.
- Perfetto: when MXTPU_TRACE_DIR tracing is active each sample is also
  written to the trace stream as a `kind="mem"` record, rendered by
  `tools/trace_merge.py --memory` as a counter track beside the spans.

Every entry point returns immediately while telemetry is disabled (no
registry writes, no recorder events); weakref callbacks from entries
tracked while enabled keep the *internal* byte counts consistent but
also skip the registry when the switch is off.
"""
from __future__ import annotations

import time
import weakref

from .. import config as _config
from ..analysis.sanitizers import san_lock
from .metrics import REGISTRY
from .spans import current_span
from . import distributed as _distributed
from . import recorder as _recorder

__all__ = ["track", "untrack", "donate", "live_bytes", "peak_info",
           "step_sample", "samples", "reset", "ROLES"]

ROLES = ("params", "grads", "optimizer_state", "activations", "kv_buffers",
         "embedding")

LIVE_BYTES = "mxtpu_ledger_live_bytes"
_LIVE_HELP = ("Live NDArray bytes tracked by the HBM ledger, by role "
              "(params/grads/optimizer_state/activations/kv_buffers).")
PEAK_BYTES = "mxtpu_ledger_peak_bytes"
_PEAK_HELP = ("High-watermark of ledger-tracked live bytes; "
              "ledger.peak_info() names the span active at the peak.")
LEAKS_TOTAL = "mxtpu_ledger_leak_events_total"
_LEAKS_HELP = ("Leak-heuristic firings: the tracked live set grew for "
               "MXNET_TELEMETRY_LEAK_WINDOW consecutive samples.")

_MAX_SAMPLES = 4096

_lock = san_lock("telemetry.ledger")
_entries = {}        # token (weakref | int) -> (role, nbytes, obj_id, ref)
_by_id = {}          # id(obj) -> token
_by_role = {}        # role -> live bytes
_total = 0
_peak = 0
_peak_span = None
_peak_breakdown = {}
_samples = []        # [(ts_ns, step, {role: bytes}, total)]
_growth_run = 0
_last_total = None

_enabled_fn = None


def _on():
    global _enabled_fn
    fn = _enabled_fn
    if fn is None:
        from . import enabled as fn
        _enabled_fn = fn
    return fn()


def _nbytes(obj):
    """Per-device footprint of `obj`: for an array committed to a mesh
    this is the addressable (local-shard) bytes on the most loaded
    device, NOT the global logical nbytes — a ZeRO-sharded optimizer
    state costs 1/N of its logical size per device and the HBM ledger
    must show that saving (a replicated array still reports full size:
    every device holds a whole copy)."""
    data = getattr(obj, "_data", obj)
    try:
        shards = getattr(data, "addressable_shards", None)
        if shards:
            per_device = {}
            for s in shards:
                per_device[s.device] = (per_device.get(s.device, 0)
                                        + int(s.data.nbytes))
            return max(per_device.values())
        return int(getattr(data, "nbytes", 0))
    except (TypeError, RuntimeError):
        # tracers, deleted/donated buffers, non-jax arrays mid-teardown
        try:
            return int(getattr(data, "nbytes", 0))
        except TypeError:
            return 0


def _add_locked(role, nbytes):
    """Caller holds _lock. Returns True when a new peak was set."""
    global _total, _peak, _peak_span, _peak_breakdown
    _by_role[role] = _by_role.get(role, 0) + nbytes
    _total += nbytes
    if nbytes > 0 and _total > _peak:
        _peak = _total
        sp = current_span()
        _peak_span = sp.name if sp is not None else None
        _peak_breakdown = dict(_by_role)
        return True
    return False


def _publish(role, new_peak):
    REGISTRY.gauge(LIVE_BYTES, _LIVE_HELP).set(_by_role.get(role, 0),
                                               role=role)
    if new_peak:
        REGISTRY.gauge(PEAK_BYTES, _PEAK_HELP).set_max(_peak)


def track(obj, role):
    """Start accounting `obj` (NDArray, raw array, or a tuple/list of
    them — optimizer states come as tuples) under `role`. Bytes are
    released automatically when the object is collected, or explicitly
    via untrack()/donate(). Returns the number of bytes tracked."""
    if not _on():
        return 0
    if isinstance(obj, (tuple, list)):
        return sum(track(o, role) for o in obj)
    if obj is None:
        return 0
    nbytes = _nbytes(obj)
    if nbytes <= 0:
        return 0
    obj_id = id(obj)
    ref = None
    try:
        ref = weakref.ref(obj, _dead)
        hash(ref)  # a weakref hashes via its referent...
        token = ref
    except TypeError:
        # ...and raw jax Arrays (fused optimizer states) are weakref-able
        # but UNhashable — key those entries by id and keep a ref with an
        # id-based death callback alive inside the entry instead
        token = obj_id
        try:
            ref = weakref.ref(obj, lambda _r, _i=obj_id: _dead_id(_i))
        except TypeError:
            ref = None
    with _lock:
        if obj_id in _by_id:
            return 0  # already tracked; first role wins
        _entries[token] = (role, nbytes, obj_id, ref)
        _by_id[obj_id] = token
        new_peak = _add_locked(role, nbytes)
    _publish(role, new_peak)
    return nbytes


def _release_token(token):
    with _lock:
        entry = _entries.pop(token, None)
        if entry is None:
            return None
        role, nbytes, obj_id = entry[:3]
        _by_id.pop(obj_id, None)
        _add_locked(role, -nbytes)
    return role, nbytes


def _dead(ref):
    released = _release_token(ref)
    if released is not None and _on():
        _publish(released[0], False)


def _dead_id(obj_id):
    """Death callback for id-keyed entries (unhashable referents)."""
    with _lock:
        token = _by_id.get(obj_id)
    if token is None:
        return
    released = _release_token(token)
    if released is not None and _on():
        _publish(released[0], False)


def untrack(obj):
    """Stop accounting `obj` (idempotent). Returns bytes released."""
    if isinstance(obj, (tuple, list)):
        return sum(untrack(o) for o in obj)
    with _lock:
        token = _by_id.get(id(obj))
    if token is None:
        return 0
    released = _release_token(token)
    if released is None:
        return 0
    if _on():
        _publish(released[0], False)
    return released[1]


def donate(obj):
    """Release `obj`'s bytes NOW: its buffer was donated to an XLA
    computation, so the device memory is gone even while the Python
    object lingers (jax donate_argnums semantics)."""
    return untrack(obj)


def live_bytes(role=None):
    """Current tracked bytes, for one role or in total."""
    with _lock:
        if role is None:
            return _total
        return _by_role.get(role, 0)


def peak_info():
    """The high-watermark: bytes, the span active when it was set (None
    when outside any span), and the per-role breakdown at that moment."""
    with _lock:
        return {"peak_bytes": _peak, "span": _peak_span,
                "breakdown": dict(_peak_breakdown)}


def step_sample(step):
    """Sample the live set at a step boundary: refresh role gauges, feed
    the leak heuristic, and mirror to the trace stream when distributed
    tracing is on. Driven by memory.step_boundary every
    MXNET_TELEMETRY_LEDGER_INTERVAL steps."""
    global _growth_run, _last_total
    if not _on():
        return
    with _lock:
        role_bytes = {r: _by_role.get(r, 0) for r in ROLES}
        for extra in _by_role:
            if extra not in role_bytes:
                role_bytes[extra] = _by_role[extra]
        total = _total
        _samples.append((time.time_ns(), int(step), role_bytes, total))
        del _samples[:-_MAX_SAMPLES]
        leak_window = int(_config.get("MXNET_TELEMETRY_LEAK_WINDOW"))
        fired = False
        if leak_window > 0:
            if _last_total is not None and total > _last_total:
                _growth_run += 1
            else:
                _growth_run = 0
            _last_total = total
            if _growth_run >= leak_window:
                fired = True
                run = _growth_run
                _growth_run = 0  # re-arm: fire again only after a new run
    g = REGISTRY.gauge(LIVE_BYTES, _LIVE_HELP)
    for role, b in role_bytes.items():
        g.set(b, role=role)
    REGISTRY.gauge(PEAK_BYTES, _PEAK_HELP).set_max(_peak)
    if fired:
        REGISTRY.counter(LEAKS_TOTAL, _LEAKS_HELP).inc()
        _recorder.log_event(
            "memory_leak_suspect", step=int(step), total_bytes=int(total),
            growing_samples=run,
            roles={r: int(b) for r, b in sorted(role_bytes.items()) if b})
    if _distributed.trace_active():
        _distributed.record_span({
            "kind": "mem", "name": "hbm_ledger", "ts": time.time_ns(),
            "bytes": {r: int(b) for r, b in role_bytes.items()},
            "total": int(total)})


def samples():
    """Copy of the retained step samples:
    [(ts_ns, step, {role: bytes}, total_bytes), ...]."""
    with _lock:
        return list(_samples)


def reset():
    """Forget everything tracked (tests). Live objects stay alive; their
    later weakref deaths find no entry and are no-ops."""
    global _total, _peak, _peak_span, _peak_breakdown, _growth_run, \
        _last_total
    with _lock:
        _entries.clear()
        _by_id.clear()
        _by_role.clear()
        _total = 0
        _peak = 0
        _peak_span = None
        _peak_breakdown = {}
        del _samples[:]
        _growth_run = 0
        _last_total = None
