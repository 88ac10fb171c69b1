"""Global random state.

The reference keeps per-device PRNG resources seeded by mx.random.seed
(ref: src/resource.cc kRandom pools, python/mxnet/random.py). TPU-native
design: a single counter-based root key; every consumer takes a fresh split,
so results are reproducible per seed and independent per call — and, under
pjit, per replica when folded with axis index.
"""
from __future__ import annotations

import threading

import jax
from jax._src.core import trace_state_clean as _trace_state_clean

__all__ = ["seed", "next_key", "current_seed", "get_state", "set_state"]

_LOCK = threading.Lock()
_SEED = 0
_COUNTER = 0

# keys are precomputed in blocks: ONE jitted vmap(fold_in) dispatch per
# _BLOCK_N calls instead of an eager threefry per call (~75us charged to
# every cached-forward invocation). The values are bit-identical to
# per-call fold_in(PRNGKey(seed), counter); the block is host-resident
# numpy so handing a key out costs no device dispatch at all.
_BLOCK_N = 256
_BLOCK = None
_BLOCK_BASE = 0
_REFILL = None


def seed(seed_state, ctx="all"):
    """Seed the global generator (ref: mx.random.seed)."""
    global _SEED, _COUNTER, _BLOCK
    with _LOCK:
        _SEED = int(seed_state)
        _COUNTER = 0
        _BLOCK = None


def current_seed():
    return _SEED


def get_state():
    """Checkpointable generator position. The whole state is (seed,
    counter) on the host — keys derive via fold_in — so restoring it
    makes every subsequent `next_key()` bit-identical (docs/
    FAULT_TOLERANCE.md — Preemption and exact resume)."""
    with _LOCK:
        return {"seed": _SEED, "counter": _COUNTER}


def set_state(state):
    """Restore a `get_state()` snapshot (exact-resume counterpart of
    `seed()`, which always rewinds the counter to 0)."""
    global _SEED, _COUNTER, _BLOCK
    with _LOCK:
        _SEED = int(state["seed"])
        _COUNTER = int(state["counter"])
        _BLOCK = None


def _refill(seed_val, start):
    global _REFILL
    if _REFILL is None:
        def fill(root, counters):
            return jax.vmap(lambda c: jax.random.fold_in(root, c))(counters)

        _REFILL = jax.jit(fill)
    import numpy as np

    counters = np.arange(start, start + _BLOCK_N, dtype=np.uint32)
    return jax.device_get(_REFILL(jax.random.PRNGKey(seed_val), counters))


def next_key():
    """Return a fresh PRNG key. The global state is (seed, counter) on the
    HOST — keys derive via fold_in, so calling inside a jax trace never leaks
    a traced key into global state. Under `key_override` (hybrid tracing) the
    overridden key is split instead."""
    global _COUNTER, _BLOCK, _BLOCK_BASE
    override = getattr(_OVERRIDE, "key", None)
    if override is not None:
        new, sub = jax.random.split(override)
        _OVERRIDE.key = new
        return sub
    with _LOCK:
        _COUNTER += 1
        c = _COUNTER
        if not _trace_state_clean():
            # inside a jit trace: derive the key as literals (a closed-over
            # constant, the pre-block behavior). Running the jitted refill
            # here would inline it into the outer trace and cache a TRACED
            # value into module state — a leaked-tracer bug.
            return jax.random.fold_in(jax.random.PRNGKey(_SEED), c)
        if _BLOCK is None or not (_BLOCK_BASE <= c < _BLOCK_BASE + _BLOCK_N):
            _BLOCK_BASE = c
            _BLOCK = _refill(_SEED, c)
        return _BLOCK[c - _BLOCK_BASE]


import contextlib as _contextlib

_OVERRIDE = threading.local()


@_contextlib.contextmanager
def key_override(key):
    """Thread an explicit key through next_key() — used while jit-tracing
    hybridized blocks so randomness is a function argument, not trace-time
    state."""
    prev = getattr(_OVERRIDE, "key", None)
    _OVERRIDE.key = key
    try:
        yield
    finally:
        _OVERRIDE.key = prev
