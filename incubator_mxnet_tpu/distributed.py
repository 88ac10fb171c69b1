"""Multi-process bootstrap (ref: ps-lite env protocol DMLC_ROLE/
DMLC_PS_ROOT_* consumed by src/kvstore/kvstore_dist.h; launcher
tools/launch.py).

TPU-native: every process is a JAX distributed client; the launcher exports
MXTPU_COORDINATOR / MXTPU_NUM_PROCESSES / MXTPU_PROCESS_ID (plus the
reference-compatible DMLC_* names) and `init_from_env` turns them into
`jax.distributed.initialize`. Collectives then ride ICI within a host and
DCN across hosts — serverless all-reduce instead of parameter servers.
"""
from __future__ import annotations

from . import config as _config

__all__ = ["init_from_env", "is_initialized"]

_INITIALIZED = False


def is_initialized():
    return _INITIALIZED


def init_from_env():
    """Initialize jax.distributed from launcher env vars; idempotent no-op
    when unlaunched (single-process) or already initialized."""
    global _INITIALIZED
    if _INITIALIZED:
        return True
    import jax

    if jax.distributed.is_initialized():  # the user did it themselves
        _INITIALIZED = True
        return True
    coord = _config.get("MXTPU_COORDINATOR")
    nproc = _config.get("MXTPU_NUM_PROCESSES")
    if not coord or nproc <= 1:
        return False
    try:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nproc,
            process_id=_config.get("MXTPU_PROCESS_ID"),
        )
    except RuntimeError as e:
        # backend already started (a computation ran before kvstore.create):
        # too late to join the job — surface a clear message
        raise RuntimeError(
            "kvstore 'dist_*' must be created before the first computation "
            "(jax backends are already initialized); create the kvstore "
            "first or call distributed.init_from_env() at program start"
        ) from e
    _INITIALIZED = True
    return True
