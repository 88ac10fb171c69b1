"""Persistent, content-addressed executable cache + AOT compile path.

ROADMAP item 4: a single resnet50 train step costs 81 s (fp32) / 111 s
(bf16) of XLA compile time (one v5e chip, 2026-08-01; docs/PERF_ANALYSIS.md §1),
paid again on *every* process start — a fatal tax on preemption resume
(PR 8), elastic re-admits (PR 6), and serving restarts. This module
makes the second process skip XLA entirely:

- `wrap(name, jax.jit(fn), ...)` returns a `CachedJit` that, on the
  first call per shape signature, lowers the function to StableHLO,
  hashes the text (content-addressed: the *program* is the key, not the
  call site), and looks the executable up on disk before compiling.
  A hit deserializes via `jax.experimental.serialize_executable` —
  trace time is still paid, XLA compile time is not.
- `CachedJit.warm(*abstract)` is the AOT path: resolve (and populate)
  the executable from `jax.ShapeDtypeStruct`s without executing —
  `tools/warmup.py` uses it to precompile every (shape bucket x dtype)
  before the first request arrives.

Cache entries are keyed on (canonical graph hash, arg avals, backend +
device kind + device/process count, donation mask, framework+jax+jaxlib
version salt, `MXTPU_COMPILE_CACHE_SALT`), stored one file per entry
under `MXTPU_COMPILE_CACHE_DIR` with the crash-consistent write protocol
from `resilience/checkpoint.py` (tmp -> fsync -> replace + sha256
sidecar manifest) and an LRU size cap (`MXTPU_COMPILE_CACHE_MAX_MB`).
Corrupt, torn, or version-mismatched entries are evicted and the caller
silently falls back to a fresh compile — the cache can never change
numerics, only skip work.

Every site reports `mxtpu_compile_cache_{hits,misses,evictions}_total`
and attributes skipped wall-clock to `mxtpu_compile_cache_saved_seconds`
(the stored entry's measured compile time minus the deserialize cost).
Cache hits register their signature with `telemetry/compilereg.py` via
the cached path, so a fully-warm process shows **zero** compile events
and zero `mxtpu_compile_seconds` observations — the property the CI
cold-start tier gates on.

The cache trusts its directory (entries are pickles, same trust domain
as checkpoints); point `MXTPU_COMPILE_CACHE_DIR` only at storage you
control.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time

import jax
import jax.numpy as jnp

from . import config
from . import telemetry
from .resilience import checkpoint as _ckpt
from .telemetry import compilereg as _compilereg

__all__ = ["jax_cache_dir", "enable_jax_cache",
           "CachedJit", "wrap", "enabled", "cache_dir", "entry_key",
           "abstract_signature", "abstractify", "stats", "reset_stats",
           "clear",
           "HITS_TOTAL", "MISSES_TOTAL", "EVICTIONS_TOTAL", "SAVED_SECONDS"]

logger = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def jax_cache_dir():
    """Directory of JAX's own persistent compilation cache: wherever the
    environment's `JAX_COMPILATION_CACHE_DIR` points, else `.jax_cache`
    in the checkout this package was imported from. Never a temporary
    or per-process name — a cache that moves between runs cannot hit."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_jax_cache():
    """Turn on JAX's persistent compilation cache at `jax_cache_dir()`
    and return the directory. Entry points (chip_smoke.py, bench.py, the
    tools) call this once before their first compile; it is the only
    place the repo chooses that directory, and it chooses nothing when
    the environment already has."""
    d = jax_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", d)
    # the default (1 s) would leave every small program — the serving
    # prefill buckets, each Pallas kernel — to recompile in every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d

HITS_TOTAL = "mxtpu_compile_cache_hits_total"
_HITS_HELP = ("Executables served from the persistent compile cache "
              "instead of XLA, by fn.")
MISSES_TOTAL = "mxtpu_compile_cache_misses_total"
_MISSES_HELP = ("Cache lookups that fell through to a fresh XLA compile "
                "(the entry is then written back), by fn.")
EVICTIONS_TOTAL = "mxtpu_compile_cache_evictions_total"
_EVICT_HELP = ("Cache entries deleted, by reason (corrupt / version / "
               "lru / clear).")
SAVED_SECONDS = "mxtpu_compile_cache_saved_seconds"
_SAVED_HELP = ("Compile wall-clock skipped by cache hits: the stored "
               "entry's measured compile time minus the deserialize "
               "cost, by fn.")

# bump to invalidate every existing cache entry on a format change
_SCHEMA = 2
_SUFFIX = ".exe"

_stats_lock = threading.Lock()
_stats = {"hits": 0, "misses": 0, "evictions": 0, "saved_seconds": 0.0}


def stats():
    """Process-local cache counters (independent of telemetry state)."""
    with _stats_lock:
        return dict(_stats)


def reset_stats():
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0.0 if k == "saved_seconds" else 0


def _bump(key, amount=1):
    with _stats_lock:
        _stats[key] += amount


# ---------------------------------------------------------------------------
# key derivation
# ---------------------------------------------------------------------------

def _dtype_name(dt):
    """Canonical dtype spelling ('float32', 'bfloat16', ...) — the same
    normalization compilereg uses, so one program yields one key."""
    try:
        return jnp.dtype(dt).name
    except TypeError:
        return str(dt)


def abstract_signature(args):
    """Canonical aval signature of a pytree of (concrete or abstract)
    args: per-leaf (shape, dtype-name, weak_type) plus the treedef
    string. jax flattens dict keys in sorted order, so the treedef
    string is cross-process stable."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            # committed arrays fold their sharding into the signature:
            # under MXTPU_SHARD_POLICY the same train step is compiled
            # once with replicated params (first call) and once with the
            # settled sharded layout — two distinct executables that must
            # not collide on one key. Mirrors abstractify(): uncommitted
            # arrays (and plain ShapeDtypeStructs without a sharding)
            # contribute None, so AOT warm() and runtime still agree.
            if isinstance(leaf, jax.ShapeDtypeStruct):
                sharding = leaf.sharding
            elif getattr(leaf, "_committed", False):
                sharding = getattr(leaf, "sharding", None)
            else:
                sharding = None
            parts.append((tuple(leaf.shape), _dtype_name(leaf.dtype),
                          bool(getattr(leaf, "weak_type", False)),
                          str(sharding) if sharding is not None else None))
        else:
            parts.append(("py", type(leaf).__name__, repr(leaf)))
    return (tuple(parts), str(treedef))


def abstractify(tree):
    """Pytree of (possibly concrete) arrays -> `jax.ShapeDtypeStruct`s
    that lower to byte-identical StableHLO as the live values: committed
    arrays keep their sharding annotation (lowering embeds it in the
    module text), uncommitted ones drop it — so an AOT warm() and the
    later runtime call derive the SAME cache key."""
    def one(d):
        if isinstance(d, jax.ShapeDtypeStruct):
            return d
        if hasattr(d, "shape") and hasattr(d, "dtype"):
            committed = getattr(d, "_committed", False)
            sharding = getattr(d, "sharding", None) if committed else None
            return jax.ShapeDtypeStruct(d.shape, d.dtype,
                                        sharding=sharding)
        return d
    return jax.tree_util.tree_map(one, tree)


def _framework_version():
    try:
        from . import __version__
        return __version__
    except ImportError:
        return "0"


def _salts():
    """Version material folded into every key: any component bump
    invalidates the whole cache (serialized executables are not
    portable across jax/jaxlib versions)."""
    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", "?")
    except ImportError:
        jaxlib_v = "?"
    return (_framework_version(), jax.__version__, jaxlib_v,
            str(config.get("MXTPU_COMPILE_CACHE_SALT")))


def _platform_fingerprint():
    """Backend + device kind + topology: an executable compiled for one
    mesh shape or chip generation must never be served to another."""
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "?")
    except RuntimeError:
        kind = "?"
    return (jax.default_backend(), kind, jax.device_count(),
            jax.process_count())


def entry_key(fn_name, graph_hash, signature, donated=(), static_key=None):
    """Content-addressed cache key. `graph_hash` (sha256 of the
    StableHLO text) already pins program + shapes + dtypes; signature,
    donation mask, platform, and version salts are folded in explicitly
    so key semantics don't depend on what XLA happens to embed."""
    material = repr((
        "mxtpu-compile-cache", _SCHEMA, fn_name, graph_hash, signature,
        tuple(donated), static_key, _platform_fingerprint(), _salts()))
    return hashlib.sha256(material.encode()).hexdigest()


def graph_hash_of(lowered):
    """sha256 of the lowered StableHLO text — deterministic across
    processes (verified: no location info, stable symbol numbering)."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


# ---------------------------------------------------------------------------
# disk store
# ---------------------------------------------------------------------------

def cache_dir():
    return str(config.get("MXTPU_COMPILE_CACHE_DIR") or "")


def enabled():
    """True when MXTPU_COMPILE_CACHE_DIR names a cache directory."""
    return bool(cache_dir())


class _Store:
    """One directory of <key>.exe entries + sha256 sidecar manifests."""

    def __init__(self, root):
        self.root = root
        self._lock = threading.Lock()

    def path(self, key):
        return os.path.join(self.root, key + _SUFFIX)

    def get(self, key, fn_name=""):
        """-> entry dict, or None (miss / corrupt-evicted / stale)."""
        path = self.path(key)
        if not os.path.isfile(path):
            return None
        if not _ckpt.verify(path) or _ckpt.read_manifest(path) is None:
            # torn write, checksum mismatch, or a bare file someone
            # dropped in (cache entries always carry a manifest)
            self.evict(path, "corrupt", fn_name=fn_name)
            return None
        try:
            with open(path, "rb") as f:
                rec = pickle.loads(f.read())
        except Exception:
            # any unpickle failure is "corrupt"; the entry is replaced
            # by the fresh compile that follows
            self.evict(path, "corrupt", fn_name=fn_name)
            return None
        if (not isinstance(rec, dict) or rec.get("schema") != _SCHEMA
                or rec.get("salts") != _salts()):
            self.evict(path, "version", fn_name=fn_name)
            return None
        try:
            os.utime(path)  # LRU recency touch
        except OSError:
            pass
        return rec

    def put(self, key, rec, fn_name=""):
        data = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
        with self._lock:
            os.makedirs(self.root, exist_ok=True)
            _ckpt.atomic_write_bytes(self.path(key), data,
                                     site="compile_cache.write",
                                     instance=fn_name)
            self._enforce_cap()

    def evict(self, path, reason, fn_name=""):
        for p in (path, _ckpt.manifest_path(path)):
            try:
                if os.path.exists(p):
                    os.remove(p)
            except OSError:
                pass
        _bump("evictions")
        telemetry.inc(EVICTIONS_TOTAL, help=_EVICT_HELP, reason=reason,
                      fn=fn_name)

    def entries(self):
        """[(mtime, bytes incl. manifest, path)] for every entry."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            size = st.st_size
            try:
                size += os.path.getsize(_ckpt.manifest_path(path))
            except OSError:
                pass
            out.append((st.st_mtime, size, path))
        return out

    def _enforce_cap(self):
        cap_mb = float(config.get("MXTPU_COMPILE_CACHE_MAX_MB"))
        if cap_mb <= 0:
            return
        cap = cap_mb * 1024 * 1024
        entries = sorted(self.entries())
        total = sum(size for _, size, _ in entries)
        # oldest-recency first; the newest entry is never evicted (a cap
        # smaller than one executable degrades to cache-of-one, not
        # cache-of-none)
        while total > cap and len(entries) > 1:
            _, size, path = entries.pop(0)
            self.evict(path, "lru")
            total -= size


_stores = {}
_stores_lock = threading.Lock()


def _store():
    root = cache_dir()
    if not root:
        return None
    root = os.path.abspath(root)
    with _stores_lock:
        st = _stores.get(root)
        if st is None:
            st = _stores[root] = _Store(root)
        return st


def clear():
    """Delete every entry in the active cache directory (tests/tools)."""
    st = _store()
    if st is None:
        return 0
    n = 0
    for _, _, path in st.entries():
        st.evict(path, "clear")
        n += 1
    return n


# ---------------------------------------------------------------------------
# the cached jit wrapper
# ---------------------------------------------------------------------------

def _has_tracer(args):
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(args))


class CachedJit:
    """Wraps a `jax.jit(...)` callable with a persistent executable
    cache. Call it exactly like the jit; use `.warm(*abstract)` for AOT
    precompilation. Attribute access falls through to the wrapped jit,
    so `.lower(...)` etc. keep working."""

    is_cached = True

    def __init__(self, fn_name, wrapped, donated=(), static_key=None):
        self._name = fn_name
        self._wrapped = wrapped
        self._donated = tuple(donated)
        self._static_key = static_key
        self._compiled = {}   # canonical signature -> jax.stages.Compiled
        self._lock = threading.Lock()

    def __call__(self, *args):
        if _has_tracer(args):
            # traced through another jit / vjp: defer to the wrapped fn,
            # the outer program owns compilation
            return self._wrapped(*args)
        compiled = self._resolve(args)
        if compiled is None:
            return self._wrapped(*args)
        return compiled(*args)

    def warm(self, *abstract_args):
        """AOT path: resolve (and, on miss, compile + persist) the
        executable for `jax.ShapeDtypeStruct` args without executing.
        Returns "hit", "miss", "memo" (already resolved in-process), or
        "disabled"."""
        if not enabled():
            return "disabled"
        before = stats()
        sig = abstract_signature(abstract_args)
        with self._lock:
            memo = sig in self._compiled
        if memo:
            return "memo"
        if self._resolve(abstract_args) is None:
            return "disabled"
        after = stats()
        return "hit" if after["hits"] > before["hits"] else "miss"

    def aot_compile(self, *abstract_args):
        """Resolve the `jax.stages.Compiled` for abstract args via the
        cache (compiling and persisting on miss) — the AOT sibling of
        `__call__` for callers that want the executable itself
        (cost_analysis, warmup)."""
        compiled = self._resolve(abstract_args)
        if compiled is None:
            compiled = self._wrapped.lower(*abstract_args).compile()
        return compiled

    def _resolve(self, args):
        sig = abstract_signature(args)
        with self._lock:
            compiled = self._compiled.get(sig)
        if compiled is not None:
            return compiled
        st = _store()
        if st is None:
            return None
        t0 = time.perf_counter()
        try:
            lowered = self._wrapped.lower(*args)
            ghash = graph_hash_of(lowered)
        except Exception:
            logger.debug("compile cache: lowering failed for %s; "
                         "falling back to plain jit", self._name,
                         exc_info=True)
            return None
        key = entry_key(self._name, ghash, sig, donated=self._donated,
                        static_key=self._static_key)
        compiled = self._load(st, key, ghash, sig, t0)
        if compiled is None:
            compiled = self._compile_and_put(st, key, lowered, ghash,
                                             sig, t0)
        with self._lock:
            self._compiled[sig] = compiled
        return compiled

    def _load(self, st, key, ghash, sig, t0):
        rec = st.get(key, fn_name=self._name)
        if rec is None:
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load)
            # the devices the program was compiled for, in assignment
            # order: left to its default, deserialize_and_load binds the
            # executable to EVERY device of the backend, and a one-device
            # program then dies at call time on any multi-device host
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["device_ids"]])
        except Exception:
            # stale flatbuffer, partial entry the manifest missed, ...
            st.evict(st.path(key), "corrupt", fn_name=self._name)
            return None
        elapsed = time.perf_counter() - t0
        saved = max(0.0, float(rec.get("compile_s") or 0.0) - elapsed)
        _bump("hits")
        _bump("saved_seconds", saved)
        telemetry.inc(HITS_TOTAL, help=_HITS_HELP, fn=self._name)
        telemetry.inc(SAVED_SECONDS, amount=saved, help=_SAVED_HELP,
                      fn=self._name)
        # record the signature as known WITHOUT counting a compile:
        # the warm process must show zero compile events
        _compilereg.register_cached(self._name, sig, graph_hash=ghash[:16])
        return compiled

    def _compile_and_put(self, st, key, lowered, ghash, sig, t0):
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        _bump("misses")
        telemetry.inc(MISSES_TOTAL, help=_MISSES_HELP, fn=self._name)
        _compilereg.register(self._name, sig, compile_s=compile_s,
                             graph_hash=ghash[:16])
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(compiled)
            st.put(key, {
                "schema": _SCHEMA, "salts": _salts(),
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree, "fn": self._name,
                "device_ids": [
                    d.id for d in
                    compiled.runtime_executable().local_devices()],
                "graph_hash": ghash, "compile_s": compile_s,
                "created": time.time(),
            }, fn_name=self._name)
        except Exception:
            # unserializable executable (callbacks, host buffers):
            # still usable in-process, just not persisted
            logger.debug("compile cache: could not persist %s",
                         self._name, exc_info=True)
        return compiled

    def __getattr__(self, name):
        return getattr(self._wrapped, name)


def wrap(fn_name, jitted, donated=(), static_key=None):
    """Wrap a fresh `jax.jit(...)` in a CachedJit when the cache is
    enabled; return it unchanged otherwise (zero overhead when off).
    The decision is taken at wrap time — build models after setting
    `MXTPU_COMPILE_CACHE_DIR`."""
    if not enabled():
        return jitted
    return CachedJit(fn_name, jitted, donated=donated,
                     static_key=static_key)
