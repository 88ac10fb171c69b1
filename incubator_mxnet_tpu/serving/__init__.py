"""Online serving: continuous batching over a paged KV cache.

The reference framework ships no request-facing path (its serving story
was the C predict API over static graphs); this package is the TPU-native
one. `pages.PageAllocator` owns the global KV page pool; `engine
.ServingEngine` runs vLLM/Orca-style iteration-level scheduling: a fixed
set of decode slots advance one token per step in ONE compiled program
(`models.transformer.decode_step_paged` over
`ops.pallas_kernels.paged_decode_attention`, which gathers each slot's
live pages from the pool in HBM a 128-token block at a time, every head
in one loop step), requests admit into free
slots with bucketed prefill and evict on EOS/length with immediate page
recycling. Every shape is static, so the steady state performs zero
retraces — gated by telemetry.compilereg and warmed by compile_cache.

Three optional levers stack on that base (each knob-off
byte-identical): `pages.PrefixCache` prefix-shares page-aligned prompt
KV copy-on-write (`MXTPU_PREFIX_CACHE`), chunked prefill interleaves
prompt chunks with decode steps (`MXTPU_PREFILL_CHUNK`), and n-gram
prompt-lookup speculation verifies drafts through one wide-query
program (`MXTPU_SPEC_NGRAM`/`MXTPU_SPEC_LOOKAHEAD`).

Above the single engine sits the fault-tolerant fleet layer:
`fleet.FleetRouter` health-checks replicas by heartbeat, fails
in-flight requests over mid-stream through the `fleet.RequestJournal`
(greedy decode makes the replayed continuation token-identical), and
runs zero-drop draining rolling restarts; `gateway.ServingGateway` is
the streaming HTTP front door with tenant-fair admission control
backpressured by KV page-pool occupancy.
"""
from .pages import PageAllocator, PrefixCache  # noqa: F401
from .engine import Request, RequestResult, ServingEngine  # noqa: F401
from .fleet import (  # noqa: F401
    FleetRouter, JournalEntry, Replica, RequestJournal)
from .gateway import ServingGateway  # noqa: F401

__all__ = ["PageAllocator", "PrefixCache", "Request", "RequestResult",
           "ServingEngine", "FleetRouter", "JournalEntry", "Replica",
           "RequestJournal", "ServingGateway"]
