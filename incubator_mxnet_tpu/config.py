"""Central runtime-configuration registry.

The reference documents ~72 `MXNET_*` env knobs in docs/faq/env_var.md and
reads them via dmlc::GetEnv at use sites; this module is the equivalent
tier for the TPU framework: every environment variable the framework reads
is REGISTERED here with its type, default, and documentation, and read
through `config.get(...)`. `config.describe()` regenerates the env-var
reference (the doc-generating reflection the reference gets from
dmlc::Parameter).

Many reference knobs have no TPU analog because XLA subsumes the subsystem
they tuned (thread pools per GPU, memory-pool shapes, bulking windows);
those are listed in `SUBSUMED` with the subsuming mechanism so users
migrating from the reference can find where each knob went.
"""
from __future__ import annotations

import dataclasses
import os

__all__ = ["Knob", "KNOBS", "SUBSUMED", "get", "describe", "register_knob"]


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    default: object
    type: type
    doc: str


KNOBS: dict[str, Knob] = {}


def register_knob(name, default, type_, doc):
    KNOBS[name] = Knob(name, default, type_, doc)
    return KNOBS[name]


def get(name, default=None):
    """Read a registered knob from the environment with its typed default."""
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError(f"unregistered config knob {name!r}; add it to "
                       "incubator_mxnet_tpu/config.py")
    raw = os.environ.get(name)
    if raw is None:
        return default if default is not None else knob.default
    if knob.type is bool:
        return raw.lower() not in ("0", "false", "off", "")
    return knob.type(raw)


def describe():
    """Render the env-var reference (docs/faq/env_var.md analog)."""
    lines = ["# Environment variables", ""]
    for knob in sorted(KNOBS.values(), key=lambda k: k.name):
        lines.append(f"- `{knob.name}` (default `{knob.default}`, "
                     f"{knob.type.__name__}): {knob.doc}")
    lines += ["", "## Reference knobs subsumed by XLA/JAX", ""]
    for name, how in sorted(SUBSUMED.items()):
        lines.append(f"- `{name}`: {how}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# registry — engine / execution
# ---------------------------------------------------------------------------

register_knob("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice", str,
              "Dependency-engine implementation: ThreadedEnginePerDevice "
              "(async worker pool) or NaiveEngine (serial, for debugging "
              "races — ref: env_var.md:103).")
register_knob("MXNET_CPU_WORKER_NTHREADS", 4, int,
              "Engine worker threads for host-side ops (ref: env_var.md:42).")
register_knob("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", 64, int,
              "Max ops bulked into one engine segment (ref: env_var.md:113); "
              "on TPU the fused train step plays this role.")
register_knob("MXTPU_EAGER_JIT", False, bool,
              "Jit-compile eager op dispatches (per-(op, attrs) cache; "
              "XLA then re-specializes per input shape). Recommended for "
              "steady-shape eager loops on TPU; off by default because "
              "shape-diverse workloads pay a compile per new shape.")
register_knob("MXTPU_EAGER_JIT_CACHE_SIZE", 512, int,
              "LRU capacity of the eager-dispatch jit cache (entries; "
              "0 = unbounded). Each entry is one (op, attrs) jitted "
              "callable plus XLA's per-shape executables behind it; "
              "shape-diverse eager workloads otherwise grow the cache "
              "without limit. Read from the environment at insert time "
              "so tests can retune it at runtime; current size is "
              "exported as the mxtpu_eager_jit_cache_size gauge.")

# static analysis
register_knob("MXNET_GRAPH_VALIDATE", "off", str,
              "Opt-in graph validation at Executor bind time: 'off' "
              "(default), 'warn' (run the analysis.validate pass pipeline "
              "over the symbol being bound and log each MXA finding), or "
              "'raise' (additionally raise GraphValidationError on any "
              "error-severity finding). Findings also feed the "
              "mxtpu_graph_validate_findings_total counter when telemetry "
              "is on. See docs/STATIC_ANALYSIS.md.")

register_knob("MXTPU_SANITIZERS", "", str,
              "Comma-separated runtime sanitizers from "
              "analysis/sanitizers.py: 'locks' (san_lock primitives "
              "become instrumented — global lock-order graph with "
              "MXS001 deadlock-cycle reports, MXS002 "
              "blocking-op-under-lock, MXS003 long holds), 'pages' "
              "(shadow refcount/generation checking of every "
              "PageAllocator transition — MXS010 double free, MXS011 "
              "use-after-free, MXS012 COW violation, MXS013 leak at "
              "drain, MXS014 shadow divergence), and 'threads' (gates "
              "the MXL008-MXL010 concurrency lint in tools/sanitize.py "
              "scenarios). Empty (default) = all off: san_lock returns "
              "plain threading primitives, resolved once at lock "
              "creation — no per-acquire indirection. Findings feed "
              "mxtpu_sanitizer_findings_total and sanitizer_finding "
              "flight events. See docs/STATIC_ANALYSIS.md.")

register_knob("MXTPU_SANITIZER_HOLD_MS", 250.0, float,
              "Lock-hold-time threshold in milliseconds for the locks "
              "sanitizer: releasing a sanitized lock held longer than "
              "this emits an MXS003 long-hold finding with the "
              "acquisition site. Only read while MXTPU_SANITIZERS "
              "includes 'locks'.")

# memory traffic (see docs/PERF_ANALYSIS.md §0)
register_knob("MXTPU_FUSED_EPILOGUE", False, bool,
              "Route conv→BN→ReLU(→residual-add) chains through the Pallas "
              "NHWC epilogue kernel (ops/pallas_kernels.py:bn_act_epilogue) "
              "inside traced train steps: one HBM pass applies the BN "
              "affine, the activation, and the residual add to the conv "
              "accumulator instead of leaving the fusion decision to XLA. "
              "Off (default) keeps the XLA path bit-for-bit; off-TPU the "
              "kernel runs in interpret mode only when tests request it.")
register_knob("MXTPU_REMAT_POLICY", "", str,
              "Named jax.checkpoint_policies policy for GluonTrainStep "
              "rematerialization: 'convs' (save convolution AND matmul "
              "results, recompute cheap elementwise — the tier tuned for "
              "the HBM-saturated bf16 conv path), 'dots' (dots_saveable), "
              "'dots_no_batch' (dots_with_no_batch_dims_saveable — "
              "matmuls only; a conv net recomputes every conv under "
              "this), 'offload' (offload dot "
              "results to host memory), 'nothing' (nothing_saveable — "
              "recompute everything, the legacy remat=True behavior), "
              "'everything' (everything_saveable — no remat), or any "
              "exact jax.checkpoint_policies attribute name. A non-empty "
              "policy enables remat even without GluonTrainStep("
              "remat=True); empty (default) preserves the legacy "
              "all-or-nothing jax.checkpoint behavior.")
register_knob("MXTPU_SHARD_POLICY", "", str,
              "ZeRO sharding policy for GluonTrainStep on an explicit "
              "mesh: 'zero1' partitions optimizer state and f32 master "
              "weights over the 'data' axis (largest divisible axis per "
              "tensor, ragged tensors fall back to replication — the "
              "per-tensor decision is recorded and queryable via "
              "GluonTrainStep.shard_placements()), freeing ~(N-1)/N of "
              "optimizer+master HBM per device; 'zero2' additionally "
              "reduce-scatters gradients so the sharded update consumes "
              "only the local grad shard before all-gathering updated "
              "params — one program, no host sync, bit-identical to "
              "replicated. 'replicated' or empty (default) keeps the "
              "legacy placement and leaves compiled programs "
              "structurally identical. On the eager Trainer path the "
              "knob shards newly created optimizer-state buckets next "
              "to mesh-committed parameters. Ignored (with the legacy "
              "placement) when no mesh is attached.")

# optimizer / trainer aggregation
register_knob("MXTPU_STOCHASTIC_ROUNDING", False, bool,
              "Master-free bf16 optimizer updates: for bf16 weights under "
              "multi_precision, skip the f32 master copy and instead "
              "compute the update in f32 from the bf16 weight, then "
              "stochastically round the result back to bf16 (seeded per "
              "(step, param); the unbiased rounding replaces the master's "
              "role of accumulating sub-ulp updates). Cuts the f32 master "
              "read+write (~0.6 GB/step on ResNet-50) from optimizer "
              "traffic. Opt-in: equivalence to the f32-master path is to "
              "tolerance, not bit-exact.")
register_knob("MXNET_OPTIMIZER_AGGREGATION_SIZE", 4096, int,
              "Byte cap (in KB) of one aggregated optimizer-update bucket "
              "on the eager Trainer path: parameters are grouped into "
              "dtype-homogeneous buckets of at most this many KB and each "
              "bucket is updated by ONE jitted multi-tensor program "
              "instead of one dispatch per parameter (ref: the reference's "
              "knob of the same name, which counts tensors — default 4 — "
              "because its cost was kernel launches; here the cost is XLA "
              "program dispatches, so the cap is bytes). 0 disables "
              "aggregation (always per-param dispatch).")
register_knob("MXTPU_ALLREDUCE_BUCKET_KB", 4096, int,
              "Byte cap (in KB) of one gradient-allreduce bucket in "
              "Trainer.allreduce_grads: dense gradients are flattened into "
              "contiguous buckets of at most this many KB and each bucket "
              "crosses the kvstore as ONE pushpull instead of one per "
              "tensor (ref role: MXNET_KVSTORE_BIGARRAY_BOUND, the "
              "reference's comms-granularity knob). Sparse (row_sparse) "
              "gradients and compressed-gradient stores stay on the "
              "per-key path. 0 disables bucketing.")

# data / IO
register_knob("MXTPU_PREFETCH_BUFFER", 2, int,
              "DataIter prefetch depth (ref: prefetcher buffer_size).")
register_knob("MXTPU_DECODE_THREADS", 4, int,
              "JPEG decode/augment worker threads in ImageRecordIter "
              "(ref: preprocess_threads of iter_image_recordio_2.cc).")

# distributed / kvstore
register_knob("MXTPU_COORDINATOR", "", str,
              "host:port of the jax.distributed coordinator (set by "
              "tools/launch.py; ref role: DMLC_PS_ROOT_URI).")
register_knob("MXTPU_NUM_PROCESSES", 1, int,
              "World size for multi-process training (ref: DMLC_NUM_WORKER).")
register_knob("MXTPU_PROCESS_ID", 0, int,
              "This process's rank (ref: ps-lite rank assignment).")
register_knob("MXTPU_ASYNC_PERIOD", 16, int,
              "dist_async: pushes of a key between elastic-averaging mix "
              "points (staleness bound).")
register_knob("MXTPU_ASYNC_ALPHA", 0.5, float,
              "dist_async: mixing rate toward the cross-worker mean at a "
              "mix point.")
register_knob("MXTPU_PS_ADDR", "", str,
              "host:port of the parameter server (default: coordinator "
              "host, coordinator port + 23).")
register_knob("MXTPU_PS_SECRET", "", str,
              "Shared job secret HMAC-authenticating the parameter "
              "server's optimizer blobs (the only pickled payload on the "
              "PS wire). tools/launch.py generates and exports one; set "
              "it identically on every worker for manual launches.")
register_knob("MXTPU_HEARTBEAT_DIR", "", str,
              "Directory for worker heartbeat files (dead-node detection; "
              "default derives from MXTPU_COORDINATOR).")
register_knob("MXTPU_HEARTBEAT_INTERVAL", 2.0, float,
              "Seconds between heartbeat touches.")
register_knob("MXTPU_HEARTBEAT_TRANSPORT", "auto", str,
              "Dead-node heartbeat transport: 'tcp' (rides the PS control "
              "plane on coordinator port + 29; works cross-host), 'file' "
              "(shared-filesystem mtimes), or 'auto' (tcp when a "
              "coordinator is configured, else file).")
register_knob("MXTPU_HEARTBEAT_TIMEOUT", 20.0, float,
              "Heartbeat staleness after which a peer counts as dead "
              "(ref: ps-lite PS_HEARTBEAT_TIMEOUT).")

# resilience / fault tolerance (see docs/FAULT_TOLERANCE.md)
register_knob("MXTPU_RETRY_MAX_ATTEMPTS", 8, int,
              "Max calls (first try + retries) a resilience.RetryPolicy "
              "makes before re-raising (ref role: ps-lite resender "
              "retry bound).")
register_knob("MXTPU_RETRY_BASE_DELAY", 0.05, float,
              "Seconds slept before the first retry; attempt k sleeps "
              "base * 2**k, capped by MXTPU_RETRY_MAX_DELAY.")
register_knob("MXTPU_RETRY_MAX_DELAY", 2.0, float,
              "Upper bound (seconds) on one exponential-backoff sleep.")
register_knob("MXTPU_RETRY_DEADLINE", 120.0, float,
              "Overall wall-clock budget (seconds) across all retries of "
              "one operation; the policy re-raises rather than sleep past "
              "it.")
register_knob("MXTPU_RETRY_JITTER", 0.1, float,
              "Backoff jitter fraction: each sleep is scaled by "
              "1 + U(-j, +j) from a seeded PRNG (deterministic across "
              "runs; 0 disables).")
register_knob("MXTPU_FAULT_SPEC", "", str,
              "Deterministic fault-injection spec, `site:mode@arg` rules "
              "joined by ';' (e.g. 'ps.rpc:drop@0.05;ckpt.write:fail@2'). "
              "Modes: drop (connection), fail (IO error), torn "
              "(corrupt checkpoint), sigterm (deliver SIGTERM to self — "
              "a deterministic preemption); arg is a probability or "
              "1-based call indices. Empty (default) disables injection. "
              "See docs/FAULT_TOLERANCE.md for the grammar and sites.")
register_knob("MXTPU_FAULT_SEED", 0, int,
              "Seed for the fault injector's per-(site, instance) PRNG "
              "streams; same seed + same spec fires the same faults at "
              "the same calls.")
register_knob("MXTPU_PS_CONNECT_TIMEOUT", 30.0, float,
              "Seconds one PSClient connect attempt may take before it "
              "counts as failed and the retry policy redials.")
register_knob("MXTPU_PS_SOCKET_TIMEOUT", 320.0, float,
              "Idle timeout (seconds) on an established PSClient socket; "
              "must exceed the server-side sync/barrier wait so a blocked "
              "quorum RPC is not misread as a dead server.")
register_knob("MXTPU_PS_SYNC_TIMEOUT", 300.0, float,
              "Server-side cap (seconds) on one sync-push merge or "
              "barrier generation wait; heartbeat evictions re-evaluate "
              "the quorum well before this fires.")
register_knob("MXTPU_PS_DEDUP_WINDOW", 128, int,
              "Mutating RPCs remembered per client for exactly-once "
              "replay suppression across reconnects; must exceed the "
              "deepest pipelining a client does (the eager client "
              "pipelines 1).")
register_knob("MXTPU_MAX_WORKERS", 0, int,
              "Elastic world cap for the parameter server: join RPCs may "
              "admit brand-new ranks until num_workers reaches this value "
              "(growth commits at the next barrier boundary). 0 keeps the "
              "world fixed at the configured size; re-admission of "
              "already-known ranks is always allowed.")
register_knob("MXTPU_GUARDRAIL_POLICY", "", str,
              "Divergence guardrail in Trainer.step: when non-empty, every "
              "step runs one fused non-finite check over the gradients "
              "(a single OR-reduce on device, one host sync) BEFORE they "
              "reach the optimizer or the parameter server. 'skip' drops "
              "the poisoned update; 'backoff' additionally halves the AMP "
              "dynamic loss scale (attaching a unit-scale scaler when none "
              "is present, so later steps keep the overflow check); "
              "'rollback' raises GuardrailRollback for the training loop "
              "to restore the last good checkpoint via auto_resume. Empty "
              "(default) disables the check entirely — zero per-step "
              "cost.")
register_knob("MXTPU_CKPT_WALKBACK", 8, int,
              "How many epochs model.latest_valid_checkpoint walks back "
              "over corrupt/missing checkpoints before giving up (each "
              "skipped epoch is logged to the flight recorder). 0 walks "
              "all the way to epoch 0 — unbounded, the pre-knob "
              "behavior.")
register_knob("MXTPU_PS_BUCKET_KB", 1024, int,
              "Byte cap (KiB) of one hierarchical-allreduce bucket on "
              "dist_async_server: list-key pushpulls batch into a single "
              "push_many/pull_many RPC pair per bucket after the "
              "intra-host GSPMD reduction. 0 disables batching (one RPC "
              "pair per key).")
register_knob("MXTPU_EMBEDDING_SHARDS", "", str,
              "Comma-separated host:port list of the embedding-shard PS "
              "fleet (embedding.ShardedEmbeddingService). Row r of every "
              "sharded table lives only on server r % num_shards, so a "
              "table's HBM footprint divides across the fleet and no "
              "worker ever materializes it. Empty (default): the service "
              "must be handed explicit addresses or in-process servers "
              "(tests/bench).")
register_knob("MXTPU_SPARSE_PREFETCH", True, bool,
              "Overlap embedding-row pulls with dense compute: the "
              "sharded embedding service runs pulls and row-sparse grad "
              "pushes on an ordered background thread, so the next "
              "batch's rows stream in behind the current step's dense "
              "forward/backward (the blocking remainder is the "
              "sparse_pull stepstats phase). Off: every pull is a "
              "blocking RPC on the critical path — same math, no "
              "overlap.")

# profiler
register_knob("MXNET_PROFILER_AUTOSTART", False, bool,
              "Start profiling at import (ref: env_var.md:192).")

# distributed tracing / flight recorder (see docs/OBSERVABILITY.md)
register_knob("MXTPU_TRACE_DIR", "", str,
              "Directory for per-process binary-framed trace files "
              "(span records with trace/span/parent ids). Setting it "
              "activates cluster-wide trace export: every completed span "
              "is appended to <dir>/trace-<pid>-<suffix>.mxtrace; merge "
              "the files with tools/trace_merge.py into one "
              "Chrome-trace/Perfetto timeline. Empty (default) disables "
              "trace export.")
register_knob("MXTPU_TRACE_BUFFER_SPANS", 256, int,
              "Completed spans buffered in memory before one framed "
              "write+flush to the trace file (atexit flushes the "
              "remainder). Lower = fresher files after a crash, higher "
              "= fewer write calls on the span exit path.")
register_knob("MXTPU_FLIGHT_RECORDER_EVENTS", 4096, int,
              "Capacity of the always-on flight-recorder ring buffer "
              "(structured events: span boundaries, retries, reconnects, "
              "evictions, checkpoint writes, injected faults). The ring "
              "is a fixed-size in-memory black box costing one list "
              "store per event; 0 disables recording entirely.")
register_knob("MXTPU_FLIGHT_RECORDER_DIR", "", str,
              "Destination directory for post-mortem flight-recorder "
              "dumps (ring contents + metrics snapshot + config knobs as "
              "JSON), written when a worker dies with an uncaught "
              "exception, a retry policy exhausts, or the server evicts "
              "a rank. Empty falls back to MXTPU_TRACE_DIR; when both "
              "are empty no dump files are ever written (the ring still "
              "records).")
register_knob("MXTPU_FLIGHT_RECORDER_MAX_DUMPS", 8, int,
              "Cap on post-mortem dump files one process may write "
              "(guards against dump storms from a retry loop that "
              "exhausts repeatedly).")

# telemetry
register_knob("MXNET_TELEMETRY", False, bool,
              "Master switch for the runtime telemetry layer (metrics "
              "registry, tracing spans, exporters — see "
              "docs/OBSERVABILITY.md). Off by default; while off every "
              "instrumented site short-circuits through no-op stubs.")
register_knob("MXNET_TELEMETRY_PORT", 0, int,
              "When >0 and telemetry is enabled, serve Prometheus text "
              "exposition at http://0.0.0.0:<port>/metrics from a daemon "
              "thread (stdlib http.server; no client library needed).")
register_knob("MXNET_TELEMETRY_MEM_INTERVAL", 1, int,
              "Trainer steps between device-memory watermark samples at "
              "step boundaries (0 disables memory sampling; sampling reads "
              "device.memory_stats() plus host RSS).")
register_knob("MXNET_TELEMETRY_STEPSTATS_WINDOW", 128, int,
              "Rolling-window length (steps) for StepStats per-phase "
              "p50/p99 gauges and the step-anomaly median (performance "
              "observatory, docs/OBSERVABILITY.md).")
register_knob("MXNET_TELEMETRY_ANOMALY_FACTOR", 3.0, float,
              "A step whose wall time exceeds this multiple of the "
              "rolling median step time emits a flight-recorder "
              "step_anomaly event and bumps mxtpu_step_anomalies_total.")
register_knob("MXNET_TELEMETRY_ANOMALY_MIN_STEPS", 8, int,
              "Minimum steps in the StepStats window before anomaly "
              "detection arms (suppresses warmup/compile outliers).")
register_knob("MXNET_TELEMETRY_LEDGER_INTERVAL", 1, int,
              "Trainer steps between HBM-ledger live-set samples at step "
              "boundaries (0 disables ledger sampling and the leak "
              "heuristic; role gauges still track alloc/free).")
register_knob("MXNET_TELEMETRY_LEAK_WINDOW", 8, int,
              "Consecutive monotonically-growing ledger samples before "
              "the leak heuristic fires a memory_leak_suspect event "
              "(0 disables the heuristic).")
register_knob("MXTPU_PERF_GATE_TOLERANCE", 20.0, float,
              "Default per-metric tolerance (percent) for "
              "tools/perf_gate.py when a baseline entry carries no "
              "explicit tolerance_pct band.")

# cold start / persistent compile cache (compile_cache.py)
register_knob("MXTPU_COMPILE_CACHE_DIR", "", str,
              "Directory for the persistent, content-addressed compile "
              "cache. Empty (the default) disables caching; when set, "
              "every jit site compilereg tracks serves serialized XLA "
              "executables from disk on restart instead of recompiling "
              "(crash-consistent writes, sha256-verified loads; corrupt "
              "or version-stale entries are evicted and recompiled). "
              "Read at jit-construction time — set it before building "
              "the model.")
register_knob("MXTPU_COMPILE_CACHE_MAX_MB", 2048.0, float,
              "LRU size cap (megabytes) on the compile-cache directory; "
              "oldest-recency entries are evicted after each write until "
              "the directory fits (the newest entry is never evicted). "
              "0 or negative disables the cap.")
register_knob("MXTPU_COMPILE_CACHE_SALT", "", str,
              "Extra opaque string folded into every compile-cache key. "
              "Bump it to force a cold rebuild of the cache without "
              "deleting the directory (e.g. after an XLA flag change "
              "the key material cannot see).")

# numerics / reproducibility
register_knob("MXTPU_DEFAULT_DTYPE", "float32", str,
              "Default dtype for new NDArrays.")
register_knob("MXTPU_SPARSE_NNZ_BUCKETING", False, bool,
              "Pad sparse (data, indices) buffers along nnz to the next "
              "power-of-2 bucket (floor 16) so XLA sees a few stable "
              "shapes instead of one executable per distinct nnz. Off by "
              "default: padding trades memory/compute for compile-cache "
              "hits, which only pays on TPU with nnz-diverse batches.")

# serving (serving/engine.py — continuous batching over a paged KV cache)
register_knob("MXTPU_PAGE_SIZE", 16, int,
              "Tokens per KV-cache page in the paged decode pool "
              "(serving/pages.py). Smaller pages waste less capacity on "
              "the last partial page per sequence; paged_decode_attention "
              "reads 128 tokens' pages per loop step whatever the size "
              "(one DMA per page, so smaller pages cost more of them); "
              "a size that divides 128 fills the step; keep the page a "
              "TPU-friendly block (16 for bf16, multiples of 8).")
register_knob("MXTPU_DECODE_SLOTS", 8, int,
              "Fixed number of decode slots in the continuous-batching "
              "engine — the static batch dimension of every paged decode "
              "step. Requests beyond this wait in the queue; raising it "
              "trades per-step latency for throughput. Static so the "
              "steady-state serving loop never retraces.")
register_knob("MXTPU_SERVING_PAGES", 0, int,
              "Total pages in the serving KV pool (page 0 is the "
              "reserved null page). 0 (default) auto-sizes to "
              "slots x ceil(max_len / page_size) + 1 — every slot can "
              "hold a full-length sequence; set lower to oversubscribe "
              "HBM and let admission backpressure manage the pool.")
register_knob("MXTPU_PREFILL_BUCKETS", "", str,
              "Comma-separated prompt-length buckets for serving "
              "prefill (each bucket is one compiled program; prompts "
              "pad up to the next bucket — the "
              "MXTPU_SPARSE_NNZ_BUCKETING idea applied to sequence "
              "length). Empty (default) uses powers of two from 16 up "
              "to the model's max_len.")
register_knob("MXTPU_PREFIX_CACHE", 0, int,
              "Prefix-cached copy-on-write KV pages in the serving "
              "engine (the vLLM block-sharing design): prompts sharing "
              "a page-aligned token prefix map the cached pages "
              "read-only instead of re-prefilling them. 0 (default) "
              "disables — the engine is byte-identical to the uncached "
              "path; 1 enables with an unbounded cache (bounded only by "
              "pool pressure); >1 enables with an LRU cap of that many "
              "cached pages. Cached pages are only evicted at refcount "
              "0 (no live request mapped).")
register_knob("MXTPU_PREFILL_CHUNK", 0, int,
              "Chunked prefill (Sarathi-style): slice serving prompts "
              "into chunks of this many tokens and interleave one chunk "
              "per engine step with the batched decode, so short "
              "requests' TTFT stops hiding behind long prompts. 0 "
              "(default) disables — prompts prefill in one bucketed "
              "program at admission.")
register_knob("MXTPU_SPEC_NGRAM", 0, int,
              "N-gram length for draft-free prompt-lookup speculative "
              "decoding in the serving engine: the trailing n-gram of a "
              "request's own token history is matched against earlier "
              "history and the continuation proposed. 0 (default) "
              "disables speculation.")
register_knob("MXTPU_SPEC_LOOKAHEAD", 4, int,
              "Tokens proposed per speculative decode step (the wide "
              "verification program processes lookahead+1 query rows "
              "per slot). Only meaningful when MXTPU_SPEC_NGRAM > 0.")

# serving SLOs (telemetry/slo.py) — a threshold of 0 disables that
# objective; when every threshold is 0 the serving engine attaches no
# monitor at all (zero per-request cost)
register_knob("MXTPU_SLO_TTFT_P99", 0.0, float,
              "Serving SLO: time-to-first-token ceiling in seconds. A "
              "finished request whose TTFT exceeds this burns error "
              "budget; 0 disables the objective.")
register_knob("MXTPU_SLO_QUEUE_WAIT_P99", 0.0, float,
              "Serving SLO: queue-wait (submit to slot admission) "
              "ceiling in seconds; 0 disables the objective.")
register_knob("MXTPU_SLO_REQUEST_P99", 0.0, float,
              "Serving SLO: end-to-end request latency ceiling in "
              "seconds; 0 disables the objective.")
register_knob("MXTPU_SLO_GOODPUT_MIN", 0.0, float,
              "Serving SLO: goodput floor in [0, 1] — the fraction of "
              "processed tokens that were neither prefill padding nor "
              "spent on evicted requests. Samples BELOW the floor burn "
              "budget; 0 disables the objective.")
register_knob("MXTPU_SLO_BUDGET", 0.01, float,
              "Error budget for every SLO objective: the fraction of "
              "requests allowed to violate their threshold. Burn rate "
              "= bad_fraction / budget (burn 1.0 spends the budget "
              "exactly).")
register_knob("MXTPU_SLO_WINDOW_SHORT", 32, int,
              "Short burn-rate window in SAMPLES (finished requests). "
              "Count-based, not wall-clock, so burn math is "
              "deterministic under test.")
register_knob("MXTPU_SLO_WINDOW_LONG", 128, int,
              "Long burn-rate window in samples; breach requires BOTH "
              "windows over MXTPU_SLO_BREACH_BURN (the classic "
              "multi-window guard against paging on a blip).")
register_knob("MXTPU_SLO_MIN_SAMPLES", 8, int,
              "Samples an objective must see before the state machine "
              "may leave 'ok' (cold-start guard).")
register_knob("MXTPU_SLO_WARN_BURN", 1.0, float,
              "Short-window burn rate at which an objective enters "
              "'warning'.")
register_knob("MXTPU_SLO_BREACH_BURN", 10.0, float,
              "Burn rate both windows must reach for 'breach' (bumps "
              "mxtpu_slo_breaches_total and writes one post-mortem "
              "dump); the objective re-arms when the short window "
              "drops back below this.")
register_knob("MXTPU_SLO_DUMP_TIMELINES", 32, int,
              "Finished-request timelines the serving engine retains "
              "for the breach post-mortem dump (last N).")
register_knob("MXTPU_DEBUG_ENDPOINTS", False, bool,
              "Serve registered /debug/* JSON endpoints (e.g. "
              "/debug/engine) from the telemetry HTTP server. Off by "
              "default: introspection snapshots expose request ids and "
              "queue contents, which not every /metrics scraper should "
              "see.")

# serving fleet (serving/fleet.py + serving/gateway.py — health-checked
# routing, journaled mid-stream failover, draining rolling restarts)
register_knob("MXTPU_FLEET_HEARTBEAT_TIMEOUT", 10.0, float,
              "Seconds without a scheduler-pump heartbeat before the "
              "fleet router declares a serving replica dead and "
              "resubmits its journaled in-flight requests to the "
              "survivors. Must exceed the replica's worst-case single "
              "step (first-request compiles included) or a merely-slow "
              "replica fails over spuriously — harmless for clients "
              "(the journal dedups the zombie's late tokens) but "
              "wasteful.")
register_knob("MXTPU_FLEET_MAX_RESUBMITS", 3, int,
              "Failover resubmissions a single request may consume "
              "before the router gives up and fails it back to the "
              "client (guards against a poison request that kills "
              "every replica it lands on).")
register_knob("MXTPU_GATEWAY_PORT", 0, int,
              "TCP port for the serving HTTP gateway "
              "(serving/gateway.py). 0 (default) binds an ephemeral "
              "port — read it back from ServingGateway.port.")
register_knob("MXTPU_GATEWAY_QUEUE_LIMIT", 64, int,
              "Per-tenant router queue depth at which the gateway "
              "stops admitting that tenant's requests and answers 429 "
              "with Retry-After (bounded queueing instead of unbounded "
              "latency collapse).")
register_knob("MXTPU_GATEWAY_MAX_OCCUPANCY", 0.95, float,
              "KV page-pool occupancy (on the LEAST loaded healthy "
              "replica) above which the gateway sheds new requests "
              "with 429 — admission control backpressured by the same "
              "PageAllocator that backpressures slot admission.")
register_knob("MXTPU_GATEWAY_RETRY_AFTER", 1.0, float,
              "Retry-After seconds the gateway attaches to 429/503 "
              "responses.")
register_knob("MXTPU_GATEWAY_ACCESS_LOG", "", str,
              "Structured NDJSON access log for the serving gateway: "
              "a file path to append one JSON line per request "
              "(tenant, status, token counts, queue-wait/TTFT/latency, "
              "trace id, serving replica, failover count), '-' for "
              "stderr, empty (default) for off.")

# contrib / compatibility shims
register_knob("MXTPU_USE_TENSORRT", False, bool,
              "TensorRT-compat preference flag (contrib.tensorrt). Purely "
              "advisory on TPU: XLA compiles and fuses every bind already, "
              "so this records the script's intent rather than toggling a "
              "graph pass (ref: MXNET_USE_TENSORRT).")

# model zoo
register_knob("MXTPU_MODELS_ROOT", "", str,
              "Directory for downloaded model-zoo parameter files "
              "(default ~/.mxtpu/models; ref role: MXNET_HOME model "
              "cache).")


# Reference knobs whose role is subsumed by the XLA/JAX substrate: the
# migration map (docs/faq/env_var.md names -> what replaces them here).
SUBSUMED = {
    "MXNET_GPU_WORKER_NTHREADS": "XLA async launch + stream assignment",
    "MXNET_GPU_COPY_NTHREADS": "PJRT transfer manager",
    "MXNET_OMP_MAX_THREADS": "XLA CPU thread pool (--xla_cpu_* flags)",
    "MXNET_GPU_MEM_POOL_SIZE": "PJRT BFC allocator "
                               "(XLA_PYTHON_CLIENT_MEM_FRACTION)",
    "MXNET_GPU_MEM_POOL_TYPE": "PJRT BFC allocator",
    "MXNET_GPU_MEM_POOL_RESERVE": "XLA_PYTHON_CLIENT_PREALLOCATE",
    "MXNET_EXEC_ENABLE_INPLACE": "XLA buffer reuse + donation",
    "MXNET_BACKWARD_DO_MIRROR": "jax.checkpoint / remat policies; the "
                                "policy choice is MXTPU_REMAT_POLICY",
    "MXNET_EXEC_INPLACE_GRAD_SUM_CAP": "XLA fusion of gradient sums",
    "MXNET_KVSTORE_REDUCTION_NTHREADS": "ICI collective all-reduce",
    "MXNET_KVSTORE_BIGARRAY_BOUND": "GSPMD sharding decides partitioning; "
                                    "the comms-granularity role lives on as "
                                    "MXTPU_ALLREDUCE_BUCKET_KB",
    "MXNET_KVSTORE_USETREE": "XLA collective scheduling over ICI topology",
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": "XLA autotuning at compile time",
    "MXNET_SUBGRAPH_BACKEND": "XLA fusion passes",
    "MXNET_MKLDNN_ENABLED": "XLA:CPU oneDNN integration",
    "MXNET_SAFE_ACCUMULATION": "fp32 accumulation in bf16 matmuls "
                               "(preferred_element_type)",
}
