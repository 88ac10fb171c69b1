"""Continuous-batching decode engine over the paged KV cache.

Iteration-level scheduling (Orca) over PagedAttention-style storage
(vLLM), on the repo's own primitives:

- a FIFO request queue feeding a FIXED set of `MXTPU_DECODE_SLOTS`
  decode slots — the static batch dimension of every decode step;
- admission = all-or-nothing page allocation (serving/pages.py) for the
  request's worst case, then a BUCKETED prefill (prompt padded up to one
  of a few static lengths — the MXTPU_SPARSE_NNZ_BUCKETING idea applied
  to sequence length) writing prompt K/V straight into the pages;
- one `decode_step_paged` per engine step advances EVERY live slot one
  token, each at its own depth (per-slot positions + page-table rows);
- eviction on EOS or max-tokens recycles pages immediately — the next
  admission can reuse them without touching device memory.

Every device call has a static shape: one decode program, one prefill
program per bucket. The steady state therefore performs ZERO retraces
(compilereg-gated in CI) and a warm replica performs zero compiles
(`warm()` AOT-populates the PR 10 compile cache; tools/warmup.py
--decode drives it).

Greedy decoding (temperature 0) — token-for-token identical to
sequential `models.transformer.generate()` per request, which is the
equivalence CI asserts.

A model whose cache has no kind that grows with the context
(models.brumby: every layer carries a fixed-size state) gets no page
pool: the allocator holds the null page alone, page tables are empty
(width 0) and slots alone bound admission.

A model whose programs say `decode_ahead` (models.transformer,
models.falcon_h1 and models.brumby do) is decoded ONE STEP AHEAD while
every slot decodes, no lever is on and no live request has an `eos_id`: the step after the one in
flight is dispatched (its tokens still on the device, its positions one
further) before the one in flight is read, so the host's turn (the fetch's
latency, the bookkeeping, the caller's own reading, the next upload and
dispatch) runs beside the device and not between two of its steps. A step that would end a request
(its length is known; an `eos_id` is not, so such a request keeps the
loop synchronous) is never run ahead of, so admissions come exactly when
they did; the step that starts a run dispatches and returns no token,
which keeps what the cache holds after `step()` one row apart from what
was read, never two. Tokens are the synchronous loop's, one for one; a
caller with a free slot, a lever or an `eos_id` gets that loop, call for
call. `decode_in_flight` says whether a step is dispatched and unread (who
reads `eng.paged` after a `step()` asks it); `cache_stats()` counts the
decode steps dispatched and how many of them went ahead.

Three OPTIONAL throughput levers stack on this substrate, each
knob-off byte-identical to the base engine (no extra compiled programs,
same outputs):

- `MXTPU_PREFIX_CACHE` — prefix-cached copy-on-write pages (vLLM
  block sharing): admission looks up the longest cached page-aligned
  prefix of the prompt, maps those pages READ-ONLY into the new
  request's table (a host table write instead of device prefill) and
  prefills only the tail. A cached partial page is copied before the
  tail writes into it; a freshly-cached partial page is copied on the
  first decode write (`serving_page_copy`).
- `MXTPU_PREFILL_CHUNK` — chunked prefill (Sarathi-Serve): prompts
  stream through one wide-query program (`serving_wide_q{C}`) a chunk
  per step, interleaved with the batched decode, so short requests
  stop waiting behind long prompts.
- `MXTPU_SPEC_NGRAM` / `MXTPU_SPEC_LOOKAHEAD` — draft-free prompt
  lookup speculation: the trailing n-gram of each slot's own history
  proposes up to `lookahead` tokens; ONE wide-query call verifies all
  slots' proposals and accepted prefixes advance positions in bulk.
  Rejected tails need no rollback — their K/V lands beyond every
  live `n_valid` (dead data, overwritten by the next step's writes).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from .. import compile_cache, config, telemetry
from ..analysis import sanitizers as _sanitizers
from ..telemetry import compilereg
from ..telemetry import distributed as _dtrace
from ..telemetry import exporters as _exporters
from ..telemetry import recorder as _recorder
from ..telemetry import slo as _slo
from .pages import PageAllocator, PrefixCache

__all__ = ["Request", "RequestResult", "ServingEngine"]

QUEUE_DEPTH = "mxtpu_serving_queue_depth"
SLOTS_IN_USE = "mxtpu_serving_slots_in_use"
PAGES_IN_USE = "mxtpu_serving_pages_in_use"
PAGE_UTILIZATION = "mxtpu_serving_page_utilization"
REQUESTS_TOTAL = "mxtpu_serving_requests_total"
TOKENS_TOTAL = "mxtpu_serving_tokens_total"
REQUEST_SECONDS = "mxtpu_serving_request_seconds"
QUEUE_WAIT_SECONDS = "mxtpu_serving_queue_wait_seconds"
TTFT_SECONDS = "mxtpu_serving_ttft_seconds"
OLDEST_QUEUED = "mxtpu_serving_oldest_queued_seconds"
ADMISSION_BLOCKED = "mxtpu_serving_admission_blocked_total"
WASTED_TOKENS = "mxtpu_serving_wasted_tokens_total"
GOODPUT = "mxtpu_serving_goodput"
PREFIX_LOOKUPS = "mxtpu_serving_prefix_lookups_total"
PREFIX_TOKENS_SAVED = "mxtpu_serving_prefix_tokens_saved_total"
PREFIX_CACHED_PAGES = "mxtpu_serving_prefix_cached_pages"
COW_COPIES = "mxtpu_serving_cow_copies_total"
PREFILL_CHUNKS = "mxtpu_serving_prefill_chunks_total"
SPEC_PROPOSED = "mxtpu_spec_proposed_tokens_total"
SPEC_ACCEPTED = "mxtpu_spec_accepted_tokens_total"
DECODE_STEPS = "mxtpu_serving_decode_steps_total"

# tail-prefill chunk width when the prefix cache is on but chunked
# prefill is off: the tail still streams through the wide program (the
# bucketed prefill can only start at position 0), in fixed-width chunks
# so ONE wide signature covers every tail length
_SYNC_TAIL_CHUNK = 32

_EMPTY_PROP = np.zeros((0,), np.int32)

# per-request lifecycle record names (registered in telemetry/names.py);
# emitted straight through distributed.record_span — zero-cost when
# tracing is off, and rendered as one lane per request by
# tools/trace_merge.py --requests
REQ_SPAN = "serving.request"
REQ_QUEUED_SPAN = "serving.request.queued"
REQ_PREFILL_SPAN = "serving.request.prefill"
REQ_DECODE_SPAN = "serving.request.decode"
REQ_STEP_KIND = "req_step"  # batched decode-progress record, one per STEP

# the engine's phase spans (children of serving.prefill and
# serving.decode) and the short names they go by in a step's phase tally
PHASES = {"serving.h2d": "h2d", "serving.dispatch": "dispatch",
          "serving.fetch": "fetch", "serving.bookkeep": "bookkeep"}
# what a step() counts of itself, beside the phase tally: decode programs
# dispatched (0 or 1), those of them sent before the step in flight was
# read, flights read, requests whose prefill it ran to the first token, and
# requests that ended in it. They close `serving.step` as attributes and go
# with a `serving_step_slow` event; a reader classes a step from them (a
# plain one lands one flight, dispatches one program, admits nobody and
# ends nobody, in either loop), the engine computes no kind
STEP_COUNTS = ("dispatched", "ahead", "landed", "prefills", "finished")
# a step is slow when it took more than this many rolling medians (the
# rule of the benchmark's stall_share.sat), judged against the last
# _STEP_WINDOW steps once _STEP_MIN of them are in; the median is worked
# out again every _STEP_MIN steps
SLOW_STEP_FACTOR = 3.0
_STEP_WINDOW = 64
_STEP_MIN = 8

# sub-ms to minutes: decode steps are ms-scale, queued requests can wait
_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


@dataclasses.dataclass
class Request:
    """One generation request: greedy-decode up to `max_new_tokens`
    continuation tokens, stopping early when `eos_id` is produced
    (the EOS token is included in the output)."""
    request_id: int
    prompt: np.ndarray  # (T_p,) int32
    max_new_tokens: int
    eos_id: int | None = None
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    # when the first token reached the host (None until then), and that
    # minus submit. A caller of step() sees the token first_token_held_s
    # later, when the step that made it returns (None until it has)
    first_token_at: float | None = None
    ttft_s: float = 0.0
    first_token_held_s: float | None = None
    trace: dict | None = None  # per-request trace context (tracing on)


@dataclasses.dataclass
class RequestResult:
    request_id: int
    tokens: list  # generated continuation (includes EOS when hit)
    finish_reason: str  # "eos" | "length" | "evicted" | "cancelled"
    prompt_len: int
    queue_wait_s: float
    latency_s: float
    ttft_s: float = 0.0  # 0.0 for cancelled-in-queue requests


def _default_buckets(max_len):
    """Powers of two from 16 up to (and always including) max_len."""
    raw = str(config.get("MXTPU_PREFILL_BUCKETS") or "")
    if raw.strip():
        buckets = sorted({int(b) for b in raw.split(",") if b.strip()})
    else:
        buckets, b = [], 16
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
    return [b for b in buckets if b <= max_len] or [max_len]


class _Phase:
    """One of the engine's PHASES as a context manager: a telemetry span
    whose time on the engine's clock also adds to the running step's
    phase tally, with or without a profiler session. One instance per
    engine and phase, entered again each time (a phase never nests in
    itself), so a phase allocates nothing beyond its span."""

    __slots__ = ("_name", "_key", "_tally", "_clock", "_span", "_t0")

    def __init__(self, name, tally, clock):
        self._name, self._key = name, PHASES[name]
        self._tally, self._clock = tally, clock

    def __enter__(self):
        self._span = telemetry.span(self._name)
        self._span.__enter__()
        self._t0 = self._clock()
        return self._span

    def __exit__(self, *exc):
        self._tally[self._key] += self._clock() - self._t0
        return self._span.__exit__(*exc)


class ServingEngine:
    """Continuous-batching greedy-decode engine for one transformer.

    >>> eng = ServingEngine(params, cfg)
    >>> rid = eng.submit([1, 2, 3], max_new_tokens=16, eos_id=0)
    >>> results = eng.run()          # drain queue + slots
    >>> results[rid].tokens

    `step()` runs ONE scheduler iteration (admissions + one decode
    step) for callers that interleave serving with other work.
    """

    def __init__(self, params, cfg, *, slots=None, page_size=None,
                 num_pages=None, max_len=None, clock=time.monotonic,
                 slo=None, prefix_cache=None, prefill_chunk=None,
                 spec_ngram=None, spec_lookahead=None):
        self.params = params
        self.cfg = cfg
        self.page_size = int(page_size or config.get("MXTPU_PAGE_SIZE"))
        self.slots = int(slots or config.get("MXTPU_DECODE_SLOTS"))
        self.max_len = int(max_len or cfg.max_len)
        if self.max_len > cfg.max_len:
            raise ValueError(f"max_len {self.max_len} exceeds the "
                             f"model's positional table ({cfg.max_len})")
        # the seam: the model brings its cache (pools by layer kind,
        # per-slot state) and its programs; the engine owns no shape
        self.model = cfg.paged_programs()
        # the pool's pages hold the kinds of cache that grow with the
        # context; a model with none (every layer recurrent) gets no pool:
        # its allocator holds the null page alone, its page tables are
        # empty, admission reserves nothing and slots alone bound it
        self.has_pool = any(kind.get("grows") for kind in
                            self.model.cache_kinds(self.page_size).values())
        self.table_width = (-(-self.max_len // self.page_size)
                            if self.has_pool else 0)
        if not self.has_pool:
            num_pages = 1
        elif num_pages is None:
            num_pages = int(config.get("MXTPU_SERVING_PAGES"))
        if not num_pages:  # auto: every slot can hold a full sequence
            num_pages = self.slots * self.table_width + 1
        self.allocator = PageAllocator(num_pages, self.page_size)
        # shadow-state refcount checker (None unless MXTPU_SANITIZERS
        # lists "pages"); run() proves quiescence at drain through it
        self._page_san = (_sanitizers.attach_page_sanitizer(self.allocator)
                          if self.has_pool else None)
        self.paged = self.model.init_cache(self.slots, num_pages,
                                           self.page_size)
        self.prefill_buckets = _default_buckets(self.max_len)
        self._clock = clock
        # explicit timeline lane for this engine's trace records (fleet
        # replicas set it to their replica id so a multi-replica process
        # still renders one lane per replica); None = the process lane
        self.trace_lane = None
        # the engine lock: submit/cancel arrive from gateway and fleet
        # threads while the pump thread sits inside step(). Reentrant
        # because step() finishing a request may call back through the
        # public surface; a san_rlock so lockdep sees the ordering
        # against the fleet/journal locks.
        self._lock = _sanitizers.san_rlock("serving.engine")

        # perf levers (each defaults from its knob; constructor args
        # override for tests/benches) — all off reproduces the base
        # engine byte-for-byte: no extra jits are even constructed
        if prefix_cache is None:
            prefix_cache = int(config.get("MXTPU_PREFIX_CACHE"))
        if prefill_chunk is None:
            prefill_chunk = int(config.get("MXTPU_PREFILL_CHUNK"))
        if spec_ngram is None:
            spec_ngram = int(config.get("MXTPU_SPEC_NGRAM"))
        if spec_lookahead is None:
            spec_lookahead = int(config.get("MXTPU_SPEC_LOOKAHEAD"))
        if self.model.recurrent_state:
            levers = [name for name, on in (
                ("prefix_cache", prefix_cache),
                ("prefill_chunk", prefill_chunk),
                ("spec_ngram", spec_ngram)) if on]
            if levers:
                raise ValueError(
                    f"{', '.join(levers)} cannot serve "
                    f"{type(cfg).__name__}: its layers carry fixed-size "
                    f"recurrent state per slot, and the engine has no "
                    f"state snapshots to resume a cached prefix from, to "
                    f"hand a prompt on between chunks, or to roll "
                    f"rejected speculation back out of")
        self.prefill_chunk = max(0, min(int(prefill_chunk), self.max_len))
        self.spec_ngram = max(0, int(spec_ngram))
        self.spec_lookahead = max(1, int(spec_lookahead))
        self.prefix_cache = (
            PrefixCache(self.allocator,
                        max_pages=prefix_cache if prefix_cache > 1 else 0)
            if prefix_cache else None)

        S, W = self.slots, self.table_width
        self._tables = np.zeros((S, W), np.int32)
        self._positions = np.zeros((S,), np.int32)
        self._next_tok = np.zeros((S,), np.int32)
        self._slot_req: list[Request | None] = [None] * S
        self._slot_pages: list[list] = [[] for _ in range(S)]
        self._slot_out: list[list] = [[] for _ in range(S)]
        # lever slot state: pending chunked-prefill descriptor, and the
        # table index whose page must copy-on-write before the slot's
        # next decode write (-1 = none)
        self._slot_prefill: list[dict | None] = [None] * S
        self._slot_cow_idx = [-1] * S
        # the decode step dispatched and not yet read, (live slots, tokens
        # on the device): only for a model that decodes ahead, levers off
        self._flight = None
        self._queue: deque[Request] = deque()
        self._results: dict[int, RequestResult] = {}
        self._ids = itertools.count()
        self.steps = 0
        # the running step's seconds by phase and what it did
        # (STEP_COUNTS), and one reusable context manager per phase that
        # adds to the first
        self._tally = dict.fromkeys(PHASES.values(), 0.0)
        self._did = dict.fromkeys(STEP_COUNTS, 0)
        self._h2d, self._dispatch, self._fetch, self._bookkeep = (
            _Phase(name, self._tally, clock) for name in PHASES)
        # durations of the last steps that ran a program (the slow-step
        # rule's rolling median), and the requests whose first token the
        # running step made, with the finish records that wait for it
        self._step_s: deque = deque(maxlen=_STEP_WINDOW)
        self._median_s = None
        # the slow steps so far: how many, what they took beyond the
        # median in all, and the last one's event (/debug/engine)
        self._slow_steps = {"count": 0, "excess_s": 0.0, "last": None}
        self._first_tokens: list = []
        self._held_finishes: list = []

        # host-side goodput accounting (source of truth independent of
        # whether the metrics registry is enabled): device token-position
        # kinds, plus tokens spent on requests later evicted mid-stream
        self._tokens = {"prefill": 0, "decode": 0, "pad": 0,
                        "spec_rejected": 0}
        self._wasted_evicted = 0
        # tokens the decode steps attended, by the model's kinds of cache
        # (what a kernel's least bytes are worked out from)
        self._attended = dict.fromkeys(self.model.attended(()), 0)
        # and the tokens they fetched for it: whole blocks, tails masked
        self._fetched = dict.fromkeys(
            self.model.fetched((), self.page_size), 0)
        # dispatches of the decode program, and those of them that went
        # out before the step ahead of them was read
        self._decode_steps = 0
        self._decode_steps_ahead = 0
        # lever counters (host source of truth; mirrored to telemetry)
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._prefix_tokens_saved = 0
        self._cow_copies = 0
        self._prefill_chunks = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # last-N finished-request timelines, embedded in SLO breach dumps
        # and the /debug/engine snapshot
        self._timelines: deque = deque(
            maxlen=max(1, int(config.get("MXTPU_SLO_DUMP_TIMELINES"))))
        if slo is None:
            slo = _slo.from_env(timelines=self.recent_timelines)
        self.slo = slo or None
        _exporters.register_debug_handler("/debug/engine",
                                          self.debug_snapshot)

        # the donated pool is every program's input, loop carry and
        # output in one allocation (models.transformer._paged_layers);
        # CPU buffers aren't donatable (jax warns and copies anyway)
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._decode = compile_cache.wrap(
            "serving_decode_step",
            jax.jit(self._decode_fn, donate_argnums=donate),
            donated=donate)
        # one jit per bucket: the bucket length is baked into the prompt
        # shape, so each T_b is its own named executable for compilereg,
        # the compile cache, and warmup
        self._prefills = {
            T_b: compile_cache.wrap(
                f"serving_prefill_b{T_b}",
                jax.jit(self._prefill_fn, donate_argnums=donate),
                donated=donate, static_key=T_b)
            for T_b in self.prefill_buckets}
        # lever programs are built LAZILY (and the page-copy jit only
        # when the prefix cache is on) so an all-knobs-off engine
        # registers exactly the legacy compile sites
        self._donate = donate
        self._wides: dict = {}
        if self.prefix_cache is not None:
            copy_donate = (0,) if donate else ()
            self._page_copy = compile_cache.wrap(
                "serving_page_copy",
                jax.jit(self._copy_fn, donate_argnums=copy_donate),
                donated=copy_donate)

    # -- jitted programs ---------------------------------------------------

    # self.cfg, not self.model: the programs are traced from the
    # configuration alone (a stand-in self with only `cfg` compiles them)

    def _decode_fn(self, params, paged, tokens, positions, table):
        logits, paged = self.cfg.paged_programs().decode(
            params, paged, tokens, positions, table)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), paged

    def _prefill_fn(self, params, paged, *inputs):
        paged, logits = self.cfg.paged_programs().prefill(
            params, paged, *inputs)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), paged

    def _wide_fn(self, params, paged, tokens, start, n_real, table):
        logits, paged = self.cfg.paged_programs().wide(
            params, paged, tokens, start, n_real, table)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), paged

    def _copy_fn(self, paged, src, dst):
        return self.cfg.paged_programs().copy_page(paged, src, dst)

    def _wide(self, n_q):
        """Wide-query program for `n_q` rows per slot — one named site
        (`serving_wide_q{n_q}`) per width, so chunked prefill, prefix
        tail prefill, and speculative verification each trace exactly
        once and the steady state stays retrace-free."""
        fn = self._wides.get(n_q)
        if fn is None:
            fn = compile_cache.wrap(
                f"serving_wide_q{n_q}",
                jax.jit(self._wide_fn, donate_argnums=self._donate),
                donated=self._donate, static_key=n_q)
            self._wides[n_q] = fn
        return fn

    # -- public API --------------------------------------------------------

    def submit(self, prompt, max_new_tokens, eos_id=None, trace_ctx=None):
        """Queue one request; returns its request id. Validation is
        eager: an unservable request fails here, not mid-decode.

        `trace_ctx` is an optional inbound (trace_id, parent_span_id)
        pair — the fleet router passes its `fleet.dispatch` span so a
        failed-over request's engine spans on BOTH replicas share ONE
        trace, parented under the dispatch that placed them."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len ({self.max_len})")
        need = self._pages_for(total)
        if need > self.allocator.capacity:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.allocator.capacity}")
        # step() holds the lock throughout, so a gateway thread can wait
        # here for a whole step: the span starts before the lock
        t_call = self._clock()
        with telemetry.span("serving.submit") as sp, self._lock:
            rid = next(self._ids)
            req = Request(rid, prompt, int(max_new_tokens), eos_id,
                          submitted_at=self._clock())
            sp.set_metadata(
                request=rid,
                lock_wait_us=int(1e6 * (req.submitted_at - t_call)))
            if _dtrace.trace_active():
                # trace context is born HERE (or adopted from trace_ctx):
                # tid groups the whole lifecycle, sid is the root
                # "serving.request" span every stage parents under,
                # ns_submit anchors engine-clock deltas to wall time
                tid, psid = trace_ctx if trace_ctx else (None, None)
                req.trace = {"tid": tid or _dtrace.new_id(),
                             "sid": _dtrace.new_id(),
                             "ns_submit": time.time_ns(),
                             "clk_submit": req.submitted_at}
                if psid is not None:
                    req.trace["pid"] = psid
            self._queue.append(req)
            if telemetry.enabled():
                telemetry.set_gauge(QUEUE_DEPTH, len(self._queue))
                telemetry.set_gauge(
                    OLDEST_QUEUED,
                    self._clock() - self._queue[0].submitted_at)
            return rid

    def step(self):
        """One scheduler iteration: admit queued requests into free
        slots (FIFO, backpressured by page availability), then advance
        every live slot one token in a single decode program. Returns
        the number of live slots after the iteration."""
        with self._lock:
            t_step = self._clock()
            with telemetry.span("serving.step", step=self.steps,
                                live=self.slots_in_use,
                                queued=len(self._queue)) as step_span:
                with telemetry.span("serving.admit") as sp:
                    admitted, blocked = self._admit()
                    sp.set_metadata(admitted=admitted, blocked=blocked)
                if self.prefill_chunk:
                    self._prefill_chunks_once()
                if self.spec_ngram:
                    live = self._decode_spec_once()
                else:
                    live = self._decode_once()
                step_span.set_metadata(**self._did)
            self.steps += 1
            self._export_gauges()
            self._close_step(t_step)
            return live

    def _close_step(self, t_step):
        """The end of step(), as late as the lock allows: the moment a
        caller can first read what the step made. Stamps how long each
        first token of this step was held since it reached the host, logs
        the finish records that waited for that stamp, and judges the
        step's duration against the rolling median."""
        now = self._clock()
        for req in self._first_tokens:
            req.first_token_held_s = now - req.first_token_at
        self._first_tokens.clear()
        for args in self._held_finishes:
            self._log_finish(*args)
        self._held_finishes.clear()
        tally, did = self._tally, self._did
        if any(tally.values()):  # an idle poll is no sample of a step
            self._judge_step(t_step, now - t_step)
            for k in tally:
                tally[k] = 0.0
        for k in did:
            did[k] = 0

    def _judge_step(self, t_step, dur):
        """Logs a step that ran a program as slow when it took more than
        SLOW_STEP_FACTOR rolling medians: the always-on record of a stall,
        with where the step's time went (the phase tally), what the step
        did (STEP_COUNTS: one that carried a prefill is slow by its work,
        one that did not stalled) and `at`, its start on the engine's
        clock as `serving_request_finish` carries `submitted`."""
        recent = self._step_s
        if len(recent) >= _STEP_MIN:
            if self._median_s is None or self.steps % _STEP_MIN == 0:
                # a sort of 64 floats is 3 us: not on every step
                self._median_s = sorted(recent)[len(recent) // 2]
            median = self._median_s
            if dur > SLOW_STEP_FACTOR * median > 0:
                tally, slow = self._tally, self._slow_steps
                event = dict(
                    step=self.steps - 1, at=t_step,
                    step_s=round(dur, 6), median_s=round(median, 6),
                    phases={k: round(v, 6) for k, v in tally.items()},
                    other_s=round(dur - sum(tally.values()), 6),
                    **self._did)
                slow["count"] += 1
                slow["excess_s"] += dur - median
                slow["last"] = event
                _recorder.log_event("serving_step_slow", **event)
        recent.append(dur)

    def run(self, max_steps=100_000):
        """Drive step() until the queue and every slot drain; returns
        {request_id: RequestResult} for everything finished so far.
        `max_steps` bounds a scheduler bug (a request that can never
        finish) — hitting it raises instead of spinning forever."""
        for _ in range(max_steps):
            with self._lock:
                if not self._queue and not any(self._slot_req):
                    if self._page_san is not None:
                        # every live reference must now be owned by the
                        # prefix cache; anything else leaked (MXS013)
                        self._page_san.assert_quiescent()
                    return dict(self._results)
            self.step()
        raise RuntimeError(f"serving engine did not drain within "
                           f"{max_steps} steps")

    def results(self):
        with self._lock:
            return dict(self._results)

    def live_tokens(self):
        """{request_id: continuation tokens streamed so far} for every
        request holding a slot (mid-prefill slots report []). Queued
        requests have produced nothing and do not appear. This is the
        fleet journal's streaming tap: it is read after every pump and
        the per-request deltas forwarded to the client."""
        with self._lock:
            return {r.request_id: list(self._slot_out[s])
                    for s, r in enumerate(self._slot_req) if r is not None}

    def queued_request_ids(self):
        """Request ids still waiting in the admission queue (FIFO
        order) — the set a draining replica hands straight back to the
        router instead of finishing locally."""
        with self._lock:
            return [r.request_id for r in self._queue]

    @property
    def queue_depth(self):
        return len(self._queue)

    @property
    def slots_in_use(self):
        return sum(r is not None for r in self._slot_req)

    def warm(self):
        """AOT-precompile the decode step and every prefill bucket into
        the persistent compile cache (no execution, no buffer writes).
        Returns {site: status} with compile_cache.warm statuses."""
        S, W = self.slots, self.table_width
        a = compile_cache.abstractify
        i32 = jnp.int32
        out = {}
        if getattr(self._decode, "is_cached", False):
            out["serving_decode_step"] = self._decode.warm(
                a(self.params), a(self.paged),
                jax.ShapeDtypeStruct((S,), i32),
                jax.ShapeDtypeStruct((S,), i32),
                jax.ShapeDtypeStruct((S, W), i32))
        for T_b, fn in self._prefills.items():
            if getattr(fn, "is_cached", False):
                out[f"serving_prefill_b{T_b}"] = fn.warm(
                    a(self.params), a(self.paged),
                    *(jax.ShapeDtypeStruct(shape, i32)
                      for shape in self.model.prefill_shapes(T_b, W)))
        # lever programs: exactly the wide widths the enabled knobs
        # will call, plus the page-copy program when caching is on
        wide_qs = set()
        if self.prefill_chunk:
            wide_qs.add(self.prefill_chunk)
        elif self.prefix_cache is not None:
            wide_qs.add(min(_SYNC_TAIL_CHUNK, self.max_len))
        if self.spec_ngram:
            wide_qs.add(self.spec_lookahead + 1)
        for q in sorted(wide_qs):
            fn = self._wide(q)
            if getattr(fn, "is_cached", False):
                out[f"serving_wide_q{q}"] = fn.warm(
                    a(self.params), a(self.paged),
                    jax.ShapeDtypeStruct((S, q), i32),
                    jax.ShapeDtypeStruct((S,), i32),
                    jax.ShapeDtypeStruct((S,), i32),
                    jax.ShapeDtypeStruct((S, W), i32))
        if (self.prefix_cache is not None
                and getattr(self._page_copy, "is_cached", False)):
            out["serving_page_copy"] = self._page_copy.warm(
                a(self.paged), jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((), i32))
        return out

    # -- scheduling internals ----------------------------------------------

    def _free_slot(self):
        for s, r in enumerate(self._slot_req):
            if r is None:
                return s
        return None

    def _pages_for(self, n_tokens):
        """Pages of the pool `n_tokens` cache entries take: none where the
        model keeps no cache that grows."""
        return self.allocator.pages_needed(n_tokens) if self.has_pool else 0

    def _bucket_for(self, n):
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds the largest "
                         f"prefill bucket {self.prefill_buckets[-1]}")

    def _admit(self):
        """FIFO admission: stop at the first request that can't get a
        slot or its pages (head-of-line order keeps scheduling
        deterministic — no small request overtakes a starved big one).
        Returns how many it admitted and what stopped it ("slots",
        "pages", or "none" when the queue emptied)."""
        levered = self.prefix_cache is not None or self.prefill_chunk
        admitted = 0
        while self._queue:
            slot = self._free_slot()
            if slot is None:
                telemetry.inc(ADMISSION_BLOCKED, reason="slots")
                return admitted, "slots"
            req = self._queue[0]
            if levered:
                if not self._admit_levered(slot, req):
                    return admitted, "pages"  # wait for an eviction
                admitted += 1
                continue
            total = req.prompt.size + req.max_new_tokens
            pages = self.allocator.alloc(self._pages_for(total),
                                         owner=req.request_id)
            if pages is None:
                telemetry.inc(ADMISSION_BLOCKED, reason="pages")
                return admitted, "pages"  # wait for an eviction
            admitted += 1
            self._queue.popleft()
            req.admitted_at = self._clock()
            telemetry.observe(QUEUE_WAIT_SECONDS,
                              req.admitted_at - req.submitted_at,
                              buckets=_LATENCY_BUCKETS)
            telemetry.set_gauge(QUEUE_DEPTH, len(self._queue))
            if req.trace is not None:
                self._emit_request_record(
                    REQ_QUEUED_SPAN, req.trace, ts=req.trace["ns_submit"],
                    dur_s=req.admitted_at - req.submitted_at,
                    pid=req.trace["sid"],
                    extra={"request": req.request_id})
            self._prefill_into(slot, req, pages)
        return admitted, "none"

    def _prefill_into(self, slot, req, pages):
        T_p = req.prompt.size
        T_b = self._bucket_for(T_p)
        row = np.asarray(
            self.allocator.table_row(pages, self.table_width), np.int32)
        prompt = np.zeros((1, T_b), np.int32)
        prompt[0, :T_p] = req.prompt
        clk_prefill = self._clock()
        # to the first token ON THE HOST: the dispatch returns at once,
        # the fetch is where the prefill's device time is waited for
        with telemetry.span(
                "serving.prefill", request=req.request_id, bucket=T_b,
                prompt_len=T_p, slot=slot,
                queue_wait_us=int(1e6 * (req.admitted_at
                                         - req.submitted_at))):
            with self._h2d:
                inputs = [jnp.asarray(a) for a in
                          self.model.prefill_inputs(prompt, T_p, row, slot)]
            with self._dispatch:
                tok, self.paged = self._prefills[T_b](
                    self.params, self.paged, *inputs)
            with self._fetch:
                first = int(np.asarray(tok)[0])
        clk_first = self._clock()
        pad = T_b - T_p
        self._tokens["prefill"] += T_p
        telemetry.inc(TOKENS_TOTAL, amount=float(T_p), kind="prefill")
        if pad:
            # padded rows run through the MXU like real tokens — they are
            # processed-but-wasted, the prefill half of the goodput split
            self._tokens["pad"] += pad
            telemetry.inc(TOKENS_TOTAL, amount=float(pad), kind="pad")
            telemetry.inc(WASTED_TOKENS, amount=float(pad),
                          reason="prefill_pad")
        req.first_token_at = clk_first
        req.ttft_s = clk_first - req.submitted_at
        self._first_tokens.append(req)
        self._did["prefills"] += 1
        telemetry.observe(TTFT_SECONDS, req.ttft_s,
                          buckets=_LATENCY_BUCKETS)
        if req.trace is not None:
            req.trace["clk_first"] = clk_first
            self._emit_request_record(
                REQ_PREFILL_SPAN, req.trace,
                ts=self._trace_ts(req.trace, clk_prefill),
                dur_s=clk_first - clk_prefill, pid=req.trace["sid"],
                extra={"request": req.request_id, "bucket": T_b,
                       "prompt_len": T_p, "pad": pad})
        self._slot_req[slot] = req
        self._slot_pages[slot] = pages
        self._slot_out[slot] = [first]
        self._tables[slot] = row
        self._positions[slot] = T_p
        self._next_tok[slot] = first
        if self._is_done(req, [first]):
            self._finish(slot)

    # -- lever path: prefix-cached COW pages + chunked prefill -------------

    def _admit_levered(self, slot, req):
        """Admission with the prefix-cache / chunked-prefill levers on:
        map the longest cached page-aligned prefix read-only into the
        slot's table (a host write instead of device prefill), allocate
        fresh pages for the rest, then stream only the uncached tail
        through the wide program — synchronously here, or one chunk per
        step when chunked prefill is on. Returns False on page
        backpressure (the request stays queued)."""
        ps = self.page_size
        T_p = req.prompt.size
        w_req = self.allocator.pages_needed(T_p + req.max_new_tokens)
        used_full, part_page, n_part = [], None, 0
        if self.prefix_cache is not None:
            full_pages, partial = self.prefix_cache.lookup(req.prompt)
            # the LAST prompt token is always recomputed — its logits
            # are the first output token, which a table write can't give
            limit = T_p - 1
            n_full = min(len(full_pages), limit // ps)
            used_full = full_pages[:n_full]
            if partial is not None and n_full == len(full_pages):
                page, chunk = partial
                n_part = min(int(chunk.size), limit - n_full * ps)
                part_page = page if n_part > 0 else None
                n_part = max(0, n_part) if part_page is not None else 0
        n_cached = len(used_full) * ps + n_part
        # references: mapped full pages are shared for the slot's whole
        # lifetime; the cached partial page is pinned only until its
        # bytes are copied into a fresh page below
        protect = used_full + ([part_page] if part_page is not None
                               else [])
        self.allocator.share(protect, owner=req.request_id)
        fresh = self.allocator.alloc(w_req - len(used_full),
                                     owner=req.request_id)
        if fresh is None and self.prefix_cache is not None:
            # pool pressure: LRU-evict cache pages no live request maps
            deficit = (w_req - len(used_full)) - self.allocator.num_free
            self.prefix_cache.evict(deficit)
            fresh = self.allocator.alloc(w_req - len(used_full),
                                         owner=req.request_id)
        if fresh is None:
            self.allocator.free(protect, owner=req.request_id)
            telemetry.inc(ADMISSION_BLOCKED, reason="pages")
            return False
        if self.prefix_cache is not None:
            self._prefix_lookups += 1
            hit = n_cached > 0
            self._prefix_hits += int(hit)
            self._prefix_tokens_saved += n_cached
            telemetry.inc(PREFIX_LOOKUPS,
                          outcome="hit" if hit else "miss")
            if n_cached:
                telemetry.inc(PREFIX_TOKENS_SAVED,
                              amount=float(n_cached))
        self._queue.popleft()
        req.admitted_at = self._clock()
        telemetry.observe(QUEUE_WAIT_SECONDS,
                          req.admitted_at - req.submitted_at,
                          buckets=_LATENCY_BUCKETS)
        telemetry.set_gauge(QUEUE_DEPTH, len(self._queue))
        if req.trace is not None:
            self._emit_request_record(
                REQ_QUEUED_SPAN, req.trace, ts=req.trace["ns_submit"],
                dur_s=req.admitted_at - req.submitted_at,
                pid=req.trace["sid"],
                extra={"request": req.request_id})
        pages = used_full + fresh
        row = np.asarray(
            self.allocator.table_row(pages, self.table_width), np.int32)
        if part_page is not None:
            # eager copy-on-write: the tail prefill writes into this
            # page's token range, so the slot gets a private copy of
            # the cached bytes first
            self.paged = self._page_copy(
                self.paged, jnp.asarray(part_page, jnp.int32),
                jnp.asarray(fresh[0], jnp.int32))
            self.allocator.free([part_page],  # drop the pin only
                                owner=req.request_id)
            self._cow_copies += 1
            telemetry.inc(COW_COPIES, site="admit")
        self._slot_req[slot] = req
        self._slot_pages[slot] = pages
        self._slot_out[slot] = []
        self._slot_prefill[slot] = {
            "prompt": req.prompt, "row": row, "pos": n_cached,
            "n_cached": n_cached, "chunks": 0,
            "clk_start": self._clock()}
        if not self.prefill_chunk:
            # synchronous tail prefill: run every chunk before the next
            # admission (chunked mode instead leaves the descriptor for
            # step() to advance one chunk per iteration)
            with telemetry.span(
                    "serving.prefill", request=req.request_id,
                    prompt_len=T_p, cached=n_cached,
                    queue_wait_us=int(1e6 * (req.admitted_at
                                             - req.submitted_at))):
                while self._slot_prefill[slot] is not None:
                    self._prefill_chunks_once(only_slot=slot)
        return True

    def _prefill_chunks_once(self, only_slot=None):
        """Advance pending prefills one chunk in ONE wide-program call
        covering every mid-prefill slot; decoding/idle slots ride along
        masked out (n_real=0, zero table rows — writes land in the null
        page), so the call shape is static."""
        pend = [s for s in range(self.slots)
                if self._slot_prefill[s] is not None
                and (only_slot is None or s == only_slot)]
        if not pend:
            return
        C = self.prefill_chunk or min(_SYNC_TAIL_CHUNK, self.max_len)
        S, W = self.slots, self.table_width
        toks = np.zeros((S, C), np.int32)
        start = np.zeros((S,), np.int32)
        n_real = np.zeros((S,), np.int32)
        tables = np.zeros((S, W), np.int32)
        for s in pend:
            st = self._slot_prefill[s]
            pos, prompt = st["pos"], st["prompt"]
            n = min(C, prompt.size - pos)
            toks[s, :n] = prompt[pos:pos + n]
            start[s] = pos
            n_real[s] = n
            tables[s] = st["row"]
        if self._page_san is not None:
            for s in pend:
                lo = int(start[s]) // self.page_size
                hi = (int(start[s]) + int(n_real[s]) - 1) // self.page_size
                self._page_san.note_write(
                    self._slot_req[s].request_id,
                    self._slot_pages[s][lo:hi + 1])
        with telemetry.span("serving.prefill_chunk", slots=len(pend)):
            with self._h2d:
                args = (jnp.asarray(toks), jnp.asarray(start),
                        jnp.asarray(n_real), jnp.asarray(tables))
            with self._dispatch:
                out, self.paged = self._wide(C)(
                    self.params, self.paged, *args)
            with self._fetch:
                out = np.asarray(out)
            with self._bookkeep:
                for s in pend:
                    self._note_chunk(s, int(n_real[s]), C, out)

    def _note_chunk(self, s, n, C, out):
        st = self._slot_prefill[s]
        st["pos"] += n
        st["chunks"] += 1
        self._prefill_chunks += 1
        self._tokens["prefill"] += n
        telemetry.inc(TOKENS_TOTAL, amount=float(n), kind="prefill")
        telemetry.inc(PREFILL_CHUNKS)
        pad = C - n
        if pad:
            self._tokens["pad"] += pad
            telemetry.inc(TOKENS_TOTAL, amount=float(pad), kind="pad")
            telemetry.inc(WASTED_TOKENS, amount=float(pad),
                          reason="prefill_pad")
        if st["pos"] >= st["prompt"].size:
            self._finish_prefill(s, int(out[s, n - 1]))

    def _finish_prefill(self, slot, first):
        """Last tail chunk done: record TTFT, install the slot's decode
        state, register the prompt's pages in the prefix cache, and arm
        the lazy copy-on-write if caching shared the page the first
        decode token will write into."""
        st = self._slot_prefill[slot]
        self._slot_prefill[slot] = None
        req = self._slot_req[slot]
        prompt = st["prompt"]
        T_p = prompt.size
        clk_first = self._clock()
        req.first_token_at = clk_first
        req.ttft_s = clk_first - req.submitted_at
        self._first_tokens.append(req)
        self._did["prefills"] += 1
        telemetry.observe(TTFT_SECONDS, req.ttft_s,
                          buckets=_LATENCY_BUCKETS)
        if req.trace is not None:
            req.trace["clk_first"] = clk_first
            self._emit_request_record(
                REQ_PREFILL_SPAN, req.trace,
                ts=self._trace_ts(req.trace, st["clk_start"]),
                dur_s=clk_first - st["clk_start"], pid=req.trace["sid"],
                extra={"request": req.request_id,
                       "prompt_len": int(T_p),
                       "cached": int(st["n_cached"]),
                       "chunks": int(st["chunks"])})
        self._slot_out[slot] = [first]
        self._tables[slot] = st["row"]
        self._positions[slot] = T_p
        self._next_tok[slot] = first
        if self.prefix_cache is not None:
            n_prompt_pages = self.allocator.pages_needed(T_p)
            self.prefix_cache.insert(
                prompt, self._slot_pages[slot][:n_prompt_pages])
            telemetry.set_gauge(PREFIX_CACHED_PAGES,
                                self.prefix_cache.cached_pages)
            # the page the first decode token (position T_p) writes
            # into: if insert() just shared the slot's own partial tail
            # page, it must copy-on-write before that write lands
            wi = T_p // self.page_size
            if (T_p % self.page_size
                    and wi < len(self._slot_pages[slot])
                    and self.allocator.refcount(
                        self._slot_pages[slot][wi]) > 1):
                self._slot_cow_idx[slot] = wi
        if self._is_done(req, [first]):
            self._finish(slot)

    def _resolve_cow(self, slot):
        """The slot's next decode write lands in a shared
        partially-filled page: give it a private page first. Fallbacks
        when the pool has no page for the copy: steal the cache's own
        reference back (the writer becomes exclusive — no copy
        needed), else LRU-evict one cached page and retry."""
        idx = self._slot_cow_idx[slot]
        self._slot_cow_idx[slot] = -1
        page = self._slot_pages[slot][idx]
        rid = self._slot_req[slot].request_id
        new = self.allocator.cow(page, owner=rid)
        if new is None:
            if self.prefix_cache.release(page):
                return  # cache ref dropped; the slot now owns the page
            if self.prefix_cache.evict(1):
                new = self.allocator.cow(page, owner=rid)
        if new is None:
            raise RuntimeError(
                f"copy-on-write of page {page} failed: KV pool "
                f"exhausted and the prefix cache holds no evictable "
                f"page")
        if new != page:
            self.paged = self._page_copy(
                self.paged, jnp.asarray(page, jnp.int32),
                jnp.asarray(new, jnp.int32))
            self._slot_pages[slot][idx] = new
            self._tables[slot, idx] = new
            self._cow_copies += 1
            telemetry.inc(COW_COPIES, site="decode")

    # -- lever path: n-gram prompt-lookup speculation ----------------------

    def _propose(self, prompt, out, k):
        """Prompt-lookup proposal: match the trailing `spec_ngram`
        tokens of the slot's history (prompt + generated) against
        earlier history and propose up to `k` continuation tokens of
        the most recent prior match."""
        n = self.spec_ngram
        hist = np.concatenate([prompt, np.asarray(out, np.int32)])
        if hist.size < n + 1:
            return _EMPTY_PROP
        gram = hist[-n:]
        for i in range(hist.size - n - 1, -1, -1):
            if np.array_equal(hist[i:i + n], gram):
                return hist[i + n:i + n + k].astype(np.int32)
        return _EMPTY_PROP

    def _decode_spec_once(self):
        """Speculative decode step: every live slot processes
        `lookahead+1` query rows in one wide program — its guaranteed
        next token plus its proposal. The longest proposal prefix
        matching the model's own greedy outputs is accepted in bulk;
        rejected rows need no rollback (their K/V sits beyond the
        slot's advanced position — dead data the next step
        overwrites)."""
        live_slots = self._decoding_slots()
        if live_slots:
            with telemetry.span("serving.decode", live=len(live_slots)):
                self._decode_spec_live(live_slots)
        return self.slots_in_use

    def _decoding_slots(self):
        return [s for s, r in enumerate(self._slot_req)
                if r is not None and self._slot_prefill[s] is None]

    def _decode_spec_live(self, live_slots):
        if self.prefix_cache is not None:
            for s in live_slots:
                if self._slot_cow_idx[s] >= 0:
                    self._resolve_cow(s)
        S = self.slots
        Q = self.spec_lookahead + 1
        toks = np.zeros((S, Q), np.int32)
        start = np.zeros((S,), np.int32)
        n_real = np.zeros((S,), np.int32)
        props = {}
        for s in live_slots:
            req = self._slot_req[s]
            room = req.max_new_tokens - len(self._slot_out[s]) - 1
            k_s = min(self.spec_lookahead, room)
            prop = (self._propose(req.prompt, self._slot_out[s], k_s)
                    if k_s > 0 else _EMPTY_PROP)
            props[s] = prop
            toks[s, 0] = self._next_tok[s]
            if prop.size:
                toks[s, 1:1 + prop.size] = prop
            start[s] = self._positions[s]
            n_real[s] = 1 + prop.size
        if self._page_san is not None:
            # rows [start, start+n_real) of each slot land in its table
            for s in live_slots:
                lo = int(start[s]) // self.page_size
                hi = (int(start[s]) + int(n_real[s]) - 1) // self.page_size
                self._page_san.note_write(
                    self._slot_req[s].request_id,
                    self._slot_pages[s][lo:hi + 1])
        with self._h2d:
            args = (jnp.asarray(toks), jnp.asarray(start),
                    jnp.asarray(n_real), jnp.asarray(self._tables))
        did = self._did
        did["dispatched"] += 1
        with self._dispatch:
            tok, self.paged = self._wide(Q)(self.params, self.paged, *args)
        did["landed"] += 1
        with self._fetch:
            tok = np.asarray(tok)
        with self._bookkeep:
            self._note_spec_tokens(live_slots, props, tok, Q)

    def _note_spec_tokens(self, live_slots, props, tok, Q):
        for s in live_slots:
            req = self._slot_req[s]
            prop = props[s]
            # row i's argmax is the model's true greedy token i+1; the
            # proposal is accepted exactly as far as it matches them
            emitted = [int(tok[s, 0])]
            for i in range(prop.size):
                if int(prop[i]) != emitted[i]:
                    break
                emitted.append(int(tok[s, i + 1]))
            accepted = len(emitted) - 1
            self._spec_proposed += int(prop.size)
            self._spec_accepted += accepted
            if prop.size:
                telemetry.inc(SPEC_PROPOSED, amount=float(prop.size))
            if accepted:
                telemetry.inc(SPEC_ACCEPTED, amount=float(accepted))
            applied = 0
            for t in emitted:
                applied += 1
                self._slot_out[s].append(t)
                self._positions[s] += 1
                self._next_tok[s] = t
                if self._is_done(req, self._slot_out[s]):
                    self._finish(s)
                    break
            # Q device rows split: delivered tokens, rejected/unused
            # speculation rows, and padding rows past the proposal
            rejected = (1 + int(prop.size)) - applied
            pad = Q - 1 - int(prop.size)
            self._tokens["decode"] += applied
            telemetry.inc(TOKENS_TOTAL, amount=float(applied),
                          kind="decode")
            if rejected:
                self._tokens["spec_rejected"] += rejected
                telemetry.inc(TOKENS_TOTAL, amount=float(rejected),
                              kind="spec_rejected")
                telemetry.inc(WASTED_TOKENS, amount=float(rejected),
                              reason="spec_rejected")
            if pad:
                self._tokens["pad"] += pad
                telemetry.inc(TOKENS_TOTAL, amount=float(pad),
                              kind="pad")
                telemetry.inc(WASTED_TOKENS, amount=float(pad),
                              reason="spec_pad")
        if _dtrace.trace_active():
            rec = {
                "kind": REQ_STEP_KIND, "ts": time.time_ns(),
                "step": self.steps,
                "slots": [[self._slot_req[s].request_id,
                           len(self._slot_out[s]) + 1]
                          for s in live_slots
                          if self._slot_req[s] is not None]}
            if self.trace_lane is not None:
                rec["lane"] = self.trace_lane
            _dtrace.record_span(rec)

    def _decode_once(self):
        flight, self._flight = self._flight, None
        live_slots = flight[0] if flight else self._decoding_slots()
        if live_slots:
            with telemetry.span("serving.decode", live=len(live_slots)):
                if flight is None:
                    flight = self._launch(live_slots)
                    if self._runs_ahead(live_slots):
                        # read in the next step, behind the launch of the
                        # one after it
                        self._flight = flight
                        return self.slots_in_use
                elif self._runs_ahead(live_slots):
                    self._flight = self._launch(live_slots, flown=flight[1])
                self._land(*flight)
        return self.slots_in_use

    @property
    def decode_in_flight(self):
        """Whether a decode step is dispatched and not yet read: the
        cache then holds what that step left, one token past what
        `live_tokens()` shows."""
        return self._flight is not None

    def _runs_ahead(self, live_slots):
        """Whether the step after the one `live_slots` are in can be
        dispatched before that one is read: the model's programs say so,
        every slot decodes (nobody could be admitted in between) and the
        one in flight ends no request."""
        if not self.model.decode_ahead or (
                len(live_slots) < self.slots or self.spec_ngram
                or self.prefill_chunk or self.prefix_cache is not None):
            return False
        return all(
            self._slot_req[s].eos_id is None and
            len(self._slot_out[s]) + 1 < self._slot_req[s].max_new_tokens
            for s in live_slots)

    def _launch(self, live_slots, flown=None):
        """Dispatches one decode step of `live_slots` from the host's
        books or, with the tokens `flown` that the step in flight left on
        the device, the step after that one: those tokens, one position
        further. Returns (live slots, the step's tokens on the device)."""
        ahead = flown is not None
        positions = self._positions
        if ahead:
            positions = positions.copy()
            positions[live_slots] += 1
        if self.prefix_cache is not None:
            for s in live_slots:
                if self._slot_cow_idx[s] >= 0:
                    self._resolve_cow(s)
        if self._page_san is not None:
            # the step writes one K/V entry per live slot at positions[s]
            for s in live_slots:
                self._page_san.note_write(
                    self._slot_req[s].request_id,
                    [self._slot_pages[s][int(positions[s])
                                         // self.page_size]])
        depths = positions[live_slots] + 1
        for kind, n in self.model.attended(depths).items():
            self._attended[kind] += n
        for kind, n in self.model.fetched(depths, self.page_size).items():
            self._fetched[kind] += n
        self._decode_steps += 1
        self._decode_steps_ahead += ahead
        did = self._did
        did["dispatched"] += 1
        did["ahead"] += ahead
        telemetry.inc(DECODE_STEPS, dispatch="ahead" if ahead else "sync")
        with self._h2d:
            args = (flown if ahead
                    else self._as_a_step_leaves(self._next_tok),
                    jnp.asarray(positions), jnp.asarray(self._tables))
        with self._dispatch as sp:
            sp.set_metadata(ahead=int(ahead))
            tok, self.paged = self._decode(self.params, self.paged, *args)
        return live_slots, tok

    def _as_a_step_leaves(self, tokens):
        """The host's `tokens` on the device, placed as a decode step
        leaves its own: a jit keys on an argument's commitment and
        sharding beside its shape, so tokens uploaded one way and handed
        on from the step before another would be two executables of one
        program, the second lowered at the first step that goes ahead. A
        program's outputs are committed to their device when any of its
        inputs was (weights a caller put there, say) and `jnp.asarray`
        commits nothing; the pool is an output of the program that ran
        before this one over the same weights, so it tells which. (A pool
        laid over several devices keeps `jnp.asarray`: how its program
        shards a token row is the compiler's choice.)"""
        pool = jax.tree_util.tree_leaves(self.paged)[0]
        if pool.committed and isinstance(
                pool.sharding, jax.sharding.SingleDeviceSharding):
            return jax.device_put(tokens, pool.sharding)
        return jnp.asarray(tokens)

    def _land(self, live_slots, tok):
        self._did["landed"] += 1
        with self._fetch:
            tok = np.asarray(tok)
        with self._bookkeep:
            self._note_tokens(live_slots, tok)

    def _note_tokens(self, live_slots, tok):
        n_live = len(live_slots)
        self._tokens["decode"] += n_live
        telemetry.inc(TOKENS_TOTAL, amount=float(n_live), kind="decode")
        if _dtrace.trace_active():
            # ONE batched progress record per decode STEP (not per token):
            # [request_id, tokens emitted so far] per live slot. Not a
            # span — trace_merge partitions kind=req_step out of the span
            # pipeline and uses it for per-request step counting.
            rec = {
                "kind": REQ_STEP_KIND, "ts": time.time_ns(),
                "step": self.steps,
                "slots": [[self._slot_req[s].request_id,
                           len(self._slot_out[s]) + 1]
                          for s in live_slots]}
            if self.trace_lane is not None:
                rec["lane"] = self.trace_lane
            _dtrace.record_span(rec)
        for s in live_slots:
            req = self._slot_req[s]
            self._slot_out[s].append(int(tok[s]))
            self._positions[s] += 1
            self._next_tok[s] = tok[s]
            if self._is_done(req, self._slot_out[s]):
                self._finish(s)

    def _is_done(self, req, out):
        if req.eos_id is not None and out and out[-1] == req.eos_id:
            return True
        return len(out) >= req.max_new_tokens

    def _finish(self, slot, reason=None):
        """Evict: record the result and recycle the pages IMMEDIATELY —
        the very next _admit() can hand them to a queued request.
        `reason` overrides the eos/length inference (mid-stream
        eviction passes "evicted").

        Idempotent per occupancy: a slot that already finished (EOS in
        the same step a cancel() raced in, say) returns without
        touching the allocator — the double-free guard the MXS010
        regression test pins."""
        req = self._slot_req[slot]
        if req is None:
            return
        self._did["finished"] += 1
        out = self._slot_out[slot]
        if reason is None:
            reason = ("eos" if req.eos_id is not None and out
                      and out[-1] == req.eos_id else "length")
        now = self._clock()
        queue_wait = req.admitted_at - req.submitted_at
        latency = now - req.submitted_at
        self._results[req.request_id] = RequestResult(
            request_id=req.request_id, tokens=list(out),
            finish_reason=reason, prompt_len=int(req.prompt.size),
            queue_wait_s=queue_wait, latency_s=latency,
            ttft_s=req.ttft_s)
        telemetry.inc(REQUESTS_TOTAL, outcome=reason)
        telemetry.observe(REQUEST_SECONDS, latency,
                          buckets=_LATENCY_BUCKETS)
        if reason == "evicted":
            # everything this request pushed through the device is now
            # undelivered output (its pad rows are already in the pad kind)
            wasted = int(req.prompt.size) + len(out)
            self._wasted_evicted += wasted
            telemetry.inc(WASTED_TOKENS, amount=float(wasted),
                          reason="evicted")
        if req.first_token_at is not None and req.first_token_held_s is None:
            # finished in the step that made its first token: how long
            # that token was held is known when the step returns
            self._held_finishes.append(
                (req, len(out), reason, queue_wait, latency))
        else:
            self._log_finish(req, len(out), reason, queue_wait, latency)
        if self.slo is not None:
            self.slo.observe_request(
                ttft=req.ttft_s, queue_wait=queue_wait,
                request_latency=latency,
                goodput=self._goodput_fraction())
        tr = req.trace
        if tr is not None:
            clk_first = tr.get("clk_first")
            if clk_first is not None and len(out) > 1:
                self._emit_request_record(
                    REQ_DECODE_SPAN, tr,
                    ts=self._trace_ts(tr, clk_first),
                    dur_s=now - clk_first, pid=tr["sid"],
                    extra={"request": req.request_id,
                           "steps": len(out) - 1})
            self._emit_request_record(
                REQ_SPAN, tr, ts=tr["ns_submit"], dur_s=latency,
                sid=tr["sid"], pid=tr.get("pid"),
                extra={"request": req.request_id,
                       "prompt_len": int(req.prompt.size),
                       "tokens": len(out), "finish": reason,
                       "queue_wait_s": queue_wait,
                       "ttft_s": req.ttft_s, "latency_s": latency,
                       "decode_steps": max(0, len(out) - 1)})
        self.allocator.free(self._slot_pages[slot], owner=req.request_id)
        self._slot_req[slot] = None
        self._slot_pages[slot] = []
        self._slot_out[slot] = []
        self._slot_prefill[slot] = None
        self._slot_cow_idx[slot] = -1
        self._tables[slot] = 0
        self._positions[slot] = 0
        self._next_tok[slot] = 0

    # -- per-request trace plumbing ----------------------------------------

    @staticmethod
    def _trace_ts(tr, clk):
        """Wall-clock ns for an engine-clock instant: deltas come from
        the injectable engine clock (so trace durations agree with the
        latency histograms even under a synthetic clock), anchored to
        the wall time captured at submit."""
        return tr["ns_submit"] + int((clk - tr["clk_submit"]) * 1e9)

    def _emit_request_record(self, name, tr, *, ts, dur_s, extra,
                             sid=None, pid=None):
        record = {"name": name, "tid": tr["tid"],
                  "sid": sid if sid is not None else _dtrace.new_id(),
                  "ts": int(ts), "dur_ns": max(0, int(dur_s * 1e9)),
                  "extra": extra}
        if pid is not None:
            record["pid"] = pid
        if self.trace_lane is not None:
            record["lane"] = self.trace_lane
        _dtrace.record_span(record)

    def _log_finish(self, req, n_tokens, reason, queue_wait, latency):
        """The always-on record of a finished request, once in the
        timelines (SLO dumps, /debug/engine) and once in the flight
        recorder's ring. `submitted` is on the engine's clock
        (`time.monotonic` unless one was injected); the three parts
        queue_wait_s (submit -> admit) + prefill_s (admit -> first token
        on the host) + first_token_held_s (-> return of the step() that
        made it) add up to the time to first token as a caller of step()
        sees it, and ttft_s to the first two. A request that never made a
        token has only its wait."""
        first = req.first_token_at
        stamps = {
            "submitted": req.submitted_at,
            "queue_wait_s": queue_wait,
            "prefill_s": None if first is None else first - req.admitted_at,
            "first_token_held_s": req.first_token_held_s,
            "ttft_s": None if first is None else req.ttft_s,
            "latency_s": latency,
        }
        self._timelines.append({
            "request_id": req.request_id,
            "prompt_len": int(req.prompt.size),
            "tokens": n_tokens, "finish": reason, **stamps})
        _recorder.log_event("serving_request_finish",
                            request=req.request_id, outcome=reason,
                            tokens=n_tokens, **stamps)

    # -- introspection ------------------------------------------------------

    def recent_timelines(self):
        """Last-N finished-request timeline dicts (newest last) — the
        payload the SLO breach dump carries."""
        return list(self._timelines)

    def goodput(self):
        """Token accounting split: device token-positions by kind, the
        wasted share (prefill padding + rejected speculation + evicted
        requests' tokens), and the useful fraction."""
        processed = sum(self._tokens.values())
        useful = (self._tokens["prefill"] + self._tokens["decode"]
                  - self._wasted_evicted)
        return {
            "prefill": self._tokens["prefill"],
            "decode": self._tokens["decode"],
            "pad": self._tokens["pad"],
            "spec_rejected": self._tokens["spec_rejected"],
            "wasted_evicted": self._wasted_evicted,
            "processed": processed,
            "useful": useful,
            "fraction": useful / processed if processed else 1.0,
        }

    def cache_stats(self):
        """The cache by kind, from the host's books (source of truth
        beside goodput()): the growing pool's pages live (holding a live
        slot's context now) and reserved (handed out at admission), what
        the model keeps per slot beside it (ring pages, state bytes), and
        the tokens the decode steps have attended so far by kind, summed
        over the layers that read them, beside the tokens their kernels
        fetched to attend those (a block is fetched whole and its tail
        masked: attended / fetched is the fill share); the decode steps
        dispatched that attended them, and how many of those went out one
        step ahead (`_runs_ahead`), before the step in flight was read."""
        live = [self._pages_for(int(self._positions[s]))
                for s in self._decoding_slots()]
        return {
            "pool": {"pages_live": sum(live),
                     "pages_reserved": self.allocator.num_in_use,
                     "capacity": self.allocator.capacity},
            "kinds": self.model.cache_kinds(self.page_size),
            "attended_tokens": dict(self._attended),
            "fetched_tokens": dict(self._fetched),
            "decode_steps": self._decode_steps,
            "decode_steps_ahead": self._decode_steps_ahead,
        }

    @property
    def prefix_hit_rate(self):
        """Fraction of admissions that mapped at least one cached page
        (0.0 when the prefix cache is off or nothing was admitted)."""
        return (self._prefix_hits / self._prefix_lookups
                if self._prefix_lookups else 0.0)

    @property
    def prefix_tokens_saved(self):
        """Prompt tokens never prefilled because their pages came from
        the prefix cache."""
        return self._prefix_tokens_saved

    @property
    def cow_copies(self):
        """Copy-on-write page copies performed (admission + decode)."""
        return self._cow_copies

    @property
    def spec_acceptance(self):
        """Accepted / proposed draft tokens (0.0 before any
        proposal)."""
        return (self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0)

    def _goodput_fraction(self):
        processed = sum(self._tokens.values())
        if not processed:
            return 1.0
        return (self._tokens["prefill"] + self._tokens["decode"]
                - self._wasted_evicted) / processed

    def debug_snapshot(self):
        """Live-engine JSON snapshot, served at /debug/engine by the
        telemetry HTTP server (MXTPU_DEBUG_ENDPOINTS=1) and rendered by
        tools/serving_top.py."""
        with self._lock:
            return self._debug_snapshot_locked()

    def _debug_snapshot_locked(self):
        now = self._clock()
        slot_rows = []
        for s, req in enumerate(self._slot_req):
            if req is None:
                slot_rows.append({"slot": s, "state": "idle"})
            else:
                pending = self._slot_prefill[s]
                slot_rows.append({
                    "slot": s,
                    "state": "prefilling" if pending else "decoding",
                    "request_id": req.request_id,
                    "age_s": now - req.submitted_at,
                    "prompt_len": int(req.prompt.size),
                    "tokens_out": len(self._slot_out[s]),
                    "position": (int(pending["pos"]) if pending
                                 else int(self._positions[s])),
                    "pages_held": len(self._slot_pages[s]),
                })
        queued = [{"request_id": r.request_id,
                   "age_s": now - r.submitted_at,
                   "prompt_len": int(r.prompt.size),
                   "max_new_tokens": r.max_new_tokens}
                  for r in self._queue]
        compile_rows = {
            fn: {"signatures": v["signatures"], "retraces": v["retraces"]}
            for fn, v in compilereg.snapshot().items()
            if fn.startswith("serving_")}
        cache = self.prefix_cache
        prefix_rows = None
        if cache is not None:
            prefix_rows = {
                "cached_pages": cache.cached_pages,
                "capacity": cache.max_pages,
                "lookups": self._prefix_lookups,
                "hits": self._prefix_hits,
                "hit_rate": self.prefix_hit_rate,
                "tokens_saved": self._prefix_tokens_saved,
                "evictions": cache.evictions,
                "cow_copies": self._cow_copies,
                "refcount_histogram": {
                    str(k): v for k, v in sorted(
                        self.allocator.refcount_histogram().items())},
            }
        spec_rows = None
        if self.spec_ngram:
            spec_rows = {
                "ngram": self.spec_ngram,
                "lookahead": self.spec_lookahead,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance": self.spec_acceptance,
            }
        chunk_rows = None
        if self.prefill_chunk:
            chunk_rows = {
                "chunk": self.prefill_chunk,
                "in_flight": sum(p is not None
                                 for p in self._slot_prefill),
                "chunks_total": self._prefill_chunks,
            }
        return {
            "schema": "mxtpu-serving-engine-debug-v2",
            "steps": self.steps,
            # a decode step dispatched and unread: the cache is one row a
            # slot past `position` and `tokens_out` below
            "decode_in_flight": self._flight is not None,
            "slots": slot_rows,
            "slots_in_use": self.slots_in_use,
            "queue": queued,
            "queue_depth": len(self._queue),
            "pages": {
                "capacity": self.allocator.capacity,
                "in_use": self.allocator.num_in_use,
                "free": self.allocator.num_free,
                "page_size": self.allocator.page_size,
                "occupancy": self.allocator.occupancy(),
                "fragmentation": self.allocator.fragmentation(),
            },
            "cache": self.cache_stats(),
            "prefix_cache": prefix_rows,
            "speculation": spec_rows,
            "chunked_prefill": chunk_rows,
            "tokens": self.goodput(),
            "compile": compile_rows,
            "slo": self.slo.snapshot() if self.slo is not None else None,
            "requests_finished": len(self._results),
            # steps over SLOW_STEP_FACTOR rolling medians so far, what
            # they took beyond the median, and the last one's event
            "slow_steps": dict(self._slow_steps),
        }

    def cancel(self, request_id):
        """Cancel a request: still-queued requests finish as
        "cancelled" (nothing was processed); live ones are EVICTED
        mid-stream — pages recycle immediately and every token they
        pushed through the device counts as wasted. Returns True when
        the request was cancelled, False when the id is unknown or
        already finished."""
        with self._lock:
            return self._cancel_locked(request_id)

    def _cancel_locked(self, request_id):
        for i, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[i]
                now = self._clock()
                waited = now - req.submitted_at
                self._results[request_id] = RequestResult(
                    request_id=request_id, tokens=[],
                    finish_reason="cancelled",
                    prompt_len=int(req.prompt.size),
                    queue_wait_s=waited, latency_s=waited)
                telemetry.inc(REQUESTS_TOTAL, outcome="cancelled")
                telemetry.set_gauge(QUEUE_DEPTH, len(self._queue))
                self._log_finish(req, 0, "cancelled", waited, waited)
                if req.trace is not None:
                    self._emit_request_record(
                        REQ_SPAN, req.trace, ts=req.trace["ns_submit"],
                        dur_s=waited, sid=req.trace["sid"],
                        pid=req.trace.get("pid"),
                        extra={"request": request_id,
                               "prompt_len": int(req.prompt.size),
                               "tokens": 0, "finish": "cancelled",
                               "latency_s": waited, "decode_steps": 0})
                return True
        for s, req in enumerate(self._slot_req):
            if req is not None and req.request_id == request_id:
                if self._flight is not None:
                    # its tokens were made: read them before the slot goes
                    flight, self._flight = self._flight, None
                    self._land(*flight)
                # where the step in flight was its last, it has finished
                # as it would have a step() earlier: nothing left to cancel
                cancelled = self._slot_req[s] is req
                if cancelled:
                    self._finish(s, reason="evicted")
                self._export_gauges()
                return cancelled
        return False

    def _export_gauges(self):
        if not telemetry.enabled():
            return
        telemetry.set_gauge(QUEUE_DEPTH, len(self._queue))
        telemetry.set_gauge(SLOTS_IN_USE, self.slots_in_use)
        telemetry.set_gauge(PAGES_IN_USE, self.allocator.num_in_use)
        telemetry.set_gauge(
            PAGE_UTILIZATION,
            self.allocator.num_in_use / max(1, self.allocator.capacity))
        telemetry.set_gauge(
            OLDEST_QUEUED,
            self._clock() - self._queue[0].submitted_at
            if self._queue else 0.0)
        telemetry.set_gauge(GOODPUT, self._goodput_fraction())
        if self.prefix_cache is not None:
            telemetry.set_gauge(PREFIX_CACHED_PAGES,
                                self.prefix_cache.cached_pages)
