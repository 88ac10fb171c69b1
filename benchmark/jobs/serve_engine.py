"""Job `serve_engine`: a transformer LM behind `serving.ServingEngine` on one
chip, driven from the client's side.

The engine is the program's own, unchanged, built from the configuration's
"serving" sizing with its optional levers off. The benchmark owns the
client: it makes the requests from the seed (harness/arrivals.py), hands
them over when they are due, calls `step()`, and after every step reads
`live_tokens()` / `results()` and stamps each new token with its own
clock. A request's first token is timed from when the request was DUE, not
from when the engine accepted it.

Two arrival processes (mix `arrivals.process`):

  backlog  the queue is kept at `min_queued_per_slot` x slots, so every
           slot decodes all the time; one ramp step fills the slots before
           the window opens. Judged by tokens delivered per second of
           wall time, stalls included.
  gamma    open loop: a feeder thread sleeps until each request is due and
           puts it in an inbox with the time it woke (its lateness is the
           generator's, not the engine's); the pump thread submits what the
           inbox holds between steps. `lead_in_s` of the same traffic runs
           before the window so it opens on a working system; requests due
           in the window are followed to completion (`drain_cap_s`).

Correctness, before the window: one warm-up request per prefill bucket the
mix will use doubles as the check — every token the engine produced must
have a reference logit within `logit_tol_std` standard deviations of that
position's maximum in the configuration's plain float32 reference, run
over prompt + engine output. Logits and not tokens, because with random
weights the largest logit changes on rounding. In the window, every
finished request must have delivered exactly its max_new_tokens.
"""
import dataclasses
import queue
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models.transformer import TransformerConfig
from incubator_mxnet_tpu.ops.pallas_kernels import DENSE_FALLBACKS_TOTAL
from incubator_mxnet_tpu.serving import ServingEngine

from benchmark.harness import arrivals, loader, stats, weights
from benchmark.harness.tracing import TailTrace, span


@dataclasses.dataclass
class Client:
    """One request as its sender sees it."""
    prompt_len: int
    expected: int              # max_new_tokens
    due: float | None          # clock time it was due (None: backlog)
    lateness: float = 0.0      # generator woke this long after `due`
    n_tokens: int = 0
    # one entry per read that brought new tokens: when, and how many
    deliveries: list = dataclasses.field(default_factory=list)
    counts: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""


def transformer_config(config):
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=config["serving"]["dtype"])


def build_engine(config, params, cfg):
    s = config["serving"]
    return ServingEngine(params, cfg, slots=s["slots"],
                         page_size=s["page_size"], max_len=s["max_len"],
                         prefix_cache=0, prefill_chunk=0, spec_ngram=0)


def bucket_for(eng, n):
    return next(b for b in eng.prefill_buckets if b >= n)


def _counter_total(name):
    fam = telemetry.REGISTRY.get(name)
    return sum(ch.value for _, ch in fam.series()) if fam else 0.0


def warm_and_check(ctx, eng, params, cfg, reqs):
    """Runs one request per prefill bucket `reqs` will use (so the decode
    program and those prefill programs compile here) and holds each token
    the engine gave to the reference. Returns (ok, detail)."""
    config, mix = ctx.cell.config, ctx.cell.traffic
    new = int(mix["check_new_tokens"])
    rng = np.random.default_rng(ctx.seed + 1)
    buckets = sorted({bucket_for(eng, r.prompt.size) for r in reqs})
    prompts = [rng.integers(1, cfg.vocab, size=min(b, eng.max_len - new))
               .astype(np.int32) for b in buckets]
    rids = [eng.submit(p, new) for p in prompts]
    results = eng.run()
    ctx.mark("engine_warm")

    ref = loader.load_reference(config, ctx.root)
    # one padded length, so the reference compiles once (causal: padding
    # after the tokens of interest does not change their rows)
    pad_to = min(eng.max_len,
                 -(-(max(p.size for p in prompts) + new) // 128) * 128)
    ref_fn = jax.jit(lambda prm, toks: ref(prm, toks, config))
    worst = 0.0
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(results[rid].tokens, np.int32)
        if out.size != new:
            return False, {"check": f"{out.size} tokens for {new} asked"}
        seq = np.zeros((pad_to,), np.int32)
        seq[: prompt.size] = prompt
        seq[prompt.size: prompt.size + new - 1] = out[:-1]
        rows = np.asarray(ref_fn(params, jnp.asarray(seq))[
            prompt.size - 1: prompt.size - 1 + new])
        margin = (rows.max(-1) - rows[np.arange(new), out]) / rows.std(-1)
        worst = max(worst, float(margin.max()))
    return worst <= float(mix["logit_tol_std"]), {
        "check_buckets": buckets, "check_tokens": new * len(prompts),
        "worst_logit_margin_std": worst}


class Pump:
    """Steps the engine and keeps the clients' books."""

    def __init__(self, eng, clock):
        self.eng = eng
        self.clock = clock
        self.clients = {}      # request id -> Client, every one submitted
        self.open = set()      # ids not finished yet
        self.steps = 0
        self.decode_steps = 0
        self.kv_tokens = 0     # sum over decode steps of tokens attended
        # sums over steps of the pool's pages that hold a live request's
        # context, and of those the allocator has handed out (a request
        # takes the pages of prompt + max_new_tokens when it is admitted)
        self.live_pages = 0
        self.reserved_pages = 0
        self.queue_max = 0     # deepest the engine's queue was after a step
        self._decoded = eng.goodput()["decode"]

    def submit(self, req, due=None, lateness=0.0):
        with span("bench.submit"):
            rid = self.eng.submit(req.prompt, req.max_new_tokens)
            self.clients[rid] = Client(req.prompt.size, req.max_new_tokens,
                                       due, lateness)
            self.open.add(rid)

    def step(self):
        with span("bench.engine_step"):
            self.eng.step()
        with span("bench.read_tokens"):
            now = self.clock()
            live = self.eng.live_tokens()
            done = self.eng.results()
            self.queue_max = max(self.queue_max, self.eng.queue_depth)
            self.steps += 1
            self.reserved_pages += self.eng.allocator.num_in_use
            decoded = self.eng.goodput()["decode"]
            if decoded > self._decoded:
                self.decode_steps += 1
                self._decoded = decoded
            for rid in list(self.open):
                c = self.clients[rid]
                if rid in live:
                    n = len(live[rid])
                    self.live_pages += -(-(c.prompt_len + n)
                                         // self.eng.page_size)
                elif rid in done:
                    n = len(done[rid].tokens)
                    c.done, c.finish_reason = True, done[rid].finish_reason
                    self.open.discard(rid)
                else:
                    continue  # still queued
                if n > max(c.n_tokens, 1):
                    # its slot decoded in this step, attending the prompt
                    # and every token but the one the step produced
                    self.kv_tokens += c.prompt_len + n - 1
                if n > c.n_tokens:
                    c.deliveries.append(now)
                    c.counts.append(n - c.n_tokens)
                    c.n_tokens = n


def _percentiles(samples):
    """For the detail line: stats.summary (the count, the median, the
    highest percentile the count supports) and the usual tails."""
    if not samples:
        return stats.summary(samples)
    return {**stats.summary(samples),
            **{f"p{q:g}": stats.percentile(samples, q)
               for q in (75, 90, 95, 99)}}


def _gaps(client, lo, hi):
    """The client's gaps between deliveries that ended in [lo, hi]."""
    d = client.deliveries
    return [b - a for a, b in zip(d, d[1:]) if lo <= b <= hi]


class Window:
    """The engine's and the pump's counters when the window opened, and
    when the trace began (None: not traced)."""

    def __init__(self, ctx, pump, tail):
        self._ctx, self._pump, self._tail = ctx, pump, tail
        self.opened = self.traced = None

    def _counters(self):
        return {"compiles": self._ctx.compiles.count,
                "goodput": self._pump.eng.goodput(),
                "steps": self._pump.steps,
                "decode_steps": self._pump.decode_steps,
                "kv_tokens": self._pump.kv_tokens,
                "live_pages": self._pump.live_pages,
                "reserved_pages": self._pump.reserved_pages}

    def tick(self, elapsed):
        """Call between steps with the seconds since the window opened
        (negative before it)."""
        if elapsed < 0:
            return
        if self.opened is None:
            self.opened = self._counters()
        if self._tail.tick(elapsed):
            self.traced = self._counters()

    def close(self):
        self._tail.stop()
        return self._counters()


def run_backlog(ctx, pump, window, reqs):
    """Keeps the engine's queue full for ctx.seconds. Returns the window's
    (start, end) and the requests it is judged by: those that finished."""
    eng, clock = pump.eng, pump.clock
    arrivals_ = ctx.cell.traffic["arrivals"]
    backlog = deque(reqs)
    target = int(arrivals_["min_queued_per_slot"]) * eng.slots

    def top_up():
        while eng.queue_depth < target and backlog:
            pump.submit(backlog.popleft())

    for _ in range(eng.slots):  # the ramp: fill every slot, then the queue
        pump.submit(backlog.popleft())
    top_up()
    pump.step()
    top_up()
    t0 = clock()
    while clock() - t0 < ctx.seconds:
        window.tick(clock() - t0)
        pump.step()
        top_up()
    if not backlog:
        raise RuntimeError("the backlog ran dry: raise the mix's `requests`")
    return t0, clock(), [c for c in pump.clients.values() if c.done]


def run_open_loop(ctx, pump, window, reqs):
    """Hands each request over when it is due and follows those due in the
    window to their end. Returns the window's start, the end of its drain,
    and the requests it is judged by: those due in the window."""
    eng, clock, mix = pump.eng, pump.clock, ctx.cell.traffic
    inbox = queue.SimpleQueue()
    t0 = clock() + float(mix.get("lead_in_s", 0.0))
    end = t0 + ctx.seconds
    cap = end + float(mix["drain_cap_s"])

    def feed():
        for r in reqs:
            due = t0 + r.due_s
            delay = due - clock()
            if delay > 0:
                time.sleep(delay)
            inbox.put((r, due, clock() - due))

    feeder = threading.Thread(target=feed, name="bench-feeder", daemon=True)
    feeder.start()
    while True:
        now = clock()
        window.tick(now - t0)
        while not inbox.empty():
            pump.submit(*inbox.get())
        waiting = any(t0 <= pump.clients[rid].due < end for rid in pump.open)
        if (now >= end and (not waiting or now >= cap)
                and not feeder.is_alive() and inbox.empty()):
            break
        if eng.queue_depth or eng.slots_in_use:
            pump.step()
            continue
        with span("bench.idle_wait"):
            try:
                arrived = inbox.get(timeout=0.02)
            except queue.Empty:
                arrived = None
        if arrived is not None:
            pump.submit(*arrived)
    feeder.join(timeout=5.0)
    if feeder.is_alive():
        raise RuntimeError("the feeder thread did not end")
    return t0, clock(), [c for c in pump.clients.values()
                         if t0 <= c.due < end]


def run(ctx):
    config, mix = ctx.cell.config, ctx.cell.traffic
    cfg = transformer_config(config)
    if len(ctx.devices) != 1:
        raise ValueError("serve_engine drives one engine on one chip")

    params = weights.transformer_params(
        cfg, ctx.seed, config["serving"]["param_dtypes"], ctx.devices[0])
    ctx.mark("weights_on_device")
    # the dense-fallback counters count at trace time and only while
    # telemetry is on; it goes off again before the window
    telemetry.enable()
    eng = build_engine(config, params, cfg)
    reqs = arrivals.requests(mix, cfg.vocab, ctx.seed, slots=eng.slots,
                             seconds=ctx.seconds)
    ctx.mark("engine_and_requests_made")
    ok, check = warm_and_check(ctx, eng, params, cfg, reqs)
    ctx.mark("reference_checked")
    fallbacks = _counter_total(DENSE_FALLBACKS_TOTAL)
    telemetry.disable()

    pump = Pump(eng, time.perf_counter)
    window = Window(ctx, pump, TailTrace(ctx, mix["trace_seconds"]))
    backlog = mix["arrivals"]["process"] == "backlog"
    t0, t_end, judged = (run_backlog if backlog else run_open_loop)(
        ctx, pump, window, reqs)
    closed = window.close()

    def since(start, key):
        return closed[key] - start[key]

    bad = {id(c) for c in judged
           if not c.done or c.n_tokens != c.expected
           or c.finish_reason != "length"}
    gaps = [g for c in pump.clients.values() for g in _gaps(c, t0, t_end)]
    facts = {"out_tokens": sum(c.n_tokens for c in judged)}
    ttfts, lateness = [], []
    if backlog:
        delivered = sorted(
            (t, n) for c in pump.clients.values()
            for t, n in zip(c.deliveries, c.counts) if t0 <= t <= t_end)
        # the rate is taken from the window's first delivery to its last,
        # over the tokens after the first: whole steps over the wall time
        # they took, stalls and all, instead of a fixed window that cuts a
        # step in two (a step is 16 tokens, half a percent of a 40 s window)
        instants = sorted({t for t, _ in delivered})
        facts = {"out_tokens": sum(n for _, n in delivered),
                 "out_tokens_spanned": sum(n for t, n in delivered
                                           if t > instants[0]),
                 "delivery_span_s": instants[-1] - instants[0],
                 "delivery_steps_s": [b - a for a, b in
                                      zip(instants, instants[1:])]}
    else:
        slo, cap = mix["slo"], float(mix["drain_cap_s"])
        ttfts = [(c.deliveries[0] - c.due) if c.deliveries else cap
                 for c in judged]
        lateness = [c.lateness for c in judged]
        facts["slo_ok"] = sum(
            1 for c, ttft in zip(judged, ttfts)
            if id(c) not in bad and ttft <= slo["ttft_s"]
            and all(g <= slo["gap_s"] for g in _gaps(c, c.due, t_end)))

    steps = max(1, since(window.opened, "steps"))
    goodput = {k: closed["goodput"][k] - window.opened["goodput"][k]
               for k in ("prefill", "decode", "pad", "processed")}
    facts.update({
        "correct": bool(ok and not bad and fallbacks == 0),
        "attempted": len(judged), "failed": len(bad),
        "window_start": t0, "window_s": t_end - t0,
        "compiles_in_window": since(window.opened, "compiles"),
        "gaps": gaps, "ttfts": ttfts, "lateness": lateness,
        "goodput": goodput,
        "decode_steps": since(window.opened, "decode_steps"),
        "slots": eng.slots,
        "pool": {"capacity": eng.allocator.capacity,
                 "live_pages_mean": since(window.opened, "live_pages") / steps,
                 "reserved_pages_mean": (since(window.opened, "reserved_pages")
                                         / steps)},
        "kv": {"n_heads": cfg.n_heads, "head_dim": cfg.d_model // cfg.n_heads,
               "n_layers": cfg.n_layers,
               "itemsize": jnp.dtype(cfg.dtype).itemsize},
    })
    if window.traced is not None:
        facts["traced"] = {
            "kv_tokens": since(window.traced, "kv_tokens"),
            "decode_steps": since(window.traced, "decode_steps"),
            "prefill_tokens": (closed["goodput"]["prefill"]
                               - window.traced["goodput"]["prefill"])}
    steps_s = facts.get("delivery_steps_s", [])
    facts["detail"] = {
        **check, "dense_fallbacks": fallbacks, "slots": eng.slots,
        "pool_pages": eng.allocator.num_pages, "window_s": t_end - t0,
        "requests_judged": len(judged), "requests_failed": len(bad),
        "out_tokens": facts["out_tokens"],
        "decode_steps": facts["decode_steps"], "goodput": goodput,
        "lateness_max_s": max(lateness, default=0.0),
        "queue_depth_max": pump.queue_max,
        "ttft_s": _percentiles(ttfts), "gap_s": _percentiles(gaps),
        "gap_mean_s": sum(gaps) / len(gaps) if gaps else None,
        "pool": facts["pool"],
        "delivery_span_s": facts.get("delivery_span_s"),
        "delivery_step_s": {**_percentiles(steps_s),
                            "max": max(steps_s, default=None)},
        # a backlog that grows shows as a second half slower than the first
        "ttft_median_by_half_s": [
            stats.summary(ttfts[: len(ttfts) // 2]).get("median"),
            stats.summary(ttfts[len(ttfts) // 2:]).get("median")]}
    return facts
