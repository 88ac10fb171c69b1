"""Job `serve_hybrid`: a decoder-hybrid-decoder LM (models.sambay) behind
`serving.ServingEngine` on one chip, under a backlog that never empties.

The client is jobs/serve_engine.py's own (`Pump`, `run_backlog`, `Window`):
the requests come from the seed, the pump stamps every delivered token, and
`serve_out_tok_per_s` is taken exactly as in the other backlog cells. What
this file brings is the model: its configuration from the source's keys,
seeded weights made on the device, the engine with its levers off (it
refuses them for a model with recurrent state), and the tallies the new
kernels' rooflines divide by, which the engine keeps by kind of cache.

Correctness, before the window: one warm-up request per slot, all of them
live in one decode batch at their own depths, through prefill, the state's
hand-off and `check_new_tokens` decode steps through all three kinds of
cache. Their true lengths (`check_lengths`) lie under the buckets' (the
state a prefill leaves is the one after the last REAL row), cover every
bucket the mix's prompts can take, cross the largest bucket's end while
decoding, and stand just under a multiple of the ring so that it wraps
between two decode steps. The programs leave the logits row of every token
they choose on the device (the model's cache keeps it); the rows of the
first and the last `check_rows` tokens of each request are held to the
plain float32 reference's full forward pass over prompt + output, logits
and not tokens: the root mean square of a row's difference, in standard
deviations of the reference's row, is at most `logit_tol_std`, and the
token the engine gave is the largest of the row it came from. In the
window every finished request must have delivered exactly its
max_new_tokens.
"""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models import sambay
from incubator_mxnet_tpu.ops.pallas_kernels import DENSE_FALLBACKS_TOTAL
from incubator_mxnet_tpu.serving import ServingEngine

from benchmark.harness import arrivals, loader
from benchmark.harness.tracing import TailTrace
from benchmark.jobs import serve_engine


def model_config(config):
    m = config["mamba"]
    return sambay.SambaYConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], window=config["sliding_window"],
        d_state=m["d_state"], d_conv=m["d_conv"], expand=m["expand"],
        dt_rank=m["dt_rank"], eps=config["layer_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=config["serving"]["dtype"])


def build_engine(config, params, cfg):
    s = config["serving"]
    return ServingEngine(params, cfg, slots=s["slots"],
                         page_size=s["page_size"], max_len=s["max_len"],
                         prefix_cache=0, prefill_chunk=0, spec_ngram=0)


def check_lengths(eng, cfg, buckets, new, rng):
    """True lengths of the warm-up prompts, one per slot: one row short of
    every bucket (the state at the true length, not at the padded end; the
    largest crosses its bucket's end on the second decode step), just
    under every multiple of a window layer's ring that the largest bucket
    holds (the ring wraps while decoding), the rest drawn from what is
    left of the buckets' range. All lie past the window and in one of
    `buckets`, so the warm-up compiles what the mix can use and no more."""
    top = min(max(buckets), eng.max_len - new)
    low = max(cfg.window, min(buckets) // 2)
    ring = sambay.ring_pages(cfg, eng.page_size) * eng.page_size
    lens = [min(b, top) - 1 for b in reversed(buckets)]
    lens += [k * ring - 3 for k in range(top // ring, 0, -1)
             if low < k * ring - 3 < top]
    lens = lens[: eng.slots]
    lens += [int(n) for n in rng.integers(low + 1, top,
                                          size=eng.slots - len(lens))]
    return lens


def check_rows(new, edge):
    """Indices of the tokens whose rows are compared: the first `edge`
    (the prefill's row and the state's hand-off) and the last `edge`
    (what the decode steps have made of the caches by then)."""
    return sorted(set(range(min(edge, new)))
                  | set(range(max(0, new - edge), new)))


def served_rows(eng, prompts, new, keep):
    """Serves `prompts` for `new` tokens each, all at once, stepping the
    engine by hand. Returns {request id: (tokens, {i: the logits row, on
    the device, that token i was chosen from, for i in keep})}: after a
    step() the cache's logits_prefill holds the row of a slot admitted in
    it, logits_decode the row of every slot's latest token."""
    rids = [eng.submit(p, new) for p in prompts]
    slot_of, rows, seen = {}, {r: {} for r in rids}, dict.fromkeys(rids, 0)
    while eng.queue_depth or eng.slots_in_use:
        eng.step()
        for row in eng.debug_snapshot()["slots"]:
            if row["state"] == "decoding":
                slot_of[row["request_id"]] = row["slot"]
        live, done = eng.live_tokens(), eng.results()
        for rid in rids:
            n = len(live[rid] if rid in live else done[rid].tokens
                    if rid in done else ())
            if n == seen[rid]:
                continue
            s = slot_of[rid]
            if seen[rid] == 0 and 0 in keep:
                rows[rid][0] = eng.paged["logits_prefill"][s]
            if n > 1 and n - 1 in keep:
                rows[rid][n - 1] = eng.paged["logits_decode"][s]
            seen[rid] = n
    done = eng.results()
    return {rid: (np.asarray(done[rid].tokens, np.int32), rows[rid])
            for rid in rids}


@jax.jit
def _row_stats(got, want, tokens):
    std = want.std(-1)
    err = jnp.sqrt(jnp.mean(jnp.square(got - want), -1)) / std
    margin = (want.max(-1) - jnp.take_along_axis(
        want, tokens[:, None], -1)[:, 0]) / std
    return err, margin, jnp.argmax(got, -1) == tokens


def warm_and_check(ctx, eng, params, cfg, reqs):
    """serve_engine.warm_and_check for a model that hands its logits out:
    one request per slot, decoding together, the first and last rows of
    each held to the reference. Returns (ok, detail)."""
    config, mix = ctx.cell.config, ctx.cell.traffic
    new, edge = int(mix["check_new_tokens"]), int(mix["check_rows"])
    keep = check_rows(new, edge)
    rng = np.random.default_rng(ctx.seed + 1)
    span = mix["prompt_len"]
    buckets = sorted({serve_engine.bucket_for(eng, n) for n in
                      [span["min"], span["max"]]
                      + [r.prompt.size for r in reqs]})
    sizes = check_lengths(eng, cfg, buckets, new, rng)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in sizes]
    served = served_rows(eng, prompts, new, set(keep))
    ctx.mark("engine_warm")

    ref = loader.load_reference(config, ctx.root)
    pad_to = min(eng.max_len, -(-(max(sizes) + new) // 128) * 128)
    ref_fn = jax.jit(lambda prm, toks, first: ref(prm, toks, config,
                                                  rows=(first, new)))
    kept = jnp.asarray(keep)
    errs, margins, agreed = [], [], True
    for prompt, (out, rows) in zip(prompts, served.values()):
        if out.size != new or sorted(rows) != keep:
            return False, {"check": f"{out.size} tokens, {len(rows)} rows "
                                    f"for {new}, {len(keep)} asked"}
        seq = np.zeros((pad_to,), np.int32)
        seq[: prompt.size] = prompt
        seq[prompt.size: prompt.size + new - 1] = out[:-1]
        want = ref_fn(params, jnp.asarray(seq),
                      jnp.asarray(prompt.size - 1, jnp.int32))[kept]
        err, margin, agree = _row_stats(
            jnp.stack([rows[i] for i in keep]), want, jnp.asarray(out)[kept])
        errs.append(np.asarray(err))
        margins.append(np.asarray(margin))
        agreed = agreed and bool(np.all(agree))
    errs = np.stack(errs)              # (requests, compared rows)
    worst = float(errs.max())
    return agreed and worst <= float(mix["logit_tol_std"]), {
        "check_buckets": buckets, "check_prompt_lens": sizes,
        "check_tokens": new * len(prompts), "check_rows": keep,
        "worst_logit_err_std": worst,
        "logit_err_std_by_row": [float(e) for e in errs.max(0)],
        "logit_err_std_mean": float(errs.mean()),
        "tokens_are_rows_argmax": agreed,
        "worst_logit_margin_std": float(np.max(margins))}


class Window(serve_engine.Window):
    """serve_engine's window counters, and the engine's tokens attended
    by kind of cache beside them."""

    def _counters(self):
        return {**super()._counters(),
                **self._pump.eng.cache_stats()["attended_tokens"]}


def run(ctx):
    config, mix = ctx.cell.config, ctx.cell.traffic
    cfg = model_config(config)
    if len(ctx.devices) != 1:
        raise ValueError("serve_hybrid drives one engine on one chip")
    if mix["arrivals"]["process"] != "backlog":
        raise ValueError("serve_hybrid runs backlog mixes")

    params = sambay.init_params(cfg, ctx.seed, ctx.devices[0])
    ctx.mark("weights_on_device")
    telemetry.enable()  # the dense-fallback counter, as in serve_engine
    eng = build_engine(config, params, cfg)
    reqs = arrivals.requests(mix, cfg.vocab, ctx.seed, slots=eng.slots,
                             seconds=ctx.seconds)
    ctx.mark("engine_and_requests_made")
    ok, check = warm_and_check(ctx, eng, params, cfg, reqs)
    ctx.mark("reference_checked")
    fallbacks = serve_engine._counter_total(DENSE_FALLBACKS_TOTAL)
    telemetry.disable()

    pump = serve_engine.Pump(eng, time.perf_counter)
    window = Window(ctx, pump, TailTrace(ctx, mix["trace_seconds"]))
    # what set-up left behind (compiled programs, the reference's graph)
    # is no garbage: out of the collector's way, as a server does after
    # warming up, so that no full collection walks it inside the window
    gc.collect()
    gc.freeze()
    t0, t_end, judged = serve_engine.run_backlog(ctx, pump, window, reqs)
    closed = window.close()
    gc.unfreeze()

    def since(start, key):
        return closed[key] - start[key]

    bad = {id(c) for c in judged
           if c.n_tokens != c.expected or c.finish_reason != "length"}
    clients = pump.clients.values()
    gaps = [g for c in clients for g in serve_engine._gaps(c, t0, t_end)]
    delivered = sorted((t, n) for c in clients
                       for t, n in zip(c.deliveries, c.counts)
                       if t0 <= t <= t_end)
    # the rate as serve_engine takes it: first to last delivery in the
    # window, over the tokens after the first
    instants = sorted({t for t, _ in delivered})
    steps_s = [b - a for a, b in zip(instants, instants[1:])]
    steps = max(1, since(window.opened, "steps"))
    goodput = {k: closed["goodput"][k] - window.opened["goodput"][k]
               for k in ("prefill", "decode", "pad", "processed")}
    kinds = eng.cache_stats()["kinds"]
    facts = {
        "out_tokens": sum(n for _, n in delivered),
        "out_tokens_spanned": sum(n for t, n in delivered
                                  if t > instants[0]),
        "delivery_span_s": instants[-1] - instants[0],
        "delivery_steps_s": steps_s,
        "correct": bool(ok and not bad and fallbacks == 0),
        "checks": serve_engine.logit_checks(check, "worst_logit_err_std",
                                            mix, fallbacks),
        "attempted": len(judged), "failed": len(bad),
        "window_start": t0, "window_s": t_end - t0,
        "compiles_in_window": since(window.opened, "compiles"),
        "gaps": gaps, "ttfts": [], "lateness": [], "goodput": goodput,
        "decode_steps": since(window.opened, "decode_steps"),
        "slots": eng.slots,
        "pool": {"capacity": eng.allocator.capacity,
                 "live_pages_mean": since(window.opened, "live_pages") / steps,
                 "reserved_pages_mean": (since(window.opened, "reserved_pages")
                                         / steps)},
        "cache": {"n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
                  "itemsize": jnp.dtype(cfg.dtype).itemsize,
                  "recurrent_layers": kinds["recurrent"]["layers"],
                  "d_inner": cfg.d_inner, "d_state": cfg.d_state,
                  "kinds": kinds},
    }
    if window.traced is not None:
        facts["traced"] = {
            "attended": {k: since(window.traced, k)
                         for k in ("shared_kv", "window_kv")},
            "decode_steps": since(window.traced, "decode_steps"),
            **{f"{k}_tokens": (closed["goodput"][k]
                               - window.traced["goodput"][k])
               for k in ("decode", "prefill")}}
    facts["detail"] = {
        **check, "dense_fallbacks": fallbacks, "slots": eng.slots,
        "pool_pages": eng.allocator.num_pages, "window_s": t_end - t0,
        "requests_judged": len(judged), "requests_failed": len(bad),
        "out_tokens": facts["out_tokens"],
        "decode_steps": facts["decode_steps"], "goodput": goodput,
        "queue_depth_max": pump.queue_max,
        # when each admission's prefill came: a judged request's last token
        "request_ends_s": sorted(c.deliveries[-1] - t0 for c in judged),
        "gap_s": serve_engine._percentiles(gaps),
        "pool": facts["pool"], "cache_kinds": kinds,
        "attended_tokens": {k: since(window.opened, k)
                            for k in ("shared_kv", "window_kv")},
        "delivery_span_s": facts["delivery_span_s"],
        "delivery_step_s": {**serve_engine._percentiles(steps_s),
                            "max": max(steps_s, default=None)}}
    return facts
