"""Job `serve_retention`: an attention-free LM of gated power-retention
layers (models.brumby: a float32 matrix state a K/V head and layer, no K/V
cache) behind `serving.ServingEngine` on one chip, under a backlog that
never empties.

The client is jobs/serve_engine.py's own (`Pump`, `run_backlog`) with
jobs/serve_hybrid.py's window, and the check is jobs/serve_parallel_hybrid.py's
(`warm_and_check`: one warm-up request per slot, all live in one decode
batch and run one step ahead, the first and last `check_rows` tokens' logits
rows of each held to the plain float32 reference's full forward pass over
prompt + output, here in the retention's quadratic form); `serve_out_tok_per_s`
is taken exactly as in the other backlog cells. What this file brings is the
model: its configuration from the source's keys, seeded weights made on the
device, the engine with its levers off (it refuses them for a model with
recurrent state, and builds no page pool for one without K/V), and the facts
kernel_costs/power_retention.py reads. In the window every finished request
must have delivered exactly its max_new_tokens.
"""
import gc
import time

import jax

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models import brumby
from incubator_mxnet_tpu.ops.pallas_kernels import DENSE_FALLBACKS_TOTAL

from benchmark.harness import arrivals
from benchmark.harness.tracing import TailTrace
from benchmark.jobs import serve_engine, serve_hybrid, serve_parallel_hybrid


def model_config(config):
    return brumby.BrumbyConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        chunk=config["retention_chunk"],
        rope_theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=config["serving"]["dtype"])


def run(ctx):
    config, mix = ctx.cell.config, ctx.cell.traffic
    cfg = model_config(config)
    if len(ctx.devices) != 1:
        raise ValueError("serve_retention drives one engine on one chip")
    if mix["arrivals"]["process"] != "backlog":
        raise ValueError("serve_retention runs backlog mixes")

    params = brumby.init_params(cfg, ctx.seed, ctx.devices[0])
    jax.block_until_ready(params)
    ctx.mark("weights_on_device")
    telemetry.enable()  # the dense-fallback counter, as in serve_engine
    eng = serve_engine.build_engine(config, params, cfg)
    reqs = arrivals.requests(mix, cfg.vocab, ctx.seed, slots=eng.slots,
                             seconds=ctx.seconds)
    ctx.mark("engine_and_requests_made")
    ok, check = serve_parallel_hybrid.warm_and_check(ctx, eng, params, cfg,
                                                     reqs)
    ctx.mark("reference_checked")
    fallbacks = serve_engine._counter_total(DENSE_FALLBACKS_TOTAL)
    telemetry.disable()

    pump = serve_engine.Pump(eng, time.perf_counter)
    window = serve_hybrid.Window(ctx, pump,
                                 TailTrace(ctx, mix["trace_seconds"]))
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
        # what kernel_costs/power_retention.py reads
        "retention": {"layers": kinds["recurrent"]["layers"],
                      "kv_heads": cfg.n_kv_heads,
                      "query_heads": cfg.n_heads,
                      "head_dim": cfg.head_dim, "kinds": kinds},
    }
    if window.traced is not None:
        facts["traced"] = {
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
        "gap_s": serve_engine._percentiles(gaps), "cache_kinds": kinds,
        "delivery_span_s": facts["delivery_span_s"],
        "delivery_step_s": {**serve_engine._percentiles(steps_s),
                            "max": max(steps_s, default=None)}}
    return facts
