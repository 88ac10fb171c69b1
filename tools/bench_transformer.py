#!/usr/bin/env python
"""Transformer training throughput benchmark (the flagship model's
tokens/sec on one chip; complements bench.py's ResNet-50 number with the
workload class the parallel/ stack is designed for).

Measures the GSPMD train step of models/transformer.py on a 1-device mesh
(single chip) — same step that dryrun_multichip shards over dp/ep/tp.
Prints one JSON line {"metric", "value", "unit", ...}.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--n-layers", type=int, default=6)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--flash", action="store_true",
                    help="Pallas flash-attention kernels")
    ap.add_argument("--fused-xent", action="store_true",
                    help="Pallas fused softmax-xent loss kernel")
    ap.add_argument("--decode-steps", type=int, default=0,
                    help="also measure KV-cache generation throughput")
    ap.add_argument("--dtype", default="float32",
                    help="parameter/activation dtype (bfloat16 = MXU rate)")
    ap.add_argument("--serving", action="store_true",
                    help="benchmark the continuous-batching serving "
                         "engine on a seeded mixed-length request trace "
                         "instead of the train step (JSON compatible "
                         "with perf_gate --subset serving)")
    ap.add_argument("--serving-requests", type=int, default=12,
                    help="requests in the seeded serving trace")
    ap.add_argument("--slots", type=int, default=3,
                    help="decode slots for --serving")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size for --serving")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace seed for --serving")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="fraction of --serving requests rewritten to "
                         "share one seeded system prompt (drawn from a "
                         "SEPARATE rng stream: the default trace stays "
                         "byte-identical)")
    ap.add_argument("--prefix-len", type=int, default=32,
                    help="length of the shared system prompt for "
                         "--shared-prefix-frac")
    ap.add_argument("--prefix-cache", type=int, default=None,
                    help="MXTPU_PREFIX_CACHE for the engine (None = env)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="MXTPU_PREFILL_CHUNK for the engine (None = env)")
    ap.add_argument("--spec-ngram", type=int, default=None,
                    help="MXTPU_SPEC_NGRAM for the engine (None = env)")
    ap.add_argument("--spec-lookahead", type=int, default=None,
                    help="MXTPU_SPEC_LOOKAHEAD for the engine (None = env)")
    ap.add_argument("--serving-tag", default="",
                    help="suffix for the output metric name (serving_TAG) "
                         "so lever configurations gate against their own "
                         "perf_gate baseline family")
    ap.add_argument("--verify-tokens", action="store_true",
                    help="after the measured trace, recompute every "
                         "request with sequential generate() and report "
                         "token_identity (1.0 = greedy decode identical)")
    ap.add_argument("--metrics-out",
                    help="after --serving, write the telemetry registry "
                         "snapshot (dump_json) here — the CI observability "
                         "leg cross-checks it against the trace_merge "
                         "--requests report")
    ap.add_argument("--inject-latency", type=float, default=0.0,
                    help="latency-inflation factor for the SLO negative "
                         "self-test: scales the engine's injectable clock "
                         "so every measured latency (TTFT, queue wait, "
                         "request seconds) inflates by this factor "
                         "without slowing the run; 0/1 = off")
    args = ap.parse_args()

    if args.serving:
        return serving_bench(args)

    import jax
    from jax.sharding import Mesh
    from incubator_mxnet_tpu.models import transformer as tfm

    devices = jax.devices()[:1]
    mesh = Mesh(np.array(devices).reshape(1, 1, 1),
                axis_names=("dp", "ep", "tp"))
    cfg = tfm.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_len=args.seq,
        dtype=args.dtype, use_flash=args.flash,
        use_fused_xent=args.fused_xent)
    step, params = tfm.make_gspmd_train_step(mesh, cfg)

    rng = np.random.RandomState(0)
    tok = rng.randint(0, args.vocab, (args.batch, args.seq)).astype(np.int32)
    tgt = rng.randint(0, args.vocab, (args.batch, args.seq)).astype(np.int32)

    t0 = time.perf_counter()
    loss, params = step(params, tok, tgt)
    float(loss)
    compile_s = time.perf_counter() - t0
    for _ in range(args.warmup - 1):
        loss, params = step(params, tok, tgt)
    float(loss)

    start = time.perf_counter()
    for _ in range(args.iters):
        loss, params = step(params, tok, tgt)
    float(loss)
    elapsed = time.perf_counter() - start

    tokens = args.batch * args.seq * args.iters
    tps = tokens / elapsed
    # 6 * params * tokens is the standard fwd+bwd FLOP estimate
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    flops = 6.0 * n_params * tokens / elapsed
    out = {
        "metric": "transformer_train_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "params": n_params,
        "model_tflops": round(flops / 1e12, 2),
        "compile_s": round(compile_s, 1),
        "loss": float(loss),
        "platform": devices[0].platform,
        "dtype": args.dtype,
        "config": vars(args),
    }

    if args.decode_steps > 0:
        # KV-cache generation throughput: one jitted scan program.
        # Prefill time is measured separately and subtracted so the
        # number is decode-only and comparable across decode_steps.
        import jax.numpy as jnp

        prompt_len = min(32, args.seq // 2)
        steps = min(args.decode_steps, cfg.max_len - prompt_len)
        if steps < args.decode_steps:
            out["decode_note"] = (f"decode_steps clamped to {steps} "
                                  f"(max_len {cfg.max_len})")
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(
                0, args.vocab, (args.batch, prompt_len)), jnp.int32)
        max_len = prompt_len + steps

        def prefill_only(p, x):
            cache = tfm.init_kv_cache(cfg, args.batch, max_len)
            _, logits = tfm.prefill(p, cache, x, cfg)
            return logits

        # a device_get of a slice of the LAST output closes the
        # stream-ordered dispatch chain
        def sync(o):
            return jax.device_get(jnp.ravel(o)[0])

        gen = jax.jit(lambda p, x: tfm.generate(p, x, steps, cfg,
                                                max_len=max_len))
        pre = jax.jit(prefill_only)
        sync(gen(params, prompt))  # compile
        sync(pre(params, prompt))
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            toks = gen(params, prompt)
        sync(toks)
        t_gen = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            lg = pre(params, prompt)
        sync(lg)
        t_pre = time.perf_counter() - t0
        out["decode_tokens_per_sec"] = round(
            args.batch * steps * reps / max(t_gen - t_pre, 1e-9), 1)
        out["decode_steps"] = steps
        out["prefill_tokens_per_sec"] = round(
            args.batch * prompt_len * reps / max(t_pre, 1e-9), 1)

    print(json.dumps(out))


def _pct(values, q):
    if not values:
        return 0.0
    vals = sorted(values)
    idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
    return vals[idx]


def serving_bench(args):
    """Continuous-batching engine on a seeded mixed-length trace.

    Two phases: a warmup wave that touches every prefill bucket the
    trace uses (compiles happen here, or resolve from the compile
    cache), then the measured trace with staggered arrivals. The
    structural counters the perf gate zero-tolerates — steady-state
    compiles/retraces and dense fallbacks — are deltas over the
    measured phase only; wall-time ratios are report-only.
    """
    from incubator_mxnet_tpu import compile_cache, telemetry

    # registration of jit signatures with compilereg rides the executable
    # cache wrapper, so the bench needs both on BEFORE the engine builds.
    # Its entries sit beside JAX's own, under the one cache directory
    os.environ.setdefault(
        "MXTPU_COMPILE_CACHE_DIR",
        os.path.join(compile_cache.jax_cache_dir(), "mxtpu-executables"))
    from incubator_mxnet_tpu.telemetry import compilereg
    from incubator_mxnet_tpu.models import transformer as tfm
    from incubator_mxnet_tpu.serving import ServingEngine
    from incubator_mxnet_tpu.ops.pallas_kernels import (
        DENSE_FALLBACKS_TOTAL)
    import jax

    telemetry.enable()
    cfg = tfm.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_len=args.seq,
        dtype=args.dtype)
    params = tfm.init_params(cfg, seed=0)
    factor = args.inject_latency
    if factor and factor != 1.0:
        # seeded latency inflation: the engine times everything off its
        # injectable clock, so scaling it inflates every per-request
        # latency sample deterministically — the SLO negative self-test
        clock = lambda: time.monotonic() * factor  # noqa: E731
    else:
        clock = time.monotonic
    eng = ServingEngine(params, cfg, slots=args.slots,
                        page_size=args.page_size, clock=clock,
                        prefix_cache=args.prefix_cache,
                        prefill_chunk=args.prefill_chunk,
                        spec_ngram=args.spec_ngram,
                        spec_lookahead=args.spec_lookahead)

    rng = np.random.RandomState(args.seed)
    max_prompt = max(4, min(cfg.max_len // 2, 3 * cfg.max_len // 4))
    trace = []
    for i in range(args.serving_requests):
        p_len = int(rng.randint(2, max_prompt))
        m_new = int(rng.randint(1, min(16, cfg.max_len - p_len)))
        trace.append({
            "arrival_step": int(rng.randint(0, 2 * args.serving_requests)),
            "prompt": rng.randint(1, cfg.vocab, p_len).astype(np.int32),
            "max_new": m_new})
    trace.sort(key=lambda r: r["arrival_step"])
    if args.shared_prefix_frac > 0:
        # shared-system-prompt mode: a seeded fraction of requests is
        # rewritten to one common prefix + a short private tail — the
        # workload prefix caching exists for. Drawn from a SEPARATE rng
        # stream so the default trace's draw order is untouched.
        rng2 = np.random.RandomState(args.seed + 1)
        pl = max(1, min(args.prefix_len, 3 * cfg.max_len // 4 - 2))
        shared = rng2.randint(1, cfg.vocab, pl).astype(np.int32)
        n_share = int(round(args.shared_prefix_frac * len(trace)))
        picked = rng2.choice(len(trace), size=n_share, replace=False)
        for i in sorted(int(j) for j in picked):
            r = trace[i]
            new_len = max(int(r["prompt"].size), pl + 2)
            tail = rng2.randint(1, cfg.vocab,
                                new_len - pl).astype(np.int32)
            r["prompt"] = np.concatenate([shared, tail])
            r["max_new"] = max(1, min(r["max_new"],
                                      cfg.max_len - new_len))
            r["shared"] = True

    # warmup: one request per distinct bucket the trace will hit (a
    # prompt of exactly the bucket length lands in that bucket)
    buckets = sorted({eng._bucket_for(r["prompt"].size) for r in trace})
    for b in buckets:
        # the top bucket equals max_len; clamp so prompt+max_new fits
        # (no-op for every bucket below it: identical legacy draws)
        eng.submit(rng.randint(1, cfg.vocab,
                               min(b, cfg.max_len - 2)).astype(np.int32), 2)
    eng.run()
    warm_results = len(eng.results())

    def reg_totals():
        snap = compilereg.snapshot()
        return (sum(v["signatures"] for v in snap.values()),
                sum(v["retraces"] for v in snap.values()))

    sigs0, re0 = reg_totals()
    # lever counters are cumulative on the engine; snapshot them so the
    # reported figures are measured-phase deltas (the bucket-warmup wave
    # populates the prefix cache but must not count as hits/saves)
    lever0 = (eng._prefix_lookups, eng._prefix_hits,
              eng._prefix_tokens_saved, eng._cow_copies,
              eng._spec_proposed, eng._spec_accepted,
              eng.goodput()["prefill"])
    occupancy, utilization = [], []
    # head-of-line blocking bound: the most prefill tokens any single
    # step computed. Deterministic (seeded trace, counted rows), and it
    # is the term that drives short-request p99 TTFT under load — the
    # chunked-prefill CI gate compares it off-vs-on because wall-clock
    # TTFT on CPU interpret kernels is dominated by per-call overhead.
    prefill_prev = eng.goodput()["prefill"]
    max_step_prefill = 0
    t0 = time.perf_counter()
    pending = list(trace)
    while pending or eng.queue_depth or eng.slots_in_use:
        while pending and pending[0]["arrival_step"] <= eng.steps:
            r = pending.pop(0)
            r["rid"] = eng.submit(r["prompt"], r["max_new"])
        eng.step()
        occupancy.append(eng.slots_in_use)
        utilization.append(
            eng.allocator.num_in_use / max(1, eng.allocator.capacity))
        prefill_cur = eng.goodput()["prefill"]
        max_step_prefill = max(max_step_prefill, prefill_cur - prefill_prev)
        prefill_prev = prefill_cur
    elapsed = time.perf_counter() - t0
    sigs1, re1 = reg_totals()

    results = {k: v for k, v in eng.results().items()}
    done = [results[r["rid"]] for r in trace if "rid" in r]
    gen_tokens = sum(len(r.tokens) for r in done)
    latencies = [r.latency_s for r in done]
    fallbacks = sum(
        ch.value for _, ch in
        telemetry.REGISTRY.counter(DENSE_FALLBACKS_TOTAL).series())

    # short-vs-long p99 TTFT split: classified by prompt length against
    # the trace median so an off-vs-on A/B compares identical cohorts
    median_len = float(np.median([r["prompt"].size for r in trace]))
    ttft_short = [r.ttft_s for r in done if r.prompt_len <= median_len]
    ttft_long = [r.ttft_s for r in done if r.prompt_len > median_len]

    tag = f"serving_{args.serving_tag}" if args.serving_tag else "serving"
    out = {
        "metric": tag,
        "requests_completed": len(done),
        "tokens_per_sec": round(gen_tokens / max(elapsed, 1e-9), 1),
        "p50_latency_s": round(_pct(latencies, 0.50), 4),
        "p99_latency_s": round(_pct(latencies, 0.99), 4),
        "mean_slot_occupancy": round(float(np.mean(occupancy)), 3),
        "mean_page_utilization": round(float(np.mean(utilization)), 3),
        "steady_compiles": (sigs1 - sigs0),
        "steady_retraces": (re1 - re0),
        "dense_fallbacks": fallbacks,
        "engine_steps": eng.steps,
        "warmup_requests": warm_results,
        "slots": args.slots,
        "page_size": args.page_size,
        "platform": jax.devices()[0].platform,
        "seed": args.seed,
    }
    # goodput split + SLO verdicts ride along as non-numeric-safe extras
    # (perf_gate flattens only numeric leaves; dicts are skipped, and no
    # baseline names these keys, so existing serving.* baselines hold)
    goodput = eng.goodput()
    out["goodput"] = round(goodput["fraction"], 4)
    out["tokens_split"] = {k: goodput[k] for k in
                           ("prefill", "decode", "pad", "wasted_evicted")}
    out["ttft_p99_short_s"] = round(_pct(ttft_short, 0.99), 4)
    out["ttft_p99_long_s"] = round(_pct(ttft_long, 0.99), 4)
    out["max_step_prefill_tokens"] = max_step_prefill
    if eng.prefix_cache is not None:
        lookups = eng._prefix_lookups - lever0[0]
        hits = eng._prefix_hits - lever0[1]
        saved = eng._prefix_tokens_saved - lever0[2]
        computed = goodput["prefill"] - lever0[6]
        out["prefix_hit_rate"] = round(hits / max(1, lookups), 4)
        out["prefill_tokens_saved"] = saved
        out["prefill_tokens_saved_frac"] = round(
            saved / max(1, saved + computed), 4)
        out["cow_copies"] = eng._cow_copies - lever0[3]
        out["prefix_cached_pages"] = eng.prefix_cache.cached_pages
        out["prefix_evictions"] = eng.prefix_cache.evictions
    if eng.spec_ngram:
        proposed = eng._spec_proposed - lever0[4]
        accepted = eng._spec_accepted - lever0[5]
        out["spec_proposed_tokens"] = proposed
        out["spec_accepted_tokens"] = accepted
        out["spec_acceptance"] = round(accepted / max(1, proposed), 4)
    if eng.prefill_chunk:
        out["prefill_chunks"] = eng._prefill_chunks
    if args.verify_tokens:
        # the hard gate: greedy decode through every enabled lever must
        # be token-identical to sequential generate() (outside the
        # timed window, so it never skews the wall-clock figures)
        import jax.numpy as jnp
        identical = True
        for r in trace:
            if "rid" not in r:
                continue
            got = np.asarray(results[r["rid"]].tokens)
            if got.size == 0:
                continue
            ref = np.asarray(tfm.generate(
                params, jnp.asarray(r["prompt"])[None], got.size,
                cfg))[0]
            if not np.array_equal(got, ref):
                identical = False
                break
        out["token_identity"] = float(identical)
    if eng.slo is not None:
        slo_snap = eng.slo.snapshot()
        out["slo"] = {name: row["state"] for name, row in slo_snap.items()}
        out["slo_breaches"] = {name: row["breaches"]
                               for name, row in slo_snap.items()}
    telemetry.distributed.flush()  # traced runs: close out the frames
    if args.metrics_out:
        telemetry.dump_json(args.metrics_out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    main()
