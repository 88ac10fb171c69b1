#!/usr/bin/env python
"""On-chip cost summary of the headline ResNet-50 training step.

Answers "is the step compute-bound, and at what efficiency": builds the
model/optimizer/step configuration bench.py's child measures (through
bench.build_train_step — NHWC, bs128, fp32 or bf16), then reports the
compiled executable's XLA cost analysis (FLOPs, bytes accessed) next to
the measured step time, giving achieved TFLOP/s and, for bf16, the share
of the chip's published bf16 peak (bench.DEVICE_PEAKS). For per-op
attribution use `mx.profiler` traces. Needs a TPU, like bench.py.

Usage: python tools/profile_resnet.py [--dtype bfloat16] [--batch 128]
Prints one JSON line.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import bench

    target = bench.require_tpu()[0]
    from incubator_mxnet_tpu import compile_cache

    compile_cache.enable_jax_cache()
    step = bench.build_train_step(args.dtype, args.batch, device=target)
    x, y = bench.synthetic_batch(0, args.dtype, args.batch, args.image)

    # warm + compile (the value fetch closes the timing)
    t0 = time.perf_counter()
    step(x, y).asnumpy()
    compile_s = time.perf_counter() - t0

    # XLA's own cost model for the compiled step (AOT-lower the same jitted
    # function __call__ executes; nothing runs, so donation is harmless)
    cost = {}
    try:
        from incubator_mxnet_tpu import random as _rng_mod

        lowered = step._step.lower(
            step._params, step._states, x._data, y._data,
            _rng_mod.next_key(), jnp.asarray(0.05, jnp.float32),
            jnp.asarray(1.0, jnp.float32))
        cost = lowered.compile().cost_analysis()
    except Exception as e:  # cost analysis is best-effort across backends
        cost = {"error": str(e)[:200]}

    # timed step
    t0 = time.perf_counter()
    loss = None
    for _ in range(args.iters):
        loss = step(x, y)
    loss.asnumpy()  # the fetch closes the chained-step sequence
    step_ms = (time.perf_counter() - t0) / args.iters * 1e3

    flops = float(cost.get("flops", 0.0))
    # the one peaks table; an unknown device_kind is an error there. No
    # fp32 peak is published, so only bf16 gets a utilization
    peak = bench.device_peaks(target.device_kind)["bf16_flops"]
    out = {
        "tool": "profile_resnet",
        "dtype": args.dtype,
        "platform": target.platform,
        "device_kind": target.device_kind,
        "batch": args.batch,
        "compile_s": round(compile_s, 1),
        "step_ms": round(step_ms, 2),
        "ips": round(args.batch / (step_ms / 1e3), 1),
        "xla_flops_per_step": flops,
        "achieved_tflops": round(flops / (step_ms / 1e3) / 1e12, 1)
        if flops else None,
        "mfu_vs_xla_flops": round(flops / (step_ms / 1e3) / peak, 3)
        if flops and args.dtype == "bfloat16" else None,
        "xla_bytes_accessed": cost.get("bytes accessed"),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
