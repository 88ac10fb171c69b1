#!/usr/bin/env python
"""Config sweep over the headline train step (needs a TPU, like bench.py).

The round-5 profiler finding (docs/PERF_ANALYSIS.md §0): the bf16 step is
HBM-bandwidth-bound and batch 256 REGRESSES (remat/spill). Each config
runs bench.py's own child (BENCH_CHILD=1), one process per config so each
holds the chip alone (this parent never imports jax), and logs one JSON
line per config — including `bytes_per_step` from XLA's cost model, so
the traffic levers (remat policy, fused epilogue, stochastic rounding)
report the byte reduction next to the throughput they buy. A config that
times out or crashes is logged and makes the sweep exit non-zero.

Usage: python tools/bench_sweep.py [--configs a,b,...]
                                   [--remat-policy P] [--fused-epilogue]
(--remat-policy / --fused-epilogue overlay EVERY selected config — e.g.
`--configs base,bs256 --remat-policy convs` reruns the regression pair
under the selective policy.)
Configs (comma list; default all):
  bs64        bf16 NHWC batch 64   (below the spill threshold?)
  bs96        bf16 NHWC batch 96
  base        bf16 NHWC batch 128  (the headline configuration, for control)
  bs256       bf16 NHWC batch 256  (the measured regression case)
  remat       bf16 NHWC batch 128 + blanket jax.checkpoint (legacy)
  remat-convs bf16 NHWC batch 128 + MXTPU_REMAT_POLICY=convs
  bs256-convs bf16 NHWC batch 256 + MXTPU_REMAT_POLICY=convs
  epilogue    bf16 NHWC batch 128 + MXTPU_FUSED_EPILOGUE=1
  sr          bf16 NHWC batch 128 + MXTPU_STOCHASTIC_ROUNDING=1
  nchw        bf16 NCHW batch 128  (layout control)
Log: one timestamped file under tools/bench_results/ (+ stdout); the
directory is gitignored so sweep runs never dirty the tree.
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(REPO, "tools", "bench_results")

CONFIGS = {
    "bs64": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "64"},
    "bs96": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "96"},
    "base": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128"},
    "bs256": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "256"},
    "remat": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128",
              "BENCH_REMAT": "1"},
    "remat-convs": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128",
                    "BENCH_REMAT_POLICY": "convs"},
    "bs256-convs": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "256",
                    "BENCH_REMAT_POLICY": "convs"},
    "epilogue": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128",
                 "MXTPU_FUSED_EPILOGUE": "1"},
    "sr": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128",
           "MXTPU_STOCHASTIC_ROUNDING": "1"},
    "nchw": {"BENCH_DTYPE": "bfloat16", "BENCH_BATCH": "128",
             "BENCH_LAYOUT": "NCHW"},
}

_log_path = None


def log(msg):
    line = f"[{time.strftime('%H:%M:%S')}] {msg}"
    print(line, flush=True)
    with open(_log_path, "a") as f:
        f.write(line + "\n")


def main():
    global _log_path
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--remat-policy", default=None,
                    help="overlay MXTPU_REMAT_POLICY on every config")
    ap.add_argument("--shard-policy", default=None,
                    choices=("replicated", "zero1", "zero2"),
                    help="overlay BENCH_SHARD_POLICY on every config "
                         "(ZeRO-sharded optimizer state over all visible "
                         "devices; the child logs per-role ledger bytes)")
    ap.add_argument("--fused-epilogue", action="store_true",
                    help="overlay MXTPU_FUSED_EPILOGUE=1 on every config")
    ap.add_argument("--results-dir", default=RESULTS_DIR,
                    help="directory for sweep logs (created if missing)")
    args = ap.parse_args()
    os.makedirs(args.results_dir, exist_ok=True)
    _log_path = os.path.join(
        args.results_dir,
        time.strftime("bench_sweep_%Y%m%d_%H%M%S.log"))
    log(f"sweep start: configs={args.configs} "
        f"remat_policy={args.remat_policy} "
        f"shard_policy={args.shard_policy} "
        f"fused_epilogue={args.fused_epilogue} -> {_log_path}")
    failed = []
    for name in args.configs.split(","):
        cfg = CONFIGS[name.strip()]
        env = dict(os.environ)
        env.update(cfg)
        if args.remat_policy is not None:
            env["BENCH_REMAT_POLICY"] = args.remat_policy
        if args.shard_policy is not None:
            env["BENCH_SHARD_POLICY"] = args.shard_policy
        if args.fused_epilogue:
            env["MXTPU_FUSED_EPILOGUE"] = "1"
        env["BENCH_CHILD"] = "1"
        env.setdefault("BENCH_ITERS", "20")
        t0 = time.perf_counter()
        try:
            p = subprocess.run([sys.executable,
                                os.path.join(REPO, "bench.py")],
                               capture_output=True, text=True,
                               timeout=args.timeout, env=env)
        except subprocess.TimeoutExpired:
            log(f"{name}: TIMEOUT after {args.timeout}s")
            failed.append(name)
            continue
        line = None
        for ln in reversed((p.stdout or "").strip().splitlines()):
            try:
                d = json.loads(ln)
            except ValueError:
                continue
            if isinstance(d, dict) and "ips" in d:
                line = d
                break
        if p.returncode != 0 or line is None:
            log(f"{name}: rc={p.returncode} "
                f"(stderr: {(p.stderr or '').strip()[-300:]})")
            failed.append(name)
            continue
        line["config"] = name
        line["wall_s"] = round(time.perf_counter() - t0, 1)
        log(json.dumps(line))
    if failed:
        sys.exit(f"bench_sweep: {len(failed)} config(s) failed: "
                 f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
