#!/usr/bin/env python3
"""Runs cells the way the driver does and reports the spread: for each cell
`--sets` sets of `--runs` runs, each run a new process with another seed,
and for each metric the median and spread (distance between the quartiles
over the median) of each set. The bounds in BENCHMARK.json are set from the
wider of a metric's two spreads. A traced run per cell can follow.

    chiprun -- python3 benchmark/tools/measure.py --cells a,b --runs 6 --sets 2

A knee is found the same way: one mix file and one `workloads` entry per
rate in a scratch copy of the repo, one run each, and the detail lines say
where the backlog starts to grow (benchmark/README.md).

This parent never touches JAX (one process per chip): every run is a child
that holds the chip alone. Each run's result and detail lines are appended
to chiprun_out/measure_<tag>.jsonl as they come, and the summary is printed
at the end and written to chiprun_out/measure_<tag>.summary.json.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import loader, stats  # noqa: E402


def run_once(cell, seed, seconds, trace):
    cmd = loader.benchmark_json()["command"] + [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    record = {"cell": cell, "seed": seed, "trace": trace, "rc": p.returncode,
              "wall_s": wall}
    if p.returncode != 0 or not lines:
        record["stderr_tail"] = p.stderr[-3000:]
        return record
    record["result"] = json.loads(lines[-1])
    if len(lines) > 1 and lines[-2].startswith('{"detail"'):
        record["detail"] = json.loads(lines[-2])["detail"]
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs per cell after the sets")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=1000)
    ap.add_argument("--tag", default="run")
    args = ap.parse_args()
    seconds = args.seconds or loader.benchmark_json()["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, f"measure_{args.tag}.jsonl")
    seed = args.seed0
    summary = {}
    with open(log_path, "a") as log:
        for cell in args.cells.split(","):
            sets = []
            for s in range(args.sets):
                values = {}
                for _ in range(args.runs):
                    rec = run_once(cell, seed, seconds, 0)
                    rec["set"] = s
                    seed += 1
                    log.write(json.dumps(rec) + "\n")
                    log.flush()
                    res = rec.get("result")
                    print(f"[{cell} set {rec['set']} seed {rec['seed']}] "
                          f"rc {rec['rc']} wall {rec['wall_s']:.1f}s "
                          f"{json.dumps(res) if res else rec.get('stderr_tail')}"
                          f" {json.dumps(rec.get('detail', {}))}",
                          flush=True)
                    for name, m in (res or {}).get("metrics", {}).items():
                        values.setdefault(name, []).append(m["value"])
                sets.append(values)
            cell_summary = {}
            for name in sets[0] if sets else ():
                per_set = []
                for values in sets:
                    v = values.get(name, [])
                    # set-up is judged without each side's first, compiling run
                    v = v[1:] if name == "setup_s" and len(v) > 1 else v
                    if v:
                        per_set.append({"n": len(v),
                                        "median": stats.percentile(v, 50),
                                        "spread": (stats.spread(v)
                                                   if len(v) > 1 else None),
                                        "values": v})
                if per_set:
                    cell_summary[name] = {
                        "sets": per_set,
                        "widest_spread": max(
                            (s["spread"] for s in per_set
                             if s["spread"] is not None), default=None)}
            summary[cell] = cell_summary
            for _ in range(args.traced):
                rec = run_once(cell, seed, seconds, 1)
                seed += 1
                log.write(json.dumps(rec) + "\n")
                log.flush()
                print(f"[{cell} traced seed {rec['seed']}] rc {rec['rc']} "
                      f"wall {rec['wall_s']:.1f}s "
                      f"{json.dumps(rec.get('result') or rec.get('stderr_tail'))}",
                      flush=True)
    with open(os.path.join(out_dir, f"measure_{args.tag}.summary.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    print("SUMMARY " + json.dumps(
        {c: {m: {"medians": [s["median"] for s in v["sets"]],
                 "spreads": [s["spread"] and round(s["spread"], 5)
                             for s in v["sets"]]}
             for m, v in ms.items()} for c, ms in summary.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
