#!/usr/bin/env python3
"""Runs cells the way the driver does and reports the spread: for each cell
`--sets` sets of `--runs` runs, each run a new process with another seed,
and for each metric the median and spread (distance between the quartiles
over the median) of each set. The bounds in BENCHMARK.json are set from the
wider of a metric's two spreads. A traced run per cell can follow.

Set-up is taken apart too: from each run's detail line, the seconds each
stage of `setup_reached_s` took (`imports`, `chip`, `compile_cache`,
`peaks`, the job's own stages, and `to_window` from the last mark to the
window's opening), `setup_s` as judged (the chip stage left out) and
`setup_with_chip_s` (the whole of it, as it was judged before), each with
its median, its quartile distance in seconds and its spread per set. Each
set's first run, which compiles, is left out, as set-up is judged.

    chiprun -- python3 benchmark/tools/measure.py --cells a,b --runs 6 --sets 2

With --same-seeds every set takes the same seeds (seed0, seed0 + 1, ...),
so that two runs of one seed can be held against runs of other seeds:
where they agree far more closely, the seed changes the work. --warm n
makes n runs of a cell before its sets, counted in none (the first run
of a cell compiles; it is logged and printed, to be held against the
others). Beside each set's spread the summary gives its spread with the
run farthest from its median left out, and the mean of those over the
sets: the spread a bound has to hold at half its size.

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
import statistics
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


def stage_seconds(detail):
    """Seconds each set-up stage took, from the detail line's marks (each
    the time since the process started at which a stage ended)."""
    out, last = {}, 0.0
    for stage, at in detail.get("setup_reached_s", {}).items():
        out[stage] = at - last
        last = at
    if "window_opened_s" in detail:
        out["to_window"] = detail["window_opened_s"] - last
        out["setup_with_chip_s"] = detail["window_opened_s"]
        out["setup_s"] = detail["window_opened_s"] - detail["chip_s"]
    return out


def spread_of(values):
    """The median, the distance between the quartiles in seconds, and
    that distance over the median (None at a median of 0)."""
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return {"n": len(values), "median": med, "iqr": q3 - q1,
            "spread": stats.spread(values) if med else None}


def trimmed_spread(values):
    """The spread with the value farthest from the median left out."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = values[:far] + values[far + 1:]
    return stats.spread(rest) if len(rest) > 1 else None


def setup_table(records):
    """{stage: [per set: spread_of(its seconds)]} over each set's runs but
    its first."""
    by_set = {}
    for rec in records:
        if "detail" in rec:
            by_set.setdefault(rec["set"], []).append(
                stage_seconds(rec["detail"]))
    table = {}
    for s in sorted(by_set):
        runs = by_set[s][1:]
        for stage in (runs[0] if len(runs) > 1 else ()):
            values = [r[stage] for r in runs if stage in r]
            if len(values) > 1:
                table.setdefault(stage, []).append(spread_of(values))
    return table


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs per cell after the sets")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=1000)
    ap.add_argument("--same-seeds", action="store_true",
                    help="every set takes the same seeds")
    ap.add_argument("--warm", type=int, default=0,
                    help="runs per cell before its sets, in no set")
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
            sets, records = [], []
            for _ in range(args.warm):
                rec = run_once(cell, seed, seconds, 0)
                rec["set"] = "warm"
                seed += 1
                log.write(json.dumps(rec) + "\n")
                log.flush()
                print(f"[{cell} warm seed {rec['seed']}] rc {rec['rc']} "
                      f"wall {rec['wall_s']:.1f}s "
                      f"{json.dumps(rec.get('result') or rec.get('stderr_tail'))}"
                      f" {json.dumps(rec.get('detail', {}))}", flush=True)
            first = seed
            for s in range(args.sets):
                values = {}
                if args.same_seeds:
                    seed = first
                for _ in range(args.runs):
                    rec = run_once(cell, seed, seconds, 0)
                    rec["set"] = s
                    seed += 1
                    records.append(rec)
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
                                        "trimmed_spread": (
                                            trimmed_spread(v)
                                            if len(v) > 2 else None),
                                        "values": v})
                if per_set:
                    cell_summary[name] = {
                        "sets": per_set,
                        "widest_spread": max(
                            (s["spread"] for s in per_set
                             if s["spread"] is not None), default=None),
                        "mean_trimmed_spread": (statistics.mean(
                            s["trimmed_spread"] for s in per_set)
                            if all(s.get("trimmed_spread") is not None
                                   for s in per_set) else None)}
            cell_summary["setup_stages"] = setup_table(records)
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
                             for s in v["sets"]],
                 "trimmed": [s.get("trimmed_spread") and round(
                     s["trimmed_spread"], 5) for s in v["sets"]]}
             for m, v in ms.items() if m != "setup_stages"}
         for c, ms in summary.items()}))
    for c, ms in summary.items():
        for stage, per_set in ms["setup_stages"].items():
            print(f"SETUP {c} {stage}: " + " | ".join(
                f"median {s['median']:.4f} s, iqr {s['iqr']:.4f} s, spread "
                + (f"{s['spread']:.4f}" if s["spread"] is not None else "-")
                for s in per_set))
    return 0


if __name__ == "__main__":
    sys.exit(main())
