#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix, makes weights and inputs
from the seed, warms up every shape the mix uses (all of that is set-up),
measures for --seconds, checks the outputs, and prints one JSON object as
the last line of stdout: `correct`, `attempted`, `failed`, `metrics`,
`device`, and with --trace 1 `breakdown`. With --trace 0 the metrics are
the cell's end-to-end metrics, taken with the profiler off; with --trace 1
they are its per-layer metrics, and the last seconds of the window are
traced. The line before it, {"detail": ...}, is for people.

`setup_s` is the time from the process's start to the window's opening,
less `chip_s`: the first `jax.devices()`, in which the runtime starts and
is handed the chip. What the program decides stays in it (its imports,
weights, compiles or cache loads, warm-up, the check, the lead-in); the
runtime's start does not, and it swings by seconds from run to run. The
detail line's `setup_reached_s` says when each stage ended (`imports`,
`chip`, `compile_cache`, `peaks`, then the job's own), with `chip_s` and
`window_opened_s` beside it.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result. --rehearse walks the same code on
the CPU at the tiny sizes each file keeps under "rehearse"; what it
prints names the platform `cpu` and is never a measurement. The driver
never passes it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device as _device  # noqa: E402
from benchmark.harness import loader, tracered  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


@dataclasses.dataclass
class Context:
    """What a job gets."""
    cell: loader.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list              # the chips the cell runs on
    compiles: _device.CompileCounter
    trace_dir: str
    root: str = ROOT
    marks: dict = dataclasses.field(default_factory=dict)

    def mark(self, stage):
        _mark(self.marks, stage)


def _mark(marks, stage):
    """Notes that set-up has reached `stage`, in seconds since the process
    started; the detail line shows what filled the set-up."""
    marks[stage] = round(time.perf_counter() - T_PROCESS, 4)


def _backend_started():
    """Whether JAX has started a backend in this process yet: an import
    that starts one would move the runtime's start out of the chip stage."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(bridge and bridge.backends_are_initialized())


@dataclasses.dataclass
class Run:
    """What a reader gets: the job's facts, the reduced trace (None with
    --trace 0), the chip's published peaks and the cell."""
    cell: loader.Cell
    facts: dict
    trace: dict | None
    peaks: dict | None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    marks = {}
    # the cell, its job module and all the job imports: the program
    cell = loader.load_cell(args.workload, rehearse=args.rehearse)
    job = loader.load_job(cell.traffic)
    opclasses = loader.load_opclasses(cell.traffic["job"])
    started_by_imports = _backend_started()
    _mark(marks, "imports")

    # the runtime's start and the chip's hand-over: left out of setup_s
    t_chip = time.perf_counter()
    devices = _device.require_chips(cell.chips, args.rehearse)
    chip_s = time.perf_counter() - t_chip
    _mark(marks, "chip")

    from incubator_mxnet_tpu import compile_cache

    cache_dir = compile_cache.enable_jax_cache()
    _mark(marks, "compile_cache")
    # a traced run leaves its trace here until the cell's next run
    trace_dir = os.path.join(TRACE_DIR, cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), rehearse=args.rehearse,
                  devices=devices, compiles=_device.CompileCounter(),
                  trace_dir=trace_dir, marks=marks)
    # unknown chips fail here, before the run, not after it
    peaks = (None if args.rehearse
             else loader.peaks(devices[0].device_kind))
    ctx.mark("peaks")
    facts = job(ctx)
    window_opened_s = facts["window_start"] - T_PROCESS
    facts["setup_s"] = window_opened_s - chip_s

    reduced = None
    if args.trace:
        reduced = tracered.summarize(
            tracered.read_xplane(tracered.find_xplane(trace_dir),
                                 rehearse=args.rehearse), opclasses)
    run = Run(cell=cell, facts=facts, trace=reduced, peaks=peaks)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.reader(run, **m.args)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    # each number the verdict compares, with its limit
    checks = {**facts.get("checks", {}),
              "failed": [int(facts["failed"]), 0],
              "compiles_in_window": [facts["compiles_in_window"], 0]}
    dev = _device.describe(devices)
    result = {
        "correct": bool(facts["correct"]
                        and facts["compiles_in_window"] == 0),
        "attempted": int(facts["attempted"]),
        "failed": int(facts["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    detail = {"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "rehearsal": args.rehearse,
              "compile_cache": cache_dir,
              "compiles_in_window": facts["compiles_in_window"],
              "wall_s": time.perf_counter() - T_PROCESS,
              "setup_reached_s": ctx.marks,
              "chip_s": chip_s,
              "window_opened_s": window_opened_s,
              "backend_started_by_imports": started_by_imports,
              **facts.get("detail", {})}
    print(json.dumps({"detail": detail}), flush=True)
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
