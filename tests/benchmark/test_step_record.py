"""benchmark/readers/step_record.py: what `ServingEngine.step()` says it did,
read back from a profile's event stats and from the flight recorder's ring.
Every expected number is worked out here.

One thread's steps (us; the device's ops beside them), each `serving.step`
with its five counts (dispatched, ahead, landed, prefills, finished) and the
`serving.fetch` inside it:

  A     0..1000   1 1 1 0 0  plain       fetch  100..900
  B  1100..2000   0 0 1 0 1  landing     fetch 1150..1700
  C  2100..3500   1 0 0 1 0  admitting   fetch 2300..3300  (the prefill's)
  D  3600..4500   1 1 1 0 0  plain       fetch 3650..4400
  E  4600..9600   1 1 1 0 0  plain       fetch 4650..9500  (a stall)
  F  9700..9710   0 0 0 0 0  idle

  device busy 0..1600, 2400..3400, 3440..4550, 4560..5000, 9000..9650 in a
  window of 0..10000: gaps 1600..2400 (B 400, nobody 100, C 300),
  3400..3440 and 4550..4560 (under 50 us: no gaps), 5000..9000 (E 4000),
  9650..10000 (F 10, nobody 340).

Idle by class: plain 4000, landing 400, admitting 300, idle 10: 4710 us in
six steps, which is what `engine_exposed_idle_ms_per_step` reads (785 us a
step); outside plain steps 710 us for the one request that finished.
Dispatches: A, C, D, E; ahead: A, D, E. Durations 1000, 900, 1400, 900,
5000 (F did nothing): median 1000, so E is over three medians, carried no
prefill, sat 4850 us in fetch with the device idle for 4000 and busy for
1000 of its 5000.
"""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark.harness import loader, tracered
from benchmark.readers import program_spans as ps
from benchmark.readers import step_record as sr

TESTDATA = os.path.join(loader.ROOT, "benchmark", "harness", "testdata")
RECORDED = os.path.join(TESTDATA, "cpu_engine_step_record.xplane.pb")
WITHOUT = os.path.join(TESTDATA, "cpu_engine_two_steps.xplane.pb")
with open(os.path.join(TESTDATA, "step_record_ring.json")) as _f:
    RING = json.load(_f)

K = 1000  # the table's microseconds in the trace's nanoseconds


def _step(start, end, counts, fetch=None):
    stats = dict(zip(sr.COUNTS, counts), step=0, live=16, queued=32)
    events = [["serving.step", start * K, (end - start) * K, stats]]
    if fetch:
        events.append(["serving.fetch", fetch[0] * K,
                       (fetch[1] - fetch[0]) * K, {}])
    return events


LINE = (_step(0, 1000, (1, 1, 1, 0, 0), (100, 900))
        + _step(1100, 2000, (0, 0, 1, 0, 1), (1150, 1700))
        + _step(2100, 3500, (1, 0, 0, 1, 0), (2300, 3300))
        + _step(3600, 4500, (1, 1, 1, 0, 0), (3650, 4400))
        + _step(4600, 9600, (1, 1, 1, 0, 0), (4650, 9500))
        + _step(9700, 9710, (0, 0, 0, 0, 0)))
OPS = [["op", s * K, (e - s) * K] for s, e in
       ((0, 1600), (2400, 3400), (3440, 4550), (4560, 5000), (9000, 9650))]
WINDOW = [0, 10000 * K]


def _run(lines, monkeypatch, ops=OPS, win=WINDOW):
    """A reader's `run` over hand-made lines of spans and one device."""
    monkeypatch.setattr(sr.tracered, "find_xplane", lambda d: d)
    monkeypatch.setattr(sr, "_spans_of", lambda path: lines)
    device = {"name": "/device:TPU:0", "ops": ops, "modules": []}
    busy = tracered.total(tracered.busy_intervals(device, win)) / 1e9
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="a_cell"), facts={}, peaks=None,
        trace={"window": win,
               "devices": [{"busy_s": busy, "device": device}]})


@pytest.mark.parametrize("counts,cls", [
    ((1, 1, 1, 0, 0), "plain"), ((1, 0, 1, 0, 0), "plain"),
    ((0, 0, 1, 0, 1), "landing"), ((0, 0, 1, 0, 2), "landing"),
    ((1, 0, 0, 1, 0), "admitting"), ((1, 0, 1, 2, 1), "admitting"),
    ((0, 0, 0, 1, 1), "admitting"), ((1, 0, 1, 0, 1), "finishing"),
    ((1, 0, 0, 0, 0), "starting"), ((0, 0, 0, 0, 0), "idle")])
def test_a_step_is_classed_from_its_five_counts(counts, cls):
    assert sr.step_class(dict(zip(sr.COUNTS, counts))) == cls


def test_steps_carry_their_counts_fetch_time_and_class():
    steps = sr.steps_of([list(reversed(LINE))], WINDOW)
    assert [(r["start"] // K, r["class"], r["fetch_ns"] // K)
            for r in steps] == [
        (0, "plain", 800), (1100, "landing", 550), (2100, "admitting", 1000),
        (3600, "plain", 750), (4600, "plain", 4850), (9700, "idle", 0)]
    assert [tuple(r[k] for k in sr.COUNTS) for r in steps[:3]] == [
        (1, 1, 1, 0, 0), (0, 0, 1, 0, 1), (1, 0, 0, 1, 0)]
    # a window that opens after A keeps the rest, as program_spans does
    assert len(sr.steps_of([LINE], [500 * K, 10000 * K])) == 5
    # a fetch outside any step (cancel() reads a flight) is nobody's
    stray = LINE + [["serving.fetch", 2020 * K, 60 * K, {}]]
    assert [r["fetch_ns"] for r in sr.steps_of([stray], WINDOW)] == [
        r["fetch_ns"] for r in steps]


def test_the_idle_a_finish_costs_is_a_split_of_the_idle_per_step(
        monkeypatch):
    run = _run([LINE], monkeypatch)
    steps = sr.steps_of([LINE], WINDOW)
    idle = sr.idle_s_by_class(run, steps)
    assert {k: round(v * 1e6) for k, v in idle.items()} == {
        "plain": 4000, "landing": 400, "admitting": 300, "idle": 10}
    assert sr.idle_ms_per_finish(run) == pytest.approx(0.710)
    assert sr.ahead_dispatch_share(run) == pytest.approx(75.0)
    # the same gaps, the same attribution: the old metric times its steps
    monkeypatch.setattr(ps.tracered, "find_xplane", lambda d: d)
    monkeypatch.setattr(ps, "_lines_of", lambda path: {
        ("host", "pump"): [e[:3] for e in LINE]})
    per_step = ps.idle_ms_per_root(run, "serving.step")
    assert per_step == pytest.approx(4.710 / 6)
    assert 1e3 * sum(idle.values()) == pytest.approx(per_step * len(steps))


def test_nothing_finished_or_nothing_dispatched_stays_out(monkeypatch):
    quiet = (_step(0, 1000, (1, 1, 1, 0, 0), (100, 900))
             + _step(1100, 2000, (1, 1, 1, 0, 0)))
    run = _run([quiet], monkeypatch)
    assert sr.idle_ms_per_finish(run) is None
    assert sr.ahead_dispatch_share(run) == pytest.approx(100.0)
    polls = _step(0, 10, (0, 0, 0, 0, 0)) + _step(20, 30, (0, 0, 0, 0, 0))
    run = _run([polls], monkeypatch)
    assert sr.ahead_dispatch_share(run) is None
    # the synchronous loop dispatches and never ahead: 0, not absent
    sync = _step(0, 1000, (1, 0, 1, 0, 0)) + _step(1100, 2000, (1, 0, 1, 0, 1))
    assert sr.ahead_dispatch_share(_run([sync], monkeypatch)) == 0.0


def test_a_stall_in_the_stretch_is_attributed(monkeypatch):
    run = _run([LINE], monkeypatch)
    (stall,) = sr.stalls_of(sr.steps_of([LINE], WINDOW), run)
    assert stall == {
        "class": "plain", "step_ms": pytest.approx(5.0),
        "median_ms": pytest.approx(1.0), "fetch_ms": pytest.approx(4.85),
        "device_idle_ms": pytest.approx(4.0),
        "device_busy_ms": pytest.approx(1.0)}
    # as long, but it carried a prefill: slow by its work, no stall
    carried = LINE[:8] + _step(4600, 9600, (1, 0, 1, 1, 0), (4650, 9500))
    assert sr.stalls_of(sr.steps_of([carried], WINDOW), run) == []
    report = sr.report(run)
    assert report["steps"] == 6 and len(report["stalls_in_stretch"]) == 1
    assert report["by_class"]["plain"] == {
        "count": 3, "finished": 0, "host_ms": pytest.approx(6.9),
        "fetch_ms": pytest.approx(6.4),
        "device_idle_ms": pytest.approx(4.0)}
    assert report["by_class"]["landing"]["finished"] == 1
    assert report["idle_ms_in_steps"] == pytest.approx(4.710)


def _recorded_run(tmp_path, monkeypatch, source):
    """A recorded file where run.py would have left it."""
    cell_dir = tmp_path / ".bench_trace" / "a_cell"
    where = cell_dir / "plugins" / "profile" / "2026_10_05"
    where.mkdir(parents=True)
    shutil.copy(source, where / "vm.xplane.pb")
    for module in (ps, sr.program_spans):
        monkeypatch.setattr(module, "trace_dir", lambda cell: str(cell_dir))
    trace = tracered.read_xplane(str(where / "vm.xplane.pb"), rehearse=True)
    reduced = tracered.summarize(trace, loader.load_opclasses("serve_engine"))
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="a_cell"), facts={}, peaks=None,
        trace=reduced)


def test_readers_on_a_recorded_file(tmp_path, monkeypatch):
    """740 KB recorded on the CPU backend: a tiny two-slot ServingEngine
    that decodes one step ahead, telemetry off, under a jax.profiler session
    started directly: requests of 4 and 6 tokens and a third of 5 queued
    behind them, stepped inside bench.window / bench.engine_step until an
    idle poll (tests/test_serving_observability.py walks the same ten
    steps)."""
    (line,) = sr.read_spans(RECORDED)
    run = _recorded_run(tmp_path, monkeypatch, RECORDED)
    steps = sr.steps_of([line], run.trace["window"])
    assert [(r["class"],) + tuple(r[k] for k in sr.COUNTS)
            for r in steps] == [
        ("admitting", 1, 0, 0, 2, 0), ("plain", 1, 1, 1, 0, 0),
        ("plain", 1, 1, 1, 0, 0), ("landing", 0, 0, 1, 0, 1),
        ("admitting", 1, 0, 0, 1, 0), ("plain", 1, 1, 1, 0, 0),
        ("landing", 0, 0, 1, 0, 1), ("plain", 1, 0, 1, 0, 0),
        ("finishing", 1, 0, 1, 0, 1), ("idle", 0, 0, 0, 0, 0)]
    # three of seven dispatches went ahead, and three requests finished
    assert sr.ahead_dispatch_share(run) == pytest.approx(100 * 3 / 7)
    idle = sr.idle_s_by_class(run, steps)
    assert sr.idle_ms_per_finish(run) == pytest.approx(
        1e3 * sum(v for k, v in idle.items() if k != "plain") / 3)
    # a split of engine_exposed_idle_ms_per_step, not a second measurement
    assert 1e3 * sum(idle.values()) == pytest.approx(
        10 * ps.idle_ms_per_root(run, "serving.step"))
    # every fetch of the file lies in a step (read off the file, by hand)
    assert sum(r["fetch_ns"] for r in steps) == 4201571 == sum(
        d for name, _, d, _ in line if name == "serving.fetch")
    # the decode's dispatch says whether it went ahead, a prefill's nothing
    dispatches = [st for _, _, _, st in
                  sr.read_spans(RECORDED, names=("serving.dispatch",))[0]]
    assert [st["ahead"] for st in dispatches if st] == [0, 1, 1, 0, 1, 0, 0]
    assert sum(not st for st in dispatches) == 3


def test_a_program_that_says_nothing_leaves_nothing_to_read(
        tmp_path, monkeypatch):
    """What the parent commit gives under these files: steps without the
    counts, slow steps without `at`, or nothing at all."""
    run = _recorded_run(tmp_path, monkeypatch, WITHOUT)
    assert len(ps._nested_lines(run)[0]) == 19     # the spans are there
    assert sr._steps(run) == []
    assert sr.ahead_dispatch_share(run) is None
    assert sr.idle_ms_per_finish(run) is None
    assert sr.report(run)["steps"] == 0
    monkeypatch.setattr(sr.program_spans, "trace_dir",
                        lambda cell: "/nonexistent")
    assert sr.ahead_dispatch_share(run) is None
    run.trace = None
    assert sr.idle_ms_per_finish(run) is None
    # the ring: a slow step as the parent logs it
    old = [{"kind": "serving_step_slow", "step": 7, "step_s": 0.125,
            "median_s": 0.0156, "phases": {"fetch": 0.12}, "other_s": 0.0}]
    assert sr.window_slow_steps(old, 0.0, 1e9) is None
    run.facts = {"window_start": 1000.0, "window_s": 40.0}
    monkeypatch.setattr(sr.flight, "_ring", lambda: old)
    assert sr.stall_share(run) is None
    assert sr.stall_fetch_share(run) is None
    # and one that logged none: only the program can say which it is
    monkeypatch.setattr(sr.flight, "_ring", lambda: [])
    monkeypatch.setattr(sr, "_program_counts", lambda: False)
    assert sr.stall_share(run) is None
    monkeypatch.setattr(sr, "_program_counts", lambda: True)
    assert sr.stall_share(run) == sr.stall_fetch_share(run) == 0.0
    assert sr.stall_share(types.SimpleNamespace(facts={}, trace=None)) is None


def test_the_program_under_test_counts():
    assert sr._program_counts()
    from incubator_mxnet_tpu.serving import engine

    assert engine.STEP_COUNTS == sr.COUNTS
    assert engine.SLOW_STEP_FACTOR == sr.STALL_FACTOR


def test_true_stalls_of_the_window_from_the_ring(monkeypatch, tmp_path):
    events, offset = RING["events"], RING["clock_offset"]
    slow = sr.window_slow_steps(events, RING["window_start"],
                                RING["window_s"], offset)
    # the ramp's (999.5) and the one that began as the window closed
    # (1040.0) are out
    assert [e["step"] for e in slow] == [40, 731, 800, 1900, 2100]
    monkeypatch.setattr(sr.flight, "_ring", lambda: events)
    monkeypatch.setattr(sr.flight, "clock_offset", lambda: offset)
    run = types.SimpleNamespace(
        trace=None, facts={"window_start": RING["window_start"],
                           "window_s": RING["window_s"]})
    # (0.125 - 0.0156) + (2.0 - 0.016) = 2.0934 s of 40
    assert sr.stall_share(run) == pytest.approx(5.2335)
    # 0.1238 + 1.5 of 0.125 + 2.0
    assert sr.stall_fetch_share(run) == pytest.approx(100 * 1.6238 / 2.125)
    # where the benchmark's stall_share.sat counts the prefills too
    assert sum(e["step_s"] - e["median_s"] for e in slow) == pytest.approx(
        2.0934 + 0.0624 + 0.0623 + 0.0625)
    # a window with prefill-carrying steps alone: 0, and 0 of it in fetch
    run.facts["window_s"] = 10.0
    assert sr.stall_share(run) == sr.stall_fetch_share(run) == 0.0
    # a traced run leaves its slow steps beside the trace for main()
    cell_dir = tmp_path / ".bench_trace" / "a_cell"
    cell_dir.mkdir(parents=True)
    monkeypatch.setattr(sr.program_spans, "trace_dir",
                        lambda cell: str(cell_dir))
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(name="a_cell"), trace={},
        facts={"window_start": RING["window_start"],
               "window_s": RING["window_s"]})
    sr.stall_share(run)
    with open(cell_dir / sr.KEPT) as f:
        kept = json.load(f)
    assert [e["step"] for e in kept["slow_steps"]] == [40, 731, 800, 1900,
                                                      2100]
    assert kept["window_s"] == 40.0


# the serve cells whose traced rehearsal is walked below: the backlog cells
# that report `serve_out_tok_per_s`, and the open cell
BACKLOG = ["gpt2xl_serve_decode_sat", "gpt2xl_serve_long_ctx",
           "phi4mf_serve_reason_deep", "falconh1_serve_doc_chat"]
OPEN = "gpt2xl_serve_prefill_open"
NEW_ENTRIES = {
    "engine_stall_share.sat": ("%", "lower", "program_counter"),
    "engine_stall_fetch_share.sat": ("%", "lower", "program_counter"),
    "engine_ahead_dispatch_share.sat": ("%", "higher", "program_span"),
    "engine_ahead_dispatch_share.open": ("%", "higher", "program_span"),
    "engine_exposed_idle_ms_per_finish.sat": ("ms", "lower", "program_span"),
}


@pytest.mark.parametrize("name", sorted(NEW_ENTRIES))
def test_new_entry_loads_in_its_cells(name):
    """Reported in exactly the cells its `workloads` lists (a cell a later
    PR adds comes with its entries), each of which reports the metric it
    moves; the cells walked below are among them."""
    unit, better, source = NEW_ENTRIES[name]
    moves = "itl_p90_s" if name.endswith(".open") else "serve_out_tok_per_s"
    listed = next(e for e in loader.benchmark_json()["per_layer"]
                  if e["name"] == name)["workloads"]
    assert set([OPEN] if name.endswith(".open") else BACKLOG) <= set(listed)
    for w in loader.benchmark_json()["workloads"]:
        cell = loader.load_cell(w["name"])
        found = [m for m in cell.per_layer if m.name == name]
        assert bool(found) == (w["name"] in listed)
        for metric in found:
            assert callable(metric.reader) and metric.args == {}
            assert (metric.unit, metric.entry["better"],
                    metric.entry["source"]) == (unit, better, source)
            assert metric.entry["layer"] == "serving.engine"
            assert metric.entry["moves"] == moves
            assert moves in {m.name for m in cell.end_to_end}


def test_the_entries_are_the_last_five_and_every_older_one_is_as_it_was():
    """Every entry of BENCHMARK.json has a name of its own, and the five
    that read the step's counts stand beside the older `stall_share.sat`,
    wherever later PRs append theirs."""
    bj = loader.benchmark_json()
    for kind in ("workloads", "configs", "end_to_end", "per_layer"):
        names = [e["name"] for e in bj[kind]]
        assert len(set(names)) == len(names), kind
    names = {e["name"] for e in bj["per_layer"]}
    assert set(NEW_ENTRIES) | {"stall_share.sat"} <= names


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark, so that a traced rehearsal leaves its
    .bench_trace/ beside it and not in the repository (where a test of
    another file may run the same cell meanwhile)."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.mark.parametrize("cell", BACKLOG + [OPEN])
def test_a_traced_rehearsal_prints_the_new_entries(checkout, cell):
    """The CPU walk-through of each serve cell, traced: the new entries
    are on the line beside the old ones, the split adds up, and the reader
    run by hand finds the same steps. Never a measurement."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=loader.ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000011", "--seconds",
         "5" if cell == OPEN else "2", "--trace", "1", "--rehearse"],
        env=env, cwd=checkout, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True
    variant = "open" if cell == OPEN else "sat"
    by_hand = subprocess.run(
        [sys.executable,
         os.path.join(checkout, "benchmark", "readers", "step_record.py"),
         cell, "--rehearse"],
        env=env, cwd=checkout, capture_output=True, text=True, timeout=300)
    assert by_hand.returncode == 0, by_hand.stderr[-2000:]
    report = json.loads(by_hand.stdout)
    steps = report["steps"]
    assert steps == sum(r["count"] for r in report["by_class"].values()) > 0
    per_step = metrics[f"engine_exposed_idle_ms_per_step.{variant}"]
    assert report["idle_ms_in_steps"] == pytest.approx(per_step * steps)
    ahead = metrics[f"engine_ahead_dispatch_share.{variant}"]
    assert report["ahead_dispatch_share"] == pytest.approx(ahead)
    if cell == OPEN:
        assert ahead == 0.0      # a slot is always free
        assert not any(k.startswith("engine_stall") for k in metrics)
        return
    # `phi4_mini_flash` alone keeps the synchronous loop
    assert (ahead == 0.0) == (cell == "phi4mf_serve_reason_deep")
    if cell == "falconh1_serve_doc_chat":
        assert ahead > 50.0
    assert metrics["engine_stall_share.sat"] >= 0.0
    assert 0.0 <= metrics["engine_stall_fetch_share.sat"] <= 100.0
    assert report["ring"]["window_s"] > 0
    finished = sum(r["finished"] for r in report["by_class"].values())
    per_finish = metrics.get("engine_exposed_idle_ms_per_finish.sat")
    if finished:
        other = sum(r["device_idle_ms"] for cls, r in
                    report["by_class"].items() if cls != "plain")
        assert per_finish == pytest.approx(other / finished)
    else:
        assert per_finish is None
