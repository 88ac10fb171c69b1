"""benchmark/readers/program_spans.py and flight.py: the program's own spans
read back from a profile, and the engine's per-request records read back
from the flight recorder's ring. Every expected number is worked out here.

One thread's spans (ns), a step that admits and prefills one request and
then decodes:

  serving.step      0..1000
    serving.admit      10..400
      serving.prefill    20..380
        serving.h2d        30..80
        serving.dispatch   80..130
        serving.fetch     130..370
    serving.decode    410..990
      serving.h2d       420..470
      serving.fetch     500..900
      serving.bookkeep  900..980
  serving.submit   1100..1150     (outside any step)
  serving.step     1200..1500     (a step with nothing nested)

self times: step 1000-390-580 = 30; admit 390-360 = 30; prefill
360-50-50-240 = 20; decode 580-50-400-80 = 50; the leaves their durations.
Under the two `serving.step` spans everything but the fetches:
30+30+20+50+50+50+50+80 + 300 = 660 over 2 steps.
"""
import json
import os
import shutil
import types

import pytest

from benchmark.harness import loader, tracered
from benchmark.readers import flight
from benchmark.readers import program_spans as ps

TESTDATA = os.path.join(loader.ROOT, "benchmark", "harness", "testdata")
RECORDED = os.path.join(TESTDATA, "cpu_engine_two_steps.xplane.pb")

LINE = [
    ["serving.step", 0, 1000], ["serving.admit", 10, 390],
    ["serving.prefill", 20, 360], ["serving.h2d", 30, 50],
    ["serving.dispatch", 80, 50], ["serving.fetch", 130, 240],
    ["serving.decode", 410, 580], ["serving.h2d", 420, 50],
    ["serving.fetch", 500, 400], ["serving.bookkeep", 900, 80],
    ["serving.submit", 1100, 50], ["serving.step", 1200, 300],
]


def _run(lines, device_ops, win, monkeypatch, cell="a_cell"):
    """A reader's `run` over hand-made lines of spans and one device."""
    monkeypatch.setattr(ps.tracered, "find_xplane", lambda d: d)
    monkeypatch.setattr(ps, "_lines_of", lambda path: lines)
    device = {"name": "/device:TPU:0", "ops": device_ops, "modules": []}
    busy = tracered.total(tracered.busy_intervals(device, win)) / 1e9
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell), facts={}, peaks=None,
        trace={"window": win,
               "devices": [{"busy_s": busy, "device": device}]})


def test_nest_gives_paths_and_self_times():
    nodes = ps.nest(list(reversed(LINE)))  # any order in, start order out
    got = [(n["name"], n["path"], n["self_ns"]) for n in nodes]
    step, admit, prefill, decode = (
        "serving.step", "serving.admit", "serving.prefill", "serving.decode")
    assert got == [
        (step, (), 30), (admit, (step,), 30), (prefill, (step, admit), 20),
        ("serving.h2d", (step, admit, prefill), 50),
        ("serving.dispatch", (step, admit, prefill), 50),
        ("serving.fetch", (step, admit, prefill), 240),
        (decode, (step,), 50), ("serving.h2d", (step, decode), 50),
        ("serving.fetch", (step, decode), 400),
        ("serving.bookkeep", (step, decode), 80),
        ("serving.submit", (), 50), (step, (), 300)]
    # self times of a tree add up to its root
    assert sum(s for _, path, s in got[:10]) == 1000


def test_flatten_is_the_innermost_span_at_each_instant():
    flat = ps.flatten(ps.nest(LINE))
    assert flat == [
        ["serving.step", 0, 10], ["serving.admit", 10, 10],
        ["serving.prefill", 20, 10], ["serving.h2d", 30, 50],
        ["serving.dispatch", 80, 50], ["serving.fetch", 130, 240],
        ["serving.prefill", 370, 10], ["serving.admit", 380, 20],
        ["serving.step", 400, 10], ["serving.decode", 410, 10],
        ["serving.h2d", 420, 50], ["serving.decode", 470, 30],
        ["serving.fetch", 500, 400], ["serving.bookkeep", 900, 80],
        ["serving.decode", 980, 10], ["serving.step", 990, 10],
        ["serving.submit", 1100, 50], ["serving.step", 1200, 300]]
    # no overlap, and as long in all as the outermost spans
    assert all(a[1] + a[2] <= b[1] for a, b in zip(flat, flat[1:]))
    assert sum(d for _, _, d in flat) == 1000 + 50 + 300


def test_self_ms_per_root_leaves_out_the_fetch(monkeypatch):
    run = _run({("host", "pump"): LINE}, [["op", 0, 10]], [0, 2000],
               monkeypatch)
    # 660 ns of host time over two steps; with nothing left out, the mean
    # duration of a step: (1000 + 300) / 2
    assert ps.self_ms_per_root(run, "serving.step",
                               exclude=["serving.fetch"]) == \
        pytest.approx(660 / 2 / 1e6)
    assert ps.self_ms_per_root(run, "serving.step") == \
        pytest.approx(1300 / 2 / 1e6)
    assert ps.self_ms_per_root(run, "trainstep.call") is None
    table = ps.table(run)
    assert table["serving.fetch"] == {
        "count": 2, "total_ms": pytest.approx(640e-6),
        "self_ms": pytest.approx(640e-6)}
    assert table["serving.step"]["count"] == 2
    # a window that starts after the first step keeps only the second
    run.trace["window"] = [1050, 2000]
    assert ps.self_ms_per_root(run, "serving.step") == \
        pytest.approx(300 / 1e6)


def test_idle_inside_steps_and_by_innermost_span(monkeypatch):
    # x1000: gaps must reach tracered.MIN_GAP_NS (50 us) to count.
    # The device runs 100k..360k (the prefill) and 480k..890k (the decode),
    # window 0..1.6M: idle 0..100k, 360k..480k, 890k..1.6M
    line = [[n, s * 1000, d * 1000] for n, s, d in LINE]
    ops = [["prefill", 100_000, 260_000], ["decode", 480_000, 410_000]]
    run = _run({("host", "pump"): line}, ops, [0, 1_600_000], monkeypatch)
    # inside steps: 100k + 120k + (890k..1000k = 110k) + (1.2M..1.5M = 300k)
    assert ps.idle_ms_per_root(run, "serving.step") == \
        pytest.approx((100 + 120 + 110 + 300) / 2 / 1e3)
    by = ps.idle_by_span(run)
    want = {  # microseconds
        "serving.step": 10 + 10 + 10 + 300,  # 0..10, 400..410, 990..1000
        "serving.admit": 10 + 20,            # 10..20, 380..400
        "serving.prefill": 10 + 10,          # 20..30, 370..380
        "serving.h2d": 50 + 50,              # 30..80, 420..470
        "serving.dispatch": 20,              # 80..100
        "serving.fetch": 10 + 10,            # 360..370, 890..900
        "serving.decode": 10 + 10,           # 410..420, 470..480; 980..990
        "serving.bookkeep": 80,
        "serving.submit": 50,
        "(no span)": 100 + 50 + 100,         # 1000..1100, 1150..1200, 1.5M..
    }
    want["serving.decode"] += 10
    assert {k: round(v * 1e6) for k, v in by.items()} == want
    assert ps.idle_ms_per_root(run, "trainstep.call") is None


@pytest.fixture
def recorded_run(tmp_path, monkeypatch):
    """The recorded file where run.py would have left it."""
    cell_dir = tmp_path / ".bench_trace" / "a_cell"
    where = cell_dir / "plugins" / "profile" / "2026_09_30"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "vm.xplane.pb")
    monkeypatch.setattr(ps, "trace_dir", lambda cell: str(cell_dir))
    trace = tracered.read_xplane(str(where / "vm.xplane.pb"), rehearse=True)
    reduced = tracered.summarize(trace, loader.load_opclasses("serve_engine"))
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="a_cell"), facts={}, peaks=None,
        trace=reduced)


def test_readers_on_a_recorded_file(recorded_run):
    """230 KB recorded on the CPU backend: a tiny ServingEngine under a
    jax.profiler session started directly (telemetry off), one request of
    5 tokens asked for 3, two steps inside bench.window / bench.engine_step.
    Step 1 admits, prefills and decodes; step 2 decodes."""
    lines = ps.read_lines(RECORDED)
    assert list(lines) == [("/host:CPU", "python")]
    nodes = ps.nest(next(iter(lines.values())))
    assert [(n["name"], len(n["path"])) for n in nodes] == [
        ("serving.submit", 0),
        ("serving.step", 0), ("serving.admit", 1), ("serving.prefill", 2),
        ("serving.h2d", 3), ("serving.dispatch", 3), ("serving.fetch", 3),
        ("serving.decode", 1), ("serving.h2d", 2), ("serving.dispatch", 2),
        ("serving.fetch", 2), ("serving.bookkeep", 2),
        ("serving.step", 0), ("serving.admit", 1),
        ("serving.decode", 1), ("serving.h2d", 2), ("serving.dispatch", 2),
        ("serving.fetch", 2), ("serving.bookkeep", 2)]
    steps = [n for n in nodes if n["name"] == "serving.step"]
    fetches = [n for n in nodes if n["name"] == "serving.fetch"]
    host_ns = (sum(n["end"] - n["start"] for n in steps)
               - sum(n["end"] - n["start"] for n in fetches))
    assert host_ns == 3890846 - 1800661  # read off the file once, by hand
    assert ps.self_ms_per_root(
        recorded_run, "serving.step", exclude=["serving.fetch"]) == \
        pytest.approx(host_ns / 2 / 1e6)
    # the spans sit on the clock of the benchmark's own: each step inside
    # its bench.engine_step
    bench = [e for e in tracered.read_xplane(RECORDED, rehearse=True)["host"]
             if e[0] == "bench.engine_step"]
    for (_, s, d), step in zip(bench, steps):
        assert s <= step["start"] and step["end"] <= s + d
    idle = ps.idle_ms_per_root(recorded_run, "serving.step")
    assert idle is not None and 0 <= idle <= 3890846 / 2 / 1e6
    assert set(ps.idle_by_span(recorded_run)) <= set(
        n["name"] for n in nodes) | {"(no span)"}


def test_a_program_without_spans_leaves_nothing_to_read(recorded_run,
                                                        monkeypatch):
    """What the parent commit gives: a trace with no program span in it,
    or no trace at all."""
    monkeypatch.setattr(ps, "_lines_of", lambda path: {})
    assert ps.self_ms_per_root(recorded_run, "serving.step") is None
    assert ps.idle_ms_per_root(recorded_run, "serving.step") is None
    monkeypatch.setattr(ps, "trace_dir", lambda cell: "/nonexistent")
    assert ps.self_ms_per_root(recorded_run, "serving.step") is None
    recorded_run.trace = None
    assert ps.self_ms_per_root(recorded_run, "trainstep.call") is None


RING = [
    {"kind": "serving_request_finish", "request": 0, "submitted": 9.0,
     "queue_wait_s": 0.5, "prefill_s": 0.5, "first_token_held_s": 0.5},
    {"kind": "span_end", "name": "serving.step"},
    {"kind": "serving_request_finish", "request": 1, "submitted": 10.0,
     "queue_wait_s": 0.010, "prefill_s": 0.120, "first_token_held_s": 0.2},
    {"kind": "serving_request_finish", "request": 2, "submitted": 12.5,
     "queue_wait_s": 0.030, "prefill_s": 0.140, "first_token_held_s": 0.3},
    # cancelled in the queue: waited, never made a token
    {"kind": "serving_request_finish", "request": 3, "submitted": 13.0,
     "queue_wait_s": 0.2, "prefill_s": None, "first_token_held_s": None},
    # a program that does not stamp its requests
    {"kind": "serving_request_finish", "request": 4, "outcome": "length"},
    {"kind": "serving_step_slow", "step": 7},
]


def test_flight_reader_keeps_the_windows_requests(monkeypatch):
    assert [e["request"] for e in flight.window_requests(RING, 10.0)] == \
        [1, 2, 3]
    # `submitted` is on the engine's clock: an offset carries it over
    assert [e["request"] for e in
            flight.window_requests(RING, 10.0, offset=-2.6)] == [3]
    monkeypatch.setattr(flight, "_ring", lambda: RING)
    monkeypatch.setattr(flight, "clock_offset", lambda: 0.0)
    run = types.SimpleNamespace(facts={"window_start": 10.0})
    assert flight.request_mean_ms(run, "queue_wait_s") == \
        pytest.approx((10 + 30 + 200) / 3)
    assert flight.request_mean_ms(run, "prefill_s") == pytest.approx(130)
    assert flight.request_mean_ms(run, "first_token_held_s") == \
        pytest.approx(250)
    run.facts["window_start"] = 20.0
    assert flight.request_mean_ms(run, "prefill_s") is None
    assert flight.request_mean_ms(types.SimpleNamespace(facts={}),
                                  "prefill_s") is None


def test_flight_clock_offset_is_small_on_one_clock():
    """Linux gives time.monotonic and time.perf_counter one clock; the
    reader does not count on it, it measures the difference."""
    assert abs(flight.clock_offset() - flight.clock_offset()) < 1e-3


NEW_ENTRIES = {
    "engine_host_ms_per_step.sat": ("gpt2xl_serve_decode_sat",
                                    "serve_out_tok_per_s"),
    "engine_host_ms_per_step.open": ("gpt2xl_serve_prefill_open",
                                     "itl_p90_s"),
    "engine_exposed_idle_ms_per_step.sat": ("gpt2xl_serve_decode_sat",
                                            "serve_out_tok_per_s"),
    "engine_exposed_idle_ms_per_step.open": ("gpt2xl_serve_prefill_open",
                                             "itl_p90_s"),
    "train_host_ms_per_step": ("r50_train_dp4_zero1",
                               "items_per_s_per_chip"),
    "ttft_queue_wait_ms": ("gpt2xl_serve_prefill_open", "ttft_p90_s"),
    "ttft_prefill_ms": ("gpt2xl_serve_prefill_open", "ttft_p90_s"),
    "ttft_first_token_held_ms": ("gpt2xl_serve_prefill_open", "ttft_p90_s"),
}


@pytest.mark.parametrize("name", sorted(NEW_ENTRIES))
def test_new_entry_loads_in_its_cell(name):
    cell_name, moves = NEW_ENTRIES[name]
    cell = loader.load_cell(cell_name)
    metric = next(m for m in cell.per_layer if m.name == name)
    assert callable(metric.reader) and metric.unit == "ms"
    assert metric.entry["moves"] == moves
    assert metric.entry["better"] == "lower"
    assert metric.entry["source"] in ("program_span", "program_counter")
    # reported only where the end-to-end metric it moves is
    assert moves in {m.name for m in cell.end_to_end}
    # and in no cell its entry does not list
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for other in cells:
        listed = other in metric.entry["workloads"]
        has = name in {m.name for m in loader.load_cell(other).per_layer}
        assert listed == has
