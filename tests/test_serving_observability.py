"""Serving observatory tests: per-request lifecycle tracing, SLO
burn-rate math and breach dumps, live /debug/engine introspection, and
goodput accounting."""
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.serving import PageAllocator, ServingEngine
from incubator_mxnet_tpu.serving.engine import (
    ADMISSION_BLOCKED, GOODPUT, OLDEST_QUEUED, REQUESTS_TOTAL, STEP_COUNTS,
    TOKENS_TOTAL, WASTED_TOKENS)
from incubator_mxnet_tpu.telemetry import distributed as _distributed
from incubator_mxnet_tpu.telemetry import exporters as _exporters
from incubator_mxnet_tpu.telemetry import recorder as _recorder
from incubator_mxnet_tpu.telemetry import slo as _slo

_PARAM_CACHE = {}


def _tiny_engine(**kw):
    """Small enough that each engine compiles in well under a second on
    CPU; prompts in these tests stay below 16 so only one prefill
    bucket ever compiles."""
    cfg, params = _PARAM_CACHE.get("tiny") or _PARAM_CACHE.setdefault(
        "tiny", (tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                       n_layers=1, d_ff=32, max_len=32),
                 None))
    if params is None:
        params = tfm.init_params(cfg, seed=0)
        _PARAM_CACHE["tiny"] = (cfg, params)
    base = dict(slots=2, page_size=8, num_pages=16)
    base.update(kw)
    return ServingEngine(params, cfg, **base)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(
        1, 32, n).astype(np.int32)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    d = str(tmp_path / "traces")
    monkeypatch.setenv("MXTPU_TRACE_DIR", d)
    monkeypatch.setenv("MXTPU_FLIGHT_RECORDER_DIR", d)
    _distributed.refresh_from_env()
    _recorder.refresh_from_env()
    yield d
    monkeypatch.delenv("MXTPU_TRACE_DIR")
    monkeypatch.delenv("MXTPU_FLIGHT_RECORDER_DIR")
    _distributed.refresh_from_env()
    _recorder.refresh_from_env()


@pytest.fixture
def metrics_on(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.REGISTRY.reset()
    yield
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh_from_env()
    telemetry.REGISTRY.reset()


@pytest.fixture
def compile_table(metrics_on, tmp_path, monkeypatch):
    """The compile table in the engine's snapshot is fed by compilereg,
    which only sees programs routed through the persistent compile cache
    while telemetry is on."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))


def _load_records(trace_dir):
    _distributed.flush()
    records = []
    for name in sorted(os.listdir(trace_dir)):
        if name.endswith(".mxtrace"):
            records.extend(_distributed.read_trace_file(
                os.path.join(trace_dir, name)))
    return records


def _trace_merge():
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import trace_merge
    return trace_merge


def _serving_top():
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import serving_top
    return serving_top


# -- per-request lifecycle tracing -------------------------------------------

def test_request_trace_causal_chain(traced):
    eng = _tiny_engine()
    r0 = eng.submit(_prompt(5), 4)
    r1 = eng.submit(_prompt(9, seed=1), 6, eos_id=0)
    results = eng.run()
    records = _load_records(traced)

    roots = {r["extra"]["request"]: r for r in records
             if r.get("name") == "serving.request"}
    assert set(roots) == {r0, r1}
    steps = [r for r in records if r.get("kind") == "req_step"]
    for rid in (r0, r1):
        root = roots[rid]
        res = results[rid]
        # every stage shares ONE trace id and parents under the root sid
        stages = {r["name"]: r for r in records
                  if r.get("name", "").startswith("serving.request.")
                  and r["extra"].get("request") == rid}
        assert {"serving.request.queued",
                "serving.request.prefill"} <= set(stages)
        if len(res.tokens) > 1:
            assert "serving.request.decode" in stages
        for stage in stages.values():
            assert stage["tid"] == root["tid"]
            assert stage["pid"] == root["sid"]
            assert stage["ts"] >= root["ts"]
        # extras carry the engine's own result figures exactly
        extra = root["extra"]
        assert extra["finish"] == res.finish_reason
        assert extra["tokens"] == len(res.tokens)
        assert extra["prompt_len"] == res.prompt_len
        assert extra["latency_s"] == res.latency_s
        assert extra["queue_wait_s"] == res.queue_wait_s
        assert 0.0 < extra["ttft_s"] <= extra["latency_s"]
        # one batched progress record per decode step, not per token
        progressed = sum(1 for r in steps
                         for slot in r["slots"] if slot[0] == rid)
        assert progressed == extra["decode_steps"] == len(res.tokens) - 1
    assert len(steps) <= eng.steps


def test_zero_trace_records_when_off():
    assert not _distributed.trace_active()
    eng = _tiny_engine()
    emitted = []
    orig = _distributed.record_span
    _distributed.record_span = emitted.append
    try:
        rid = eng.submit(_prompt(4), 3)
        eng.run()
    finally:
        _distributed.record_span = orig
    assert eng.results()[rid].tokens
    assert not emitted, "engine emitted trace records with tracing off"
    assert eng._queue == eng._queue.__class__()  # drained


def test_trace_merge_requests_report(traced, tmp_path):
    eng = _tiny_engine()
    rids = [eng.submit(_prompt(4 + i, seed=i), 3 + i) for i in range(3)]
    results = eng.run()
    _distributed.flush()
    tm = _trace_merge()
    timeline = str(tmp_path / "timeline.json")
    report = str(tmp_path / "requests.json")
    rc = tm.main([traced, "-o", timeline, "--requests",
                  "--requests-json", report, "--check"])
    assert rc == 0
    rep = json.load(open(report))
    assert rep["count"] == len(rids)
    by_rid = {row["request"]: row for row in rep["requests"]}
    for rid in rids:
        row = by_rid[rid]
        res = results[rid]
        assert row["finish"] == res.finish_reason
        assert row["tokens"] == len(res.tokens)
        assert row["ttft_s"] <= row["latency_s"]
        assert row["progress_steps"] == row["decode_steps"]
    # one Perfetto lane per request
    tl = json.load(open(timeline))
    lanes = {e["args"]["name"] for e in tl["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {f"req{rid}" for rid in rids} <= lanes


def test_trace_merge_requests_check_catches_orphan(traced, tmp_path):
    # a root without its queued/prefill stages must fail --check
    _distributed.record_span({
        "name": "serving.request", "tid": _distributed.new_id(),
        "sid": _distributed.new_id(), "ts": 1, "dur_ns": 10,
        "extra": {"request": 7, "finish": "length", "tokens": 3,
                  "decode_steps": 2}})
    _distributed.flush()
    tm = _trace_merge()
    assert tm.main([traced, "--requests", "--check"]) == 2


# -- SLO burn-rate monitor ---------------------------------------------------

def test_burn_rate_state_machine_and_rearm():
    mon = _slo.SLOMonitor(
        [_slo.Objective("ttft", 0.5, budget=0.1)],
        window_short=4, window_long=8, min_samples=4,
        warn_burn=1.0, breach_burn=5.0, dump=False)
    # 8 good samples: burn 0, state ok
    for _ in range(8):
        assert mon.observe("ttft", 0.1) == "ok"
    # one bad sample: short window 1/4 bad -> burn 2.5 >= warn
    assert mon.observe("ttft", 2.0) == "warning"
    # three more: short burn 10, long (4 bad / 8) burn 5 -> breach
    mon.observe("ttft", 2.0)
    mon.observe("ttft", 2.0)
    assert mon.observe("ttft", 2.0) == "breach"
    snap = mon.snapshot()["ttft"]
    assert snap["breaches"] == 1
    assert snap["burn_short"] == pytest.approx(10.0)
    assert snap["burn_long"] == pytest.approx(5.0)
    # recovery drains the short window first: re-arm through warning/ok
    states = [mon.observe("ttft", 0.1) for _ in range(8)]
    assert states[-1] == "ok"
    assert "breach" not in states[4:]
    # a second episode is a SECOND breach (re-armed, not latched)
    for _ in range(4):
        state = mon.observe("ttft", 2.0)
    assert state == "breach"
    assert mon.snapshot()["ttft"]["breaches"] == 2


def test_burn_rate_goodput_floor_and_cold_start():
    mon = _slo.SLOMonitor(
        [_slo.Objective("goodput", 0.8, kind="floor", budget=0.5)],
        window_short=2, window_long=4, min_samples=4,
        warn_burn=1.0, breach_burn=2.0, dump=False)
    # below min_samples nothing can leave ok, however bad the burn
    assert mon.observe("goodput", 0.1) == "ok"
    assert mon.observe("goodput", 0.1) == "ok"
    assert mon.observe("goodput", 0.1) == "ok"
    assert mon.observe("goodput", 0.1) == "breach"  # 4th sample: both burn 2
    assert mon.state("goodput") == "breach"
    # floor direction: values ABOVE the threshold are good
    mon2 = _slo.SLOMonitor([_slo.Objective("goodput", 0.8, kind="floor")],
                           window_short=2, window_long=4, min_samples=1,
                           dump=False)
    assert mon2.observe("goodput", 0.95) == "ok"


def test_breach_fires_exactly_one_dump(traced):
    timelines = [{"request_id": 1, "latency_s": 2.0}]
    mon = _slo.SLOMonitor(
        [_slo.Objective("ttft", 0.5, budget=0.1)],
        window_short=4, window_long=4, min_samples=4,
        warn_burn=1.0, breach_burn=5.0,
        timelines=lambda: timelines)
    for _ in range(8):
        mon.observe("ttft", 2.0)
    dumps = [f for f in os.listdir(traced) if f.startswith("flightrec-")]
    assert len(dumps) == 1, f"expected exactly one dump, got {dumps}"
    payload = json.load(open(os.path.join(traced, dumps[0])))
    assert payload["reason"] == "slo-breach-ttft"
    assert payload["request_timelines"] == timelines
    assert payload["slo"]["ttft"]["state"] == "breach"
    # staying in breach writes nothing more; a fresh episode dumps again
    for _ in range(8):
        mon.observe("ttft", 0.1)
    for _ in range(8):
        mon.observe("ttft", 2.0)
    dumps = sorted(f for f in os.listdir(traced)
                   if f.startswith("flightrec-"))
    assert len(dumps) == 2


def test_slo_from_env(monkeypatch):
    assert _slo.from_env() is None
    monkeypatch.setenv("MXTPU_SLO_TTFT_P99", "0.25")
    monkeypatch.setenv("MXTPU_SLO_GOODPUT_MIN", "0.5")
    monkeypatch.setenv("MXTPU_SLO_WINDOW_SHORT", "3")
    monkeypatch.setenv("MXTPU_SLO_WINDOW_LONG", "6")
    mon = _slo.from_env()
    names = {o.name: o for o in mon.objectives}
    assert set(names) == {"ttft", "goodput"}
    assert names["ttft"].kind == "ceiling"
    assert names["goodput"].kind == "floor"
    assert mon.window_short == 3 and mon.window_long == 6
    # unknown keywords are ignored so the engine can feed its full set
    mon.observe_request(ttft=0.1, queue_wait=9.9, request_latency=9.9,
                        goodput=0.9)
    assert mon.snapshot()["ttft"]["samples"] == 1


def test_engine_attaches_slo_from_env_and_breaches(traced, monkeypatch):
    monkeypatch.setenv("MXTPU_SLO_TTFT_P99", "1e-12")  # everything is bad
    monkeypatch.setenv("MXTPU_SLO_WINDOW_SHORT", "2")
    monkeypatch.setenv("MXTPU_SLO_WINDOW_LONG", "4")
    monkeypatch.setenv("MXTPU_SLO_MIN_SAMPLES", "2")
    eng = _tiny_engine()
    assert eng.slo is not None
    for i in range(4):
        eng.submit(_prompt(4, seed=i), 3)
    eng.run()
    assert eng.slo.state("ttft") == "breach"
    dumps = [f for f in os.listdir(traced) if f.startswith("flightrec-")
             and "slo-breach-ttft" in f]
    assert len(dumps) == 1
    payload = json.load(open(os.path.join(traced, dumps[0])))
    # the dump carries the engine's own last-N request timelines
    assert payload["request_timelines"]
    assert {t["request_id"] for t in payload["request_timelines"]} <= \
        set(eng.results())
    tl = payload["request_timelines"][0]
    assert {"prompt_len", "tokens", "finish", "ttft_s",
            "latency_s"} <= set(tl)


# -- /debug/engine introspection ---------------------------------------------

def test_debug_snapshot_matches_engine_midrun(compile_table):
    eng = _tiny_engine(slots=1)
    r0 = eng.submit(_prompt(4), 8)
    r1 = eng.submit(_prompt(5, seed=1), 4)
    eng.step()  # r0 admitted + one decode step; r1 still queued
    snap = eng.debug_snapshot()
    json.dumps(snap)  # JSON-serializable end to end
    assert snap["steps"] == 1
    busy = [row for row in snap["slots"] if row["state"] == "decoding"]
    assert len(busy) == 1 and busy[0]["request_id"] == r0
    assert busy[0]["tokens_out"] == len(eng._slot_out[0])
    assert busy[0]["pages_held"] == len(eng._slot_pages[0])
    assert busy[0]["position"] == int(eng._positions[0])
    assert snap["queue_depth"] == 1
    assert snap["queue"][0]["request_id"] == r1
    assert snap["queue"][0]["age_s"] > 0
    assert snap["pages"]["in_use"] == eng.allocator.num_in_use > 0
    assert snap["pages"]["occupancy"] == eng.allocator.occupancy()
    assert snap["slo"] is None
    eng.run()
    snap = eng.debug_snapshot()
    assert snap["queue_depth"] == 0 and snap["slots_in_use"] == 0
    assert snap["requests_finished"] == 2
    assert snap["compile"]  # serving_* programs with signature counts
    assert all(fn.startswith("serving_") for fn in snap["compile"])


def test_debug_endpoint_http(monkeypatch):
    eng = _tiny_engine()
    eng.submit(_prompt(4), 3)
    eng.run()
    srv = _exporters.start_http_server(0, host="127.0.0.1")
    try:
        url = f"http://127.0.0.1:{srv.port}/debug/engine"
        # gated off by default: the endpoint must 404 without the knob
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url)
        assert err.value.code == 404
        monkeypatch.setenv("MXTPU_DEBUG_ENDPOINTS", "1")
        with urllib.request.urlopen(url) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            snap = json.loads(resp.read().decode())
        assert snap["schema"] == "mxtpu-serving-engine-debug-v2"
        assert snap["requests_finished"] == 1
        # lever sections are present but null with every knob off
        assert snap["prefix_cache"] is None
        assert snap["speculation"] is None
        assert snap["chunked_prefill"] is None
    finally:
        srv.close()


def test_serving_top_render(compile_table):
    top = _serving_top()
    eng = _tiny_engine(slots=1)
    eng.submit(_prompt(4), 8)
    eng.submit(_prompt(5, seed=1), 4)
    eng.step()
    text = top.render(eng.debug_snapshot())
    assert "decoding" in text and "queued" in text
    assert "serving_decode_step" in text
    assert "goodput" in text
    # its one slot decodes: the first decode step is dispatched and unread
    assert "decode steps 1  ahead 0 (0.00)  one in flight" in text
    eng.run()
    text = top.render(eng.debug_snapshot())
    assert "idle" in text and "in flight" not in text
    assert "decode steps 10  ahead 8 (0.80)" in text
    assert top.snapshot_url("localhost:9090") == \
        "http://localhost:9090/debug/engine"


# -- goodput accounting ------------------------------------------------------

def test_goodput_kinds_sum_to_tokens_total(metrics_on):
    eng = _tiny_engine()
    eng.submit(_prompt(5), 4)
    eng.submit(_prompt(9, seed=1), 3)
    eng.run()
    rid = eng.submit(_prompt(4, seed=2), 12)
    eng.step()
    eng.step()
    assert eng.cancel(rid)
    good = eng.goodput()
    # the registry's kind split must equal the host-side source of truth
    counter = telemetry.REGISTRY.counter(TOKENS_TOTAL)
    by_kind = {labels["kind"]: child.value
               for labels, child in counter.series()}
    assert by_kind == {"prefill": float(good["prefill"]),
                       "decode": float(good["decode"]),
                       "pad": float(good["pad"])}
    assert sum(by_kind.values()) == float(good["processed"])
    wasted = telemetry.REGISTRY.counter(WASTED_TOKENS)
    by_reason = {labels["reason"]: child.value
                 for labels, child in wasted.series()}
    assert by_reason["prefill_pad"] == float(good["pad"])
    assert by_reason["evicted"] == float(good["wasted_evicted"]) > 0
    assert 0.0 < good["fraction"] < 1.0
    assert good["useful"] == (good["prefill"] + good["decode"]
                              - good["wasted_evicted"])
    gauge = telemetry.REGISTRY.gauge(GOODPUT)
    assert {labels == {} and child.value == pytest.approx(good["fraction"])
            for labels, child in gauge.series()} == {True}
    requests = telemetry.REGISTRY.counter(REQUESTS_TOTAL)
    assert requests.value(outcome="evicted") == 1.0


def test_cancel_queued_and_unknown():
    eng = _tiny_engine(slots=1)
    r0 = eng.submit(_prompt(4), 6)
    r1 = eng.submit(_prompt(5, seed=1), 4)
    assert eng.cancel(r1)  # still queued: nothing processed
    res = eng.run()
    assert res[r1].finish_reason == "cancelled"
    assert res[r1].tokens == []
    assert res[r0].finish_reason in ("eos", "length")
    assert eng.goodput()["wasted_evicted"] == 0
    assert not eng.cancel(r1)  # already finished
    assert not eng.cancel(999)  # unknown
    assert eng.allocator.num_in_use == 0  # no page leaks


def test_evicted_request_frees_pages_for_queue():
    eng = _tiny_engine(slots=1, num_pages=5, page_size=8)
    r0 = eng.submit(_prompt(4), 20)   # holds 3 pages of 4
    r1 = eng.submit(_prompt(4, seed=1), 4)
    eng.step()
    assert eng.queue_depth == 1  # r1 blocked behind r0
    assert eng.cancel(r0)
    res = eng.run()
    assert res[r0].finish_reason == "evicted"
    assert res[r1].finish_reason in ("eos", "length")
    assert len(res[r1].tokens) == 4 or res[r1].tokens[-1] == 0


# -- satellite metrics -------------------------------------------------------

def test_oldest_queued_gauge_and_admission_blocked(metrics_on):
    eng = _tiny_engine(slots=1)
    eng.submit(_prompt(4), 8)
    eng.submit(_prompt(5, seed=1), 4)
    eng.step()
    gauge = telemetry.REGISTRY.gauge(OLDEST_QUEUED)
    [(labels, child)] = gauge.series()
    assert child.value > 0  # head-of-queue age visible BEFORE admission
    blocked = telemetry.REGISTRY.counter(ADMISSION_BLOCKED)
    assert blocked.value(reason="slots") >= 1.0
    eng.run()
    [(labels, child)] = gauge.series()
    assert child.value == 0.0  # drained queue reads zero


def test_admission_blocked_pages_reason(metrics_on):
    eng = _tiny_engine(slots=2, num_pages=4, page_size=8)
    eng.submit(_prompt(4), 20)  # 3 of the 3 allocatable pages
    eng.submit(_prompt(4, seed=1), 4)
    eng.step()
    blocked = telemetry.REGISTRY.counter(ADMISSION_BLOCKED)
    assert blocked.value(reason="pages") >= 1.0
    eng.run()


# -- page allocator health ---------------------------------------------------

def test_allocator_occupancy_and_fragmentation():
    alloc = PageAllocator(num_pages=9, page_size=8)
    assert alloc.occupancy() == 0.0
    assert alloc.fragmentation() == 0.0  # pristine free list: contiguous
    a = alloc.alloc(3)
    b = alloc.alloc(2)
    assert alloc.occupancy() == pytest.approx(5 / 8)
    alloc.free(a)  # free list now [4,5... then 1,2,3] — interleaved ids
    assert 0.0 <= alloc.fragmentation() <= 1.0
    alloc.free(b)
    assert alloc.occupancy() == 0.0
    # everything free again: ids 1..8 are one contiguous run
    assert alloc.fragmentation() == 0.0


# -- program spans on the profiler's clock -----------------------------------

def _tree(events):
    """[(depth, name)] by containment."""
    out, ends = [], []
    for name, start, end, _ in events:
        while ends and ends[-1] <= start:
            ends.pop()
        out.append((len(ends), name))
        ends.append(end)
    return out


@pytest.mark.parametrize("telemetry_on", [False, True])
def test_engine_span_tree_in_a_directly_started_session(
        profiled_spans, telemetry_on, monkeypatch):
    """The xplane of a session nobody told the program about holds the
    engine's span tree with its nesting and attributes, with telemetry
    off (bare annotations) and on (Span opens one too)."""
    if telemetry_on:
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.REGISTRY.reset()
    eng = _tiny_engine()
    eng.submit(_prompt(5), 3)
    eng.run()  # compile outside the trace
    rids = []

    def body():
        rids.append(eng.submit(_prompt(5), 3))
        eng.step()   # admits and prefills, then decodes
        eng.step()   # decodes; the request has its 3 tokens
    try:
        (events,) = profiled_spans(body)
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()
    phases = ["serving.h2d", "serving.dispatch", "serving.fetch"]
    decode = ([(1, "serving.decode")] + [(2, p) for p in phases]
              + [(2, "serving.bookkeep")])
    assert _tree(events) == (
        [(0, "serving.submit"), (0, "serving.step"), (1, "serving.admit"),
         (2, "serving.prefill")] + [(3, p) for p in phases] + decode
        + [(0, "serving.step"), (1, "serving.admit")] + decode)
    stats = [(n, st) for n, _, _, st in events if st]
    (rid,) = rids
    assert stats[0][0] == "serving.submit"
    assert stats[0][1]["request"] == rid and stats[0][1]["lock_wait_us"] >= 0
    step0 = eng.steps - 2
    # what the step did closes it: one synchronous dispatch, its flight
    # read, one prefill run to its first token, nobody ended
    assert stats[1] == ("serving.step",
                        {"step": step0, "live": 0, "queued": 1,
                         "dispatched": 1, "ahead": 0, "landed": 1,
                         "prefills": 1, "finished": 0})
    assert stats[2] == ("serving.admit", {"admitted": 1, "blocked": "none"})
    name, prefill = stats[3]
    assert name == "serving.prefill" and prefill["request"] == rid
    assert prefill["bucket"] == 16 and prefill["prompt_len"] == 5
    assert prefill["queue_wait_us"] >= 0
    assert stats[4] == ("serving.decode", {"live": 1})
    assert stats[5] == ("serving.dispatch", {"ahead": 0})
    assert stats[6] == ("serving.step",
                        {"step": step0 + 1, "live": 1, "queued": 0,
                         "dispatched": 1, "ahead": 0, "landed": 1,
                         "prefills": 0, "finished": 1})
    assert stats[7] == ("serving.admit", {"admitted": 0, "blocked": "none"})
    assert stats[9] == ("serving.dispatch", {"ahead": 0})


def _step_records(events):
    """[(dispatched, ahead, landed, prefills, finished)] of a line's
    `serving.step` spans, and the `ahead` of its decode dispatches."""
    steps = [tuple(st[k] for k in STEP_COUNTS)
             for n, _, _, st in events if n == "serving.step"]
    ahead = [st["ahead"] for n, _, _, st in events
             if n == "serving.dispatch" and "ahead" in st]
    return steps, ahead


def _three_requests(eng):
    """Two slots, requests of 4 and 6 tokens and a third of 5 queued
    behind them, stepped until an idle poll."""
    for n, new in ((5, 4), (6, 6), (4, 5)):
        eng.submit(_prompt(n, seed=n), new)
    while eng.queue_depth or eng.slots_in_use:
        eng.step()
    eng.step()


def test_step_says_what_it_did_while_it_runs_ahead(profiled_spans):
    """The five counts on `serving.step` and `ahead` on the decode's
    `serving.dispatch`, step by step, for an engine that decodes one step
    ahead: the run's start, plain steps, the step that lands a request's
    last flight, the restart that carries a prefill, the synchronous tail
    with a slot free, an idle poll."""
    telemetry.disable()
    eng = _tiny_engine()
    assert eng.model.decode_ahead
    _three_requests(eng)  # compile outside the trace
    (events,) = profiled_spans(lambda: _three_requests(eng))
    steps, ahead = _step_records(events)
    plain = (1, 1, 1, 0, 0)
    assert steps == [
        (1, 0, 0, 2, 0),   # admits two, dispatches, reads nothing yet
        plain, plain,
        (0, 0, 1, 0, 1),   # lands the first one's last flight
        (1, 0, 0, 1, 0),   # the restart: a prefill and a new run
        plain,
        (0, 0, 1, 0, 1),   # the second one's last flight
        (1, 0, 1, 0, 0),   # a slot is free: synchronous, and plain
        (1, 0, 1, 0, 1),   # the third ends
        (0, 0, 0, 0, 0)]   # an idle poll
    # one entry per decode dispatch, a prefill's dispatch has none
    assert ahead == [s[1] for s in steps if s[0]]
    stats = eng.cache_stats()
    assert stats["decode_steps"] == 2 * sum(s[0] for s in steps)
    assert stats["decode_steps_ahead"] == 2 * sum(s[1] for s in steps)


def test_step_says_what_it_did_in_the_synchronous_loop(profiled_spans,
                                                       monkeypatch):
    telemetry.disable()
    monkeypatch.setattr(tfm.TransformerPrograms, "decode_ahead", False)
    eng = _tiny_engine()
    _three_requests(eng)
    (events,) = profiled_spans(lambda: _three_requests(eng))
    steps, ahead = _step_records(events)
    plain = (1, 0, 1, 0, 0)
    assert steps == [
        (1, 0, 1, 2, 0), plain,
        (1, 0, 1, 0, 1),   # the step that ends a request dispatched too
        (1, 0, 1, 1, 0),   # the admission's step carries the prefill
        (1, 0, 1, 0, 1),
        plain,
        (1, 0, 1, 0, 1),
        (0, 0, 0, 0, 0)]
    assert ahead == [0] * 7


class _SlowToHost:
    """A device result whose copy to the host takes `seconds`: what a
    real prefill is, where the dispatch returns at once."""

    def __init__(self, value, seconds, clock=None):
        self._value, self._seconds, self._clock = value, seconds, clock

    def __array__(self, dtype=None, copy=None):
        import time

        if self._clock is None:
            time.sleep(self._seconds)
        else:
            self._clock.t += self._seconds
        return np.asarray(self._value, dtype)


def test_prefill_span_ends_after_the_first_token_is_on_the_host(metrics_on):
    eng = _tiny_engine()
    eng.submit(_prompt(5), 2)
    eng.run()
    telemetry.REGISTRY.reset()
    real = eng._prefills[16]

    def lazy(*args):
        tok, paged = real(*args)
        return _SlowToHost(tok, 0.05), paged
    eng._prefills[16] = lazy
    eng.submit(_prompt(5), 2)
    eng.run()
    hist = telemetry.REGISTRY.get(telemetry.SPAN_HISTOGRAM)
    by_span = {l["span"]: c for l, c in hist.series()}
    assert by_span["serving.prefill"].count == 1
    assert by_span["serving.prefill"].sum >= 0.05   # the fetch is inside
    assert by_span["serving.fetch"].sum >= 0.05
    assert by_span["serving.dispatch"].sum < 0.05   # and not the dispatch


def test_span_series_stay_bounded_over_steps_and_requests(metrics_on):
    """50 steps and 10 requests: one histogram child per span name, none
    per step or per request."""
    eng = _tiny_engine(slots=2)
    for i in range(10):
        eng.submit(_prompt(4 + i % 3, seed=i), 12)
    while eng.steps < 50:
        if not eng.queue_depth and not eng.slots_in_use:
            eng.submit(_prompt(4), 12)
        eng.step()
    hist = telemetry.REGISTRY.get(telemetry.SPAN_HISTOGRAM)
    series = [labels for labels, _ in hist.series()]
    assert all(set(labels) == {"span"} for labels in series)
    names = [labels["span"] for labels in series]
    assert len(names) == len(set(names)) <= 10
    assert set(names) == {
        "serving.submit", "serving.step", "serving.admit", "serving.prefill",
        "serving.decode", "serving.h2d", "serving.dispatch", "serving.fetch",
        "serving.bookkeep"}
    by_span = {l["span"]: c for l, c in hist.series()}
    assert by_span["serving.step"].count == 50


def test_everything_off_a_step_writes_nothing():
    telemetry.disable()
    telemetry.REGISTRY.reset()
    eng = _tiny_engine()
    eng.submit(_prompt(5), 20)
    eng.step()

    def kinds():
        return [e["kind"] for e in _recorder.snapshot()]
    before = kinds()
    for _ in range(5):
        eng.step()
    assert eng.slots_in_use == 1
    assert kinds() == before            # no span_end, nothing per step
    assert telemetry.REGISTRY.collect() == []


# -- always-on records: TTFT by part, slow steps ------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# the ring is process-wide and request ids start at 0 in every engine:
# these tests tell their events by a clock no other engine is on
_EPOCH = 2.0e9


def _finish_events(rid):
    return [e for e in _recorder.snapshot()
            if e["kind"] == "serving_request_finish"
            and e["request"] == rid and e.get("submitted", 0) >= _EPOCH]


def test_request_finish_carries_ttft_by_part_on_the_engine_clock():
    """Two requests admitted in ONE step: the first one's token is held
    through the second one's prefill and the decode. Device work is given
    a duration on the injected clock: a prefill 0.25 s, a decode 0.5 s."""
    clk = _Clock()
    eng = _tiny_engine(clock=clk)
    eng.submit(_prompt(5), 2)
    eng.run()  # compile
    real_prefill, real_decode = eng._prefills[16], eng._decode

    def prefill(*args):
        tok, paged = real_prefill(*args)
        return _SlowToHost(tok, 0.25, clk), paged

    def decode(*args):
        tok, paged = real_decode(*args)
        return _SlowToHost(tok, 0.5, clk), paged
    eng._prefills[16], eng._decode = prefill, decode

    clk.t = _EPOCH
    r0 = eng.submit(_prompt(5), 2)
    clk.t = _EPOCH + 0.125
    r1 = eng.submit(_prompt(6, seed=1), 3)
    clk.t = _EPOCH + 1.0                # the step starts
    eng.step()
    delivered = clk.t                   # the first moment a caller can read
    assert delivered == _EPOCH + 1.0 + 0.25 + 0.25 + 0.5
    assert set(eng.live_tokens()) == {r1}       # r0 had its 2 tokens
    (e0,) = _finish_events(r0)
    assert e0["submitted"] == _EPOCH and e0["outcome"] == "length"
    assert e0["queue_wait_s"] == 1.0            # the step began 1 s later
    assert e0["prefill_s"] == 0.25
    assert e0["first_token_held_s"] == 0.75     # r1's prefill + the decode
    assert e0["ttft_s"] == 1.25 == eng.results()[r0].ttft_s
    assert (e0["queue_wait_s"] + e0["prefill_s"] + e0["first_token_held_s"]
            == delivered - e0["submitted"])
    assert e0["latency_s"] == 2.0 and e0["tokens"] == 2
    assert not _finish_events(r1)
    eng.step()
    (e1,) = _finish_events(r1)
    assert e1["submitted"] == _EPOCH + 0.125
    assert e1["queue_wait_s"] == 1.25 - 0.125      # behind r0's prefill
    assert e1["prefill_s"] == 0.25
    assert e1["first_token_held_s"] == 0.5          # the decode alone
    assert (e1["queue_wait_s"] + e1["prefill_s"] + e1["first_token_held_s"]
            == delivered - e1["submitted"])
    # the same fields in the timelines (/debug/engine, SLO dumps)
    t0, t1 = eng.recent_timelines()[-2:]
    assert (t0["request_id"], t1["request_id"]) == (r0, r1)
    for timeline, event in ((t0, e0), (t1, e1)):
        for key in ("submitted", "queue_wait_s", "prefill_s",
                    "first_token_held_s", "ttft_s", "latency_s"):
            assert timeline[key] == event[key]
    # cancelled in the queue: its wait, and no part it never had
    eng._prefills[16], eng._decode = real_prefill, real_decode
    eng.submit(_prompt(4), 20)
    eng.submit(_prompt(4), 20)
    r4 = eng.submit(_prompt(4), 4)
    eng.step()
    clk.t += 1.5
    assert eng.cancel(r4)
    (e4,) = _finish_events(r4)
    assert e4["outcome"] == "cancelled" and e4["queue_wait_s"] == 1.5
    assert e4["prefill_s"] is e4["first_token_held_s"] is e4["ttft_s"] is None


def test_slowed_step_logs_one_serving_step_slow():
    clk = _Clock()
    eng = _tiny_engine(clock=clk)
    real_decode = eng._decode
    cost = {"fetch": 0.25}

    def decode(*args):
        tok, paged = real_decode(*args)
        return _SlowToHost(tok, cost["fetch"], clk), paged
    eng._decode = decode

    def slow():
        return [e for e in _recorder.snapshot()
                if e["kind"] == "serving_step_slow"]
    already = len(slow())
    for _ in range(3):
        eng.step()                      # an idle poll is no sample
    eng.submit(_prompt(5), 26)
    for _ in range(10):
        eng.step()
    assert len(slow()) == already and len(eng._step_s) == 10
    cost["fetch"] = 1.0                 # 4 x the median of 0.25
    eng.step()
    cost["fetch"] = 0.25
    for _ in range(5):
        eng.step()
    events = slow()[already:]
    assert len(events) == 1
    (e,) = events
    assert e["step"] == 13 and e["step_s"] == 1.0 and e["median_s"] == 0.25
    # where the time went: the blocking fetch (device or runtime), not a
    # host phase
    assert e["phases"] == {"h2d": 0.0, "dispatch": 0.0, "fetch": 1.0,
                           "bookkeep": 0.0}
    assert e["other_s"] == 0.0
    # what the step did, and when it began on the ENGINE's clock (the
    # injected one stood at 100 s and moved by the fetches alone: ten of
    # 0.25 s before this step)
    assert [e[k] for k in STEP_COUNTS] == [1, 0, 1, 0, 0]
    assert e["at"] == 100.0 + 10 * 0.25
    assert eng.debug_snapshot()["slow_steps"] == {
        "count": 1, "excess_s": 0.75, "last": {
            k: v for k, v in e.items() if k not in ("ts", "kind", "lane")}}


def test_a_slow_step_with_a_prefill_is_told_from_a_stall():
    """Two steps over three medians: one carried a prefill of 2 s (slow by
    its work), one stalled 2 s in a plain decode fetch. `prefills` tells
    them apart, which no duration does."""
    clk = _Clock()
    eng = _tiny_engine(clock=clk, slots=3)  # one stays free: synchronous
    real_prefill, real_decode = eng._prefills[16], eng._decode
    cost = {"prefill": 2.0, "decode": 0.25}

    def prefill(*args):
        tok, paged = real_prefill(*args)
        return _SlowToHost(tok, cost["prefill"], clk), paged

    def decode(*args):
        tok, paged = real_decode(*args)
        return _SlowToHost(tok, cost["decode"], clk), paged
    eng._prefills[16], eng._decode = prefill, decode
    clk.t = _EPOCH
    eng.submit(_prompt(5), 26)
    for _ in range(10):
        eng.step()
    assert eng.debug_snapshot()["slow_steps"]["count"] == 0
    eng.submit(_prompt(6, seed=1), 4)
    eng.step()              # admits: 2 s of prefill + the decode
    cost["decode"] = 2.25
    eng.step()              # a stall of the same length, no prefill
    cost["decode"] = 0.25
    eng.step()
    a, b = [e for e in _recorder.snapshot()
            if e["kind"] == "serving_step_slow" and e.get("at", 0) >= _EPOCH]
    assert a["step_s"] == b["step_s"] == 2.25 and a["median_s"] == 0.25
    assert (a["prefills"], b["prefills"]) == (1, 0)
    assert [a[k] for k in STEP_COUNTS] == [1, 0, 1, 1, 0]
    assert [b[k] for k in STEP_COUNTS] == [1, 0, 1, 0, 0]
    assert a["phases"]["fetch"] == b["phases"]["fetch"] == 2.25
    # ten plain steps after the first's prefill (2 s, no sample yet: under
    # eight were in) and the slow one before it
    assert a["at"] == _EPOCH + 2.0 + 10 * 0.25
    assert b["at"] == a["at"] + 2.25
    slow = eng.debug_snapshot()["slow_steps"]
    assert slow["count"] == 2 and slow["excess_s"] == 4.0
    assert slow["last"]["step"] == b["step"] == 11
    assert ("slow steps 2  +4.000 s over the median  last: step 11 2.250 s "
            "(fetch 2.250, prefills 0)") in _serving_top().render(
                eng.debug_snapshot())


def test_the_ring_keeps_its_finish_records_through_1000_steps(metrics_on):
    """With telemetry on every span's end is a flight event; the engine's
    four phase spans, some nine ends a step, are not (their time is in the
    step's tally and in mxtpu_span_seconds), so 1000 steps leave the
    always-on records of a ring of 4096 in place."""
    _recorder.refresh_from_env()   # an empty ring of the default size
    eng = _tiny_engine()
    first = eng.submit(_prompt(5), 2)
    eng.run()
    start = eng.steps
    while eng.steps < start + 1000:
        if not eng.queue_depth:
            eng.submit(_prompt(4), 28)
        eng.step()
    ring = _recorder.snapshot()
    kinds = {}
    for e in ring:
        name = e["kind"] + ":" + e.get("name", "")
        kinds[name] = kinds.get(name, 0) + 1
    assert not any(k.startswith("span_end:serving." + p) for k in kinds
                   for p in ("h2d", "dispatch", "fetch", "bookkeep"))
    assert kinds["span_end:serving.step"] >= 1000
    assert [e["request"] for e in ring
            if e["kind"] == "serving_request_finish"][0] == first
    hist = telemetry.REGISTRY.get(telemetry.SPAN_HISTOGRAM)
    by_span = {l["span"]: c for l, c in hist.series()}
    assert by_span["serving.fetch"].count >= 1000   # still timed


def test_dense_fallback_counts_with_telemetry_off():
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_kernels import (
        DENSE_FALLBACKS_TOTAL, flash_decode)

    telemetry.disable()
    telemetry.REGISTRY.reset()
    try:
        q = jnp.ones((1, 2, 8), jnp.float32)
        cache = jnp.ones((1, 200, 2, 8), jnp.float32)  # 200 % 128 != 0
        flash_decode(q, cache, cache, jnp.asarray(5, jnp.int32))
        fam = telemetry.REGISTRY.get(DENSE_FALLBACKS_TOTAL)
        assert fam is not None
        assert sum(child.value for _, child in fam.series()) == 1.0
    finally:
        telemetry.REGISTRY.reset()
