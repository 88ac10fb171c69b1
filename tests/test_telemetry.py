"""Unified runtime telemetry: registry primitives under threads, nested
spans, hot-path instrumentation (Trainer/kvstore/DataLoader/engine/device
memory), exporters, and the disabled no-op path."""
import json
import re
import threading

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.telemetry import recorder, stepstats


@pytest.fixture
def telem():
    telemetry.REGISTRY.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.REGISTRY.reset()


# -- registry primitives ----------------------------------------------------

def test_counter_gauge_histogram_under_threads(telem):
    c = telem.counter("t_ops_total", "test counter")
    g = telem.gauge("t_depth", "test gauge")
    h = telem.histogram("t_lat_seconds", "test histogram")

    def work():
        for i in range(500):
            c.inc(1, kind="a")
            c.inc(2)
            g.inc(1)
            h.observe(i * 1e-4, kind="a")

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(kind="a") == 8 * 500
    assert c.value() == 8 * 500 * 2
    assert g.value() == 8 * 500
    _, buckets, count, total, mn, mx_ = h.labels(kind="a").snapshot()
    assert count == 8 * 500 == sum(buckets)
    assert mn == 0.0 and mx_ == pytest.approx(499e-4)
    assert total == pytest.approx(8 * sum(i * 1e-4 for i in range(500)))


def test_metric_type_conflict_and_counter_monotonicity(telem):
    telem.counter("t_conflict")
    with pytest.raises(ValueError):
        telem.gauge("t_conflict")
    with pytest.raises(ValueError):
        telem.counter("t_conflict").inc(-1)
    # gauges go both ways; set_max is a watermark
    g = telem.gauge("t_water")
    g.set(10, dev="0")
    g.set_max(5, dev="0")
    assert g.value(dev="0") == 10
    g.set_max(25, dev="0")
    assert g.value(dev="0") == 25


# -- spans ------------------------------------------------------------------

def test_nested_spans_accumulate_into_registry(telem):
    assert telemetry.current_span() is None
    with telem.span("outer", train="1", step=7) as outer:
        assert telemetry.current_span() is outer
        with telem.span("inner") as inner:
            assert inner.parent is outer
            assert telemetry.current_span() is inner
        with telem.span("inner"):
            pass
        assert telemetry.current_span() is outer
    assert telemetry.current_span() is None
    hist = telemetry.REGISTRY.get(telemetry.SPAN_HISTOGRAM)
    series = {tuple(sorted(l.items())): child for l, child in hist.series()}
    # `train` is a registered label key; `step` identifies, so it is an
    # attribute of the trace record and never a label
    outer_key = (("span", "outer"), ("train", "1"))
    inner_key = (("span", "inner"),)
    assert set(series) == {outer_key, inner_key}
    assert outer.tags == {"train": "1"} and outer.extra == {"step": 7}
    assert series[outer_key].count == 1
    assert series[inner_key].count == 2
    # inner time is contained in outer wall time
    assert series[outer_key].sum >= series[inner_key].sum


def test_spans_off_the_ring_are_timed_and_leave_no_span_end(telem):
    """The spans of names.SPANS_OFF_THE_RING (the serving engine's phases,
    several a step) keep every sink but the flight event, and one whose
    body raised is an event all the same."""
    def ends():
        return [(e["name"], e.get("error")) for e in recorder.snapshot()
                if e["kind"] == "span_end"
                and e["name"] in ("serving.fetch", "serving.step")]
    before = ends()
    with telem.span("serving.step"):
        with telem.span("serving.fetch"):
            pass
        with pytest.raises(ValueError):
            with telem.span("serving.fetch"):
                raise ValueError("no tokens")
    assert ends()[len(before):] == [("serving.fetch", "ValueError"),
                                    ("serving.step", None)]
    hist = telemetry.REGISTRY.get(telemetry.SPAN_HISTOGRAM)
    counts = {tuple(sorted(l.items())): c.count for l, c in hist.series()}
    assert counts[(("span", "serving.fetch"),)] == 1
    assert counts[(("error", "ValueError"), ("span", "serving.fetch"))] == 1
    assert telemetry.SPANS_OFF_THE_RING <= telemetry.SPAN_NAMES


def test_spans_unify_with_profiler_aggregate_table(telem, monkeypatch):
    from incubator_mxnet_tpu import profiler

    profiler.reset_stats()
    monkeypatch.setitem(profiler._STATE, "running", True)
    monkeypatch.setitem(profiler._CONFIG, "aggregate_stats", True)
    with telem.span("telemetry_span_x"):
        pass
    table = profiler.dumps()
    assert "telemetry_span_x" in table
    profiler.reset_stats()


def test_profiler_dumps_zero_ops(telem):
    from incubator_mxnet_tpu import profiler

    profiler.reset_stats()
    table = profiler.dumps()
    assert "no ops recorded" in table
    assert "inf" not in table


# -- instrumented hot paths -------------------------------------------------

def _train_3_steps():
    """Tiny but complete loop: DataLoader -> forward/backward ->
    kvstore allreduce of the grads -> Trainer.step."""
    np.random.seed(0)
    X = np.random.randn(12, 4).astype("float32")
    Y = np.random.randn(12, 1).astype("float32")
    dataset = gluon.data.ArrayDataset(nd.array(X), nd.array(Y))
    loader = gluon.data.DataLoader(dataset, batch_size=4)
    net = nn.Dense(1, in_units=4)
    net.initialize(mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    L = gluon.loss.L2Loss()
    kv = mx.kv.create("local")
    params = list(net.collect_params().values())
    for x, y in loader:
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        for i, p in enumerate(params):
            g = p.grad()
            kv.pushpull(i, g, out=g)
        trainer.step(4)
    mx.engine.waitall()


def test_trainer_loop_produces_all_series(telem):
    _train_3_steps()
    reg = telemetry.REGISTRY

    step_hist = reg.get("mxtpu_trainer_step_seconds")
    assert step_hist is not None
    assert step_hist.labels().count == 3
    assert reg.get("mxtpu_trainer_steps_total").value() == 3

    fetch = reg.get("mxtpu_dataloader_fetch_seconds")
    assert fetch is not None and fetch.labels().count == 3

    kv_bytes = reg.get("mxtpu_kvstore_bytes_total")
    assert kv_bytes is not None
    pushed = kv_bytes.value(op="push", store="local")
    pulled = kv_bytes.value(op="pull", store="local")
    # 3 steps x (4x1 weight grad + 1 bias grad) x 4 bytes, both directions
    assert pushed == 3 * (4 + 1) * 4
    assert pulled == pushed
    assert reg.get("mxtpu_kvstore_seconds").labels(
        op="push", store="local").count == 6  # 2 keys x 3 steps

    mem = reg.get("mxtpu_device_bytes_in_use")
    assert mem is not None
    devices = [labels["device"] for labels, _ in mem.series()]
    assert devices, "no device-memory series sampled"
    peak = reg.get("mxtpu_device_peak_bytes_in_use")
    for labels, child in peak.series():
        assert child.value > 0

    waitall = reg.get("mxtpu_engine_waitall_seconds")
    assert waitall is not None and waitall.labels().count >= 1

    # executor/trainer spans landed in the shared span histogram
    span_hist = reg.get(telemetry.SPAN_HISTOGRAM)
    span_names = {labels["span"] for labels, _ in span_hist.series()}
    assert "trainer.step" in span_names


def test_waitall_error_counter_and_debug_log(telem, monkeypatch, caplog):
    import logging

    import jax

    def boom():
        raise RuntimeError("barrier exploded")

    monkeypatch.setattr(jax, "effects_barrier", boom)
    with caplog.at_level(logging.DEBUG, logger="incubator_mxnet_tpu.engine"):
        mx.engine.waitall()  # must not raise
    assert any("barrier" in r.getMessage() for r in caplog.records)
    assert telemetry.REGISTRY.get(
        "mxtpu_engine_waitall_errors_total").value() == 1


# -- exporters --------------------------------------------------------------

def test_dump_json_roundtrip(telem, tmp_path):
    _train_3_steps()
    path = tmp_path / "metrics.json"
    data = telemetry.dump_json(str(path))
    assert json.loads(json.dumps(data)) == data
    with open(path) as f:
        assert json.load(f) == data
    step = data["metrics"]["mxtpu_trainer_step_seconds"]
    assert step["type"] == "histogram"
    (series,) = step["series"]
    assert series["count"] == 3
    assert sum(series["buckets"].values()) + series["overflow"] == 3


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                      # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'              # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'         # more labels
    r' [0-9.eE+-]+(\+Inf)?$')                          # value


def test_prometheus_text_is_valid_exposition(telem):
    _train_3_steps()
    text = telemetry.prometheus_text()
    assert text.endswith("\n")
    seen_types = {}
    for line in text.rstrip("\n").splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            seen_types[name] = kind
        elif line.startswith("# HELP"):
            assert len(line.split()) >= 3
        else:
            assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
    assert seen_types["mxtpu_trainer_step_seconds"] == "histogram"
    assert seen_types["mxtpu_kvstore_bytes_total"] == "counter"
    assert seen_types["mxtpu_device_bytes_in_use"] == "gauge"
    # histograms expose cumulative buckets ending at +Inf == count
    inf = [l for l in text.splitlines()
           if l.startswith("mxtpu_trainer_step_seconds_bucket")
           and 'le="+Inf"' in l]
    assert inf and inf[0].rsplit(" ", 1)[1] == "3"


def test_metrics_http_endpoint(telem):
    import urllib.request

    telemetry.counter("t_http_total", "via http").inc(7)
    srv = telemetry.start_http_server(0)  # ephemeral port
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "t_http_total 7" in body
    finally:
        srv.close()


def test_tensorboard_compatible_periodic_logger(telem):
    class StubWriter:
        def __init__(self):
            self.scalars = []
            self.flushes = 0

        def add_scalar(self, tag, value, step):
            self.scalars.append((tag, value, step))

        def flush(self):
            self.flushes += 1

    telemetry.counter("t_tb_total").inc(3, role="w")
    telemetry.gauge("t_tb_depth").set(2)
    telemetry.histogram("t_tb_lat").observe(0.5)
    w = StubWriter()
    cb = telemetry.LogTelemetryCallback(interval=2, summary_writer=w)
    cb(None)  # step 1: below interval, no writes
    assert not w.scalars
    cb(None)  # step 2: logs everything
    tags = {t for t, _, _ in w.scalars}
    assert "telemetry/t_tb_total/role=w" in tags
    assert "telemetry/t_tb_depth" in tags
    assert "telemetry/t_tb_lat/mean" in tags
    mean = [v for t, v, _ in w.scalars if t == "telemetry/t_tb_lat/mean"]
    assert mean == [0.5]
    assert w.flushes == 1


# -- disabled path ----------------------------------------------------------

def test_disabled_paths_write_nothing():
    from jax.profiler import TraceAnnotation

    telemetry.disable()
    telemetry.REGISTRY.reset()
    try:
        # profiler-only: a bare annotation, whatever the attributes
        s = telemetry.span("anything", command="x", step=3)
        assert type(s) is TraceAnnotation
        assert type(stepstats.phase("dispatch")) is TraceAnnotation
        before = sum(e["kind"] == "span_end" for e in recorder.snapshot())
        with s as sp:
            sp.set_metadata(late=1)  # what Span.annotate is to a Span
            with telemetry.span("anything"):
                pass
        assert telemetry.current_span() is None
        telemetry.inc("t_should_not_exist_total")
        telemetry.observe("t_should_not_exist_seconds", 1.0)
        telemetry.set_gauge("t_should_not_exist_depth", 1)
        _train_3_steps()  # full instrumented loop, nothing recorded
        assert telemetry.REGISTRY.collect() == []
        assert telemetry.prometheus_text() == "\n"
        assert telemetry.dump_json()["metrics"] == {}
        assert before == sum(e["kind"] == "span_end"
                             for e in recorder.snapshot())
    finally:
        telemetry.REGISTRY.reset()


@pytest.mark.parametrize("telemetry_on", [False, True])
def test_train_step_spans_reach_a_session_nobody_told_them_of(
        profiled_spans, telemetry_on):
    """trainstep.call and the three phases under it, each phase under its
    own NAME, in a trace that mx.profiler.set_state never started — with
    telemetry off (bare annotations) and on (Span opens one too)."""
    from incubator_mxnet_tpu.fused import GluonTrainStep

    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Normal(0.1))
    L = gluon.loss.L2Loss()
    step = GluonTrainStep(net, lambda n, a, b: L(n(a), b),
                          mx.optimizer.SGD(learning_rate=0.1))
    x, y = nd.array(np.ones((3, 4), "float32")), nd.array(
        np.zeros((3, 2), "float32"))
    step(x, y)  # build and compile outside the trace
    (telemetry.enable if telemetry_on else telemetry.disable)()
    try:
        (events,) = profiled_spans(lambda: (step(x, y), step(x, y)))
    finally:
        telemetry.disable()
        telemetry.REGISTRY.reset()
        stepstats.reset()
    calls = [e for e in events if e[0] == "trainstep.call"]
    assert [c[3] for c in calls] == [{"n": 2}, {"n": 3}]
    for _, lo, hi, _ in calls:
        inside = [e[0] for e in events if lo <= e[1] and e[2] <= hi
                  and e[0] != "trainstep.call"]
        assert inside == ["trainer.phase.h2d", "trainer.phase.scalars",
                          "trainer.phase.dispatch"]


def test_enable_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    assert telemetry.refresh_from_env() is True
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    assert telemetry.refresh_from_env() is False
    monkeypatch.delenv("MXNET_TELEMETRY")
    assert telemetry.refresh_from_env() is False  # off by default
