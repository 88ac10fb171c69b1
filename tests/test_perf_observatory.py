"""Performance observatory: step-time decomposition (StepStats), the HBM
memory ledger (role accounting, peak attribution, leak heuristic), the
compile/retrace registry, exporter summary quantiles, and the perf-gate
tool."""
import gc
import importlib.util
import json
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.telemetry import compilereg, ledger, stepstats
from incubator_mxnet_tpu.telemetry import recorder as _recorder


@pytest.fixture
def telem():
    telemetry.REGISTRY.reset()
    stepstats.reset()
    ledger.reset()
    compilereg.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.REGISTRY.reset()
    stepstats.reset()
    ledger.reset()
    compilereg.reset()


# -- step-time decomposition ------------------------------------------------

def test_stepstats_phases_roll_into_quantile_gauges(telem):
    for _ in range(4):
        stepstats.record("data_fetch", 0.001)
        stepstats.record("dispatch", 0.008)
        stepstats.record("optimizer_update", 0.001)
        stepstats.step_end(0.01)
    snap = stepstats.snapshot()
    assert snap["steps"] == 4 and snap["window"] == 4
    assert snap["phases"]["dispatch"]["p50"] == pytest.approx(0.008)
    assert snap["total"]["p50"] == pytest.approx(0.01)
    # phases sum to the explicit total exactly -> coverage 1.0
    assert snap["coverage"] == pytest.approx(1.0)
    g = telemetry.REGISTRY.get("mxtpu_step_phase_seconds")
    assert g.value(phase="dispatch", q="0.5") == pytest.approx(0.008)
    assert g.value(phase="total", q="0.99") == pytest.approx(0.01)


def test_stepstats_phase_context_manager_times_region(telem):
    with stepstats.phase("device_sync"):
        pass
    stepstats.step_end(0.5)
    snap = stepstats.snapshot()
    assert "device_sync" in snap["phases"]
    assert 0 <= snap["phases"]["device_sync"]["p50"] < 0.5


def test_step_anomaly_fires_on_outlier_only(telem, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_ANOMALY_MIN_STEPS", "3")
    monkeypatch.setenv("MXNET_TELEMETRY_ANOMALY_FACTOR", "2.0")
    for _ in range(5):
        stepstats.step_end(0.01)
    assert stepstats.snapshot()["anomalies"] == 0
    stepstats.step_end(1.0)  # 100x the rolling median
    snap = stepstats.snapshot()
    assert snap["anomalies"] == 1
    c = telemetry.REGISTRY.get("mxtpu_step_anomalies_total")
    assert c.value() == 1.0
    events = [e for e in _recorder.snapshot() if e["kind"] == "step_anomaly"]
    assert events and events[-1]["total_s"] == pytest.approx(1.0)
    assert events[-1]["factor"] == 2.0


# -- HBM memory ledger ------------------------------------------------------

def test_ledger_role_accounting_alloc_free_donate(telem):
    a = nd.zeros((64, 64))
    b = nd.zeros((32, 32))
    na = ledger.track(a, "params")
    nb = ledger.track(b, "grads")
    assert na == a._data.nbytes and nb == b._data.nbytes
    assert ledger.live_bytes("params") == na
    assert ledger.live_bytes("grads") == nb
    assert ledger.live_bytes() == na + nb
    # duplicate track: first role wins, no double count
    assert ledger.track(a, "activations") == 0
    assert ledger.live_bytes("activations") == 0
    # explicit donation releases now, even though `b` is still referenced
    assert ledger.donate(b) == nb
    assert ledger.live_bytes("grads") == 0
    assert ledger.untrack(b) == 0  # idempotent
    # weakref death releases automatically
    del a
    gc.collect()
    assert ledger.live_bytes("params") == 0
    assert ledger.live_bytes() == 0
    g = telemetry.REGISTRY.get("mxtpu_ledger_live_bytes")
    assert g.value(role="params") == 0.0


def test_ledger_peak_attribution_names_active_span_and_phase(telem):
    base = nd.zeros((16, 16))
    ledger.track(base, "params")
    with telemetry.span("trainer.step"):
        with stepstats.phase("optimizer_update"):
            big = nd.zeros((128, 128))
            ledger.track(big, "optimizer_state")
    info = ledger.peak_info()
    assert info["peak_bytes"] == base._data.nbytes + big._data.nbytes
    # the innermost span at the peak is the phase span, named by its phase
    assert info["span"] == "trainer.phase.optimizer_update"
    assert info["breakdown"]["optimizer_state"] == big._data.nbytes
    peak_gauge = telemetry.REGISTRY.get("mxtpu_ledger_peak_bytes")
    assert peak_gauge.value() == info["peak_bytes"]


def test_ledger_leak_heuristic_fires_then_rearms(telem, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_LEAK_WINDOW", "3")
    keep = []
    step = 0
    # steady state: identical totals never trip the heuristic
    for _ in range(6):
        ledger.step_sample(step)
        step += 1
    assert telemetry.REGISTRY.get("mxtpu_ledger_leak_events_total") is None
    # monotonic growth: fires exactly once at the window
    for _ in range(3):
        keep.append(nd.zeros((32, 32)))
        ledger.track(keep[-1], "activations")
        ledger.step_sample(step)
        step += 1
    c = telemetry.REGISTRY.get("mxtpu_ledger_leak_events_total")
    assert c is not None and c.value() == 1.0
    events = [e for e in _recorder.snapshot()
              if e["kind"] == "memory_leak_suspect"]
    assert events and events[-1]["growing_samples"] == 3
    assert events[-1]["roles"]["activations"] == ledger.live_bytes(
        "activations")
    # re-armed: a flat sample then more growth fires again
    ledger.step_sample(step)
    step += 1
    for _ in range(3):
        keep.append(nd.zeros((32, 32)))
        ledger.track(keep[-1], "activations")
        ledger.step_sample(step)
        step += 1
    assert c.value() == 2.0


def test_ledger_samples_all_roles_present(telem):
    ledger.step_sample(0)
    samples = ledger.samples()
    assert len(samples) == 1
    _, step, role_bytes, total = samples[0]
    assert step == 0 and total == 0
    assert set(ledger.ROLES) <= set(role_bytes)


# -- compile/retrace registry ----------------------------------------------

def test_compilereg_retraces_exactly_once_per_new_signature(telem):
    sig_a = (((4, 4), "float32"),)
    sig_b = (((8, 4), "float32"),)
    assert compilereg.register("f", sig_a, compile_s=0.5) == "new"
    assert compilereg.register("f", sig_a) == "seen"
    assert compilereg.register("f", sig_b) == "retrace"
    assert compilereg.register("f", sig_b) == "seen"
    assert compilereg.register("f", sig_a) == "seen"
    compiles = telemetry.REGISTRY.get("mxtpu_compiles_total")
    retraces = telemetry.REGISTRY.get("mxtpu_retraces_total")
    assert compiles.value(fn="f") == 2.0  # both signatures compiled
    assert retraces.value(fn="f") == 1.0  # but only one was a retrace
    events = [e for e in _recorder.snapshot() if e["kind"] == "retrace"]
    assert events and events[-1]["fn"] == "f"
    assert "4, 4" in events[-1]["delta"] and "8, 4" in events[-1]["delta"]
    snap = compilereg.snapshot()
    assert snap["f"]["retraces"] == 1 and snap["f"]["signatures"] == 2
    assert len(snap["f"]["entries"]) == 2
    assert all(e["graph_hash"] for e in snap["f"]["entries"])


def test_compilereg_annotate_attaches_cost_and_compile_time(telem):
    sig = compilereg.signature_of(nd.zeros((2, 3)))
    assert sig == (((2, 3), "float32"),)
    compilereg.register("g", sig, compile_s=0.02)
    compilereg.annotate("g", cost={"flops": 100.0})  # latest signature
    info = compilereg.snapshot()["g"]["entries"][0]
    assert info["compile_s"] == 0.02
    assert info["cost"] == {"flops": 100.0}
    h = telemetry.REGISTRY.get("mxtpu_compile_seconds")
    assert h is not None  # register(compile_s=) fed the histogram


def test_train_loop_second_epoch_registers_zero_retraces(telem):
    net = nn.Sequential()
    net.add(nn.Dense(8, in_units=8))
    net.add(nn.Dense(1, in_units=8))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    x = nd.array(np.random.RandomState(0).randn(16, 8).astype("float32"))
    y = nd.array(np.random.RandomState(1).randn(16, 1).astype("float32"))
    loss_fn = gluon.loss.L2Loss()

    def retrace_total():
        c = telemetry.REGISTRY.get("mxtpu_retraces_total")
        return sum(child.value for _, child in c.series()) if c else 0.0

    def epoch():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(16)
        loss.asnumpy()

    epoch()
    before = retrace_total()
    epoch()
    assert retrace_total() == before, (
        "steady-shape second epoch must not retrace")


# -- exporter summary quantiles ---------------------------------------------

def test_prometheus_histograms_carry_summary_quantiles(telem):
    h = telemetry.histogram("t_obs_seconds", "test")
    for v in (0.001, 0.002, 0.003, 0.004, 0.1):
        h.observe(v, op="x")
    text = telemetry.prometheus_text()
    lines = [l for l in text.splitlines()
             if l.startswith("t_obs_seconds{") and "quantile=" in l]
    got = {}
    for line in lines:
        metric, value = line.rsplit(" ", 1)
        q = metric.split('quantile="')[1].split('"')[0]
        got[q] = float(value)
    assert set(got) == {"0.5", "0.95", "0.99"}
    # estimates live within the observed range and are ordered
    assert 0.001 <= got["0.5"] <= got["0.95"] <= got["0.99"] <= 0.1
    # count==0 series emit no quantile lines
    telemetry.histogram("t_empty_seconds", "test")
    assert "t_empty_seconds{" not in telemetry.prometheus_text()


# -- disabled path ----------------------------------------------------------

def test_observatory_collectors_are_noops_when_disabled():
    telemetry.disable()
    telemetry.REGISTRY.reset()
    stepstats.reset()
    ledger.reset()
    compilereg.reset()
    with stepstats.phase("dispatch"):
        pass
    stepstats.record("data_fetch", 0.01)
    stepstats.step_end()
    a = nd.zeros((8, 8))
    assert ledger.track(a, "params") == 0
    assert ledger.live_bytes() == 0
    ledger.step_sample(0)
    assert ledger.samples() == []
    assert compilereg.seen("f", (1,)) is True  # callers skip compile timing
    compilereg.register("f", (1,))
    assert compilereg.snapshot() == {}
    assert stepstats.snapshot()["steps"] == 0
    assert telemetry.REGISTRY.collect() == []


# -- perf gate --------------------------------------------------------------

def _load_perf_gate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "perf_gate.py")
    spec = importlib.util.spec_from_file_location("perf_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_gate_pass_fail_inject_and_update(tmp_path, capsys):
    gate = _load_perf_gate()
    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps({
        "metric": "m", "value": 10.0, "dispatches": 5, "ok": True}) + "\n")
    baseline = tmp_path / "baseline.json"

    # --update creates the baseline; unchanged results then pass
    assert gate.main([str(bench), "--baseline", str(baseline),
                      "--update"]) == 0
    doc = json.loads(baseline.read_text())
    assert doc["metrics"]["m.dispatches"]["value"] == 5.0
    assert gate.main([str(bench), "--baseline", str(baseline)]) == 0

    # tighten the dispatch band and seed a regression via --inject
    doc["metrics"]["m.dispatches"].update(tolerance_pct=0,
                                          direction="lower_is_better")
    baseline.write_text(json.dumps(doc))
    assert gate.main([str(bench), "--baseline", str(baseline)]) == 0
    assert gate.main([str(bench), "--baseline", str(baseline),
                      "--inject", "m.dispatches=4.0"]) == 1

    # a metric missing from the results is itself a failure
    doc["metrics"]["m.vanished"] = {"value": 1.0, "tolerance_pct": 0,
                                    "direction": "band"}
    baseline.write_text(json.dumps(doc))
    assert gate.main([str(bench), "--baseline", str(baseline)]) == 1

    # report_only regressions are printed but never fail
    doc["metrics"].pop("m.vanished")
    doc["metrics"]["m.value"].update(tolerance_pct=0, direction="band",
                                     report_only=True)
    baseline.write_text(json.dumps(doc))
    assert gate.main([str(bench), "--baseline", str(baseline),
                      "--inject", "m.value=100.0"]) == 0
    capsys.readouterr()


def test_perf_gate_directions(tmp_path):
    gate = _load_perf_gate()
    obs = {"m.x": 12.0}
    base = {"m.x": {"value": 10.0, "tolerance_pct": 10,
                    "direction": "lower_is_better"}}
    failures, _ = gate.compare(obs, base, 20.0)
    assert failures  # 12 > 10 * 1.1
    base["m.x"]["direction"] = "higher_is_better"
    failures, _ = gate.compare(obs, base, 20.0)
    assert not failures
    failures, _ = gate.compare({"m.x": 8.0}, base, 20.0)
    assert failures  # 8 < 10 * 0.9
    base["m.x"]["direction"] = "band"
    failures, _ = gate.compare({"m.x": 10.9}, base, 20.0)
    assert not failures
    failures, _ = gate.compare({"m.x": 11.1}, base, 20.0)
    assert failures
    # zero baseline with zero tolerance: any growth fails lower_is_better
    zb = {"m.z": {"value": 0.0, "tolerance_pct": 0,
                  "direction": "lower_is_better"}}
    failures, _ = gate.compare({"m.z": 1.0}, zb, 20.0)
    assert failures
    failures, _ = gate.compare({"m.z": 0.0}, zb, 20.0)
    assert not failures
