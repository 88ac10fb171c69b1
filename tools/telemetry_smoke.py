#!/usr/bin/env python
"""Telemetry CI smoke: run a tiny train loop with telemetry off and on,
assert the JSON/Prometheus dumps parse, and assert the disabled path adds
<5% wall time over the enabled run (i.e. the no-op stubs really
short-circuit — disabled must never be the slower configuration).

Also gates the always-on flight recorder: with telemetry AND tracing
off, a training loop must log zero span events into the ring (span() is
a true no-op), the default ring must cost <5% wall time over running
with the ring disabled (MXTPU_FLIGHT_RECORDER_EVENTS=0), and a burst of
log_event() calls must wrap the ring correctly (capacity kept, newest
events survive).

Usage: python tools/telemetry_smoke.py [steps]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, telemetry
from incubator_mxnet_tpu.gluon import nn

TOLERANCE = 1.05  # disabled wall time must stay within 5% of enabled
REPEATS = 5       # best-of-N to shave scheduler noise


def build():
    np.random.seed(0)
    X = np.random.randn(64, 8).astype("float32")
    Y = np.random.randn(64, 1).astype("float32")
    dataset = gluon.data.ArrayDataset(nd.array(X), nd.array(Y))
    net = nn.Dense(1, in_units=8)
    net.initialize(mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    return dataset, net, trainer, gluon.loss.L2Loss()


def run_loop(dataset, net, trainer, loss_fn, kv, params):
    from incubator_mxnet_tpu.telemetry import stepstats

    for x, y in gluon.data.DataLoader(dataset, batch_size=16):
        with autograd.record():
            # the explicit phase() puts the step-decomposition collector
            # (and, through trainer.step, the ledger sampler and compile
            # registry) inside the off/on overhead gate
            with stepstats.phase("dispatch"):
                loss = loss_fn(net(x), y)
        loss.backward()
        for i, p in enumerate(params):
            g = p.grad()
            kv.pushpull(i, g, out=g)
        trainer.step(16)
    mx.engine.waitall()


def timed_ab(n, setup_a, setup_b, args, loop=run_loop):
    """Best-of-N wall time for two configurations, measured in
    alternating rounds. The A/B pairing inside each round is what makes
    the 5%-overhead gates hold on noisy shared machines: two timings
    taken minutes apart in process life drift more than the tolerance,
    two timings taken back-to-back don't."""
    best_a = best_b = float("inf")
    for _ in range(n):
        setup_a()
        t0 = time.perf_counter()
        loop(*args)
        best_a = min(best_a, time.perf_counter() - t0)
        setup_b()
        t0 = time.perf_counter()
        loop(*args)
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else REPEATS
    dataset, net, trainer, loss_fn = build()
    kv = mx.kv.create("local")
    params = list(net.collect_params().values())
    args = (dataset, net, trainer, loss_fn, kv, params)

    run_loop(*args)  # warm the jit caches before any timing

    telemetry.REGISTRY.reset()
    t_off, t_on = timed_ab(steps, telemetry.disable, telemetry.enable, args)

    # exporters must produce parseable output from the enabled run
    data = telemetry.dump_json()
    json.loads(json.dumps(data))
    for name in ("mxtpu_trainer_step_seconds", "mxtpu_kvstore_bytes_total",
                 "mxtpu_dataloader_fetch_seconds",
                 # perf-observatory collectors must have published from
                 # the instrumented loop itself
                 "mxtpu_step_phase_seconds", "mxtpu_ledger_live_bytes"):
        assert name in data["metrics"], f"missing series {name}"
    text = telemetry.prometheus_text()
    assert "# TYPE mxtpu_trainer_step_seconds histogram" in text
    assert 'quantile="0.99"' in text, (
        "histogram summary quantile lines missing from Prometheus dump")
    for line in text.rstrip("\n").splitlines():
        if not line.startswith("#"):
            metric, value = line.rsplit(" ", 1)
            float(value)  # every sample value parses
            assert metric.strip(), line

    # functional spot-checks of the observatory collectors while enabled
    from incubator_mxnet_tpu.telemetry import compilereg, ledger, stepstats

    snap = stepstats.snapshot()
    assert snap["steps"] > 0 and "dispatch" in snap["phases"], snap
    probe = nd.zeros((32, 32))
    base = ledger.live_bytes("activations")
    ledger.track(probe, "activations")
    assert ledger.live_bytes("activations") == base + probe._data.nbytes
    ledger.untrack(probe)
    assert ledger.live_bytes("activations") == base
    assert compilereg.register("smoke.fn", ((4,),)) == "new"
    assert compilereg.register("smoke.fn", ((4,),)) == "seen"
    assert compilereg.register("smoke.fn", ((8,),)) == "retrace"
    retraces = telemetry.counter("mxtpu_retraces_total")
    assert retraces.value(fn="smoke.fn") == 1.0, (
        "exactly one retrace expected for one new signature")
    telemetry.disable()

    print(f"telemetry smoke: off={t_off * 1e3:.2f}ms "
          f"on={t_on * 1e3:.2f}ms (best of {steps})")
    assert t_off <= t_on * TOLERANCE, (
        f"disabled path is >{(TOLERANCE - 1) * 100:.0f}% slower than "
        f"enabled ({t_off:.4f}s vs {t_on:.4f}s) — no-op stubs are not "
        f"short-circuiting")

    # -- flight recorder (always-on ring) -------------------------------
    from incubator_mxnet_tpu import config as _config
    from incubator_mxnet_tpu.telemetry import recorder as _recorder

    # tracing off + telemetry off => span() is a bare annotation: the training
    # loop must not log a single span event into the ring
    before = sum(1 for e in _recorder.snapshot() if e["kind"] == "span_end")
    run_loop(*args)
    after = sum(1 for e in _recorder.snapshot() if e["kind"] == "span_end")
    assert after == before, (
        f"{after - before} span_end event(s) reached the flight recorder "
        "while telemetry and tracing were both off — the disabled span "
        "path is not a no-op")

    # the default ring must not cost measurable wall time: time the
    # disabled loop with the recorder on vs off (paired rounds)
    def ring_on():
        os.environ.pop("MXTPU_FLIGHT_RECORDER_EVENTS", None)
        _recorder.refresh_from_env()

    def ring_off():
        os.environ["MXTPU_FLIGHT_RECORDER_EVENTS"] = "0"
        _recorder.refresh_from_env()

    t_ring, t_noring = timed_ab(steps, ring_on, ring_off, args)
    ring_on()  # restore the default ring for the wrap test below
    print(f"flight recorder: ring-on={t_ring * 1e3:.2f}ms "
          f"ring-off={t_noring * 1e3:.2f}ms (best of {steps})")
    assert t_ring <= t_noring * TOLERANCE, (
        f"always-on flight recorder adds >{(TOLERANCE - 1) * 100:.0f}% "
        f"wall time ({t_ring:.4f}s with ring vs {t_noring:.4f}s without)")

    # wrap semantics: a burst larger than the ring keeps exactly
    # `capacity` events and the newest ones survive
    cap = _config.get("MXTPU_FLIGHT_RECORDER_EVENTS")
    for i in range(cap + 16):
        telemetry.log_event("smoke_burst", i=i)
    snap = _recorder.snapshot()
    assert len(snap) == cap, (
        f"ring holds {len(snap)} events after a {cap + 16}-event burst "
        f"(capacity {cap})")
    assert snap[-1]["kind"] == "smoke_burst" and snap[-1]["i"] == cap + 15, (
        "newest burst event missing from the ring snapshot")

    # -- serving observatory (request tracing + SLO monitor) ------------
    from incubator_mxnet_tpu.models import transformer as _tfm
    from incubator_mxnet_tpu.serving import ServingEngine
    from incubator_mxnet_tpu.telemetry import distributed as _distributed
    from incubator_mxnet_tpu.telemetry import slo as _slo

    # no MXTPU_SLO_* thresholds set => no monitor, zero per-request cost
    assert _slo.from_env() is None, (
        "slo.from_env() built a monitor with no thresholds configured")

    cfg = _tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                 n_layers=1, d_ff=32, max_len=32)
    sparams = _tfm.init_params(cfg, seed=0)
    eng = ServingEngine(sparams, cfg, slots=2, page_size=8, num_pages=16)
    assert eng.slo is None
    rng = np.random.RandomState(0)

    def serve_loop(eng):
        for _ in range(3):
            eng.submit(rng.randint(1, cfg.vocab, 5).astype("int32"), 4)
        eng.run()

    # tracing off => the engine must emit ZERO trace records (request
    # lifecycle spans and req_step progress records alike)
    serve_loop(eng)  # warm the serving jits before counting or timing
    assert not _distributed.trace_active(), (
        "smoke must run with MXTPU_TRACE_DIR unset")
    emitted = []
    orig_record = _distributed.record_span
    _distributed.record_span = emitted.append
    try:
        serve_loop(eng)
    finally:
        _distributed.record_span = orig_record
    assert not emitted, (
        f"{len(emitted)} trace record(s) emitted by the serving engine "
        "while tracing was off — the request-trace path is not free")

    # disabled-overhead gate over the new collectors: telemetry+SLO off
    # vs telemetry on with every serving objective attached
    monitor = _slo.SLOMonitor(
        [_slo.Objective("ttft", 60.0),
         _slo.Objective("queue_wait", 60.0),
         _slo.Objective("request_latency", 60.0),
         _slo.Objective("goodput", 0.0, kind="floor")],
        window_short=8, window_long=32, min_samples=4, dump=False)

    def slo_off():
        telemetry.disable()
        eng.slo = None

    def slo_on():
        telemetry.enable()
        eng.slo = monitor

    t_plain, t_slo = timed_ab(steps, slo_off, slo_on, (eng,),
                              loop=serve_loop)
    telemetry.disable()
    eng.slo = None
    print(f"serving observability: off={t_plain * 1e3:.2f}ms "
          f"on={t_slo * 1e3:.2f}ms (best of {steps})")
    assert t_plain <= t_slo * TOLERANCE, (
        f"serving loop with telemetry+SLO disabled is "
        f">{(TOLERANCE - 1) * 100:.0f}% slower than enabled "
        f"({t_plain:.4f}s vs {t_slo:.4f}s) — the serving collectors "
        f"are not short-circuiting")

    # -- fleet observatory (gateway + router zero-cost-when-off) --------
    import http.client as _http_client

    from incubator_mxnet_tpu.resilience import fault as _fault
    from incubator_mxnet_tpu.serving import FleetRouter, ServingGateway

    _fault.install(_fault.FaultInjector("", 0))
    fleet = FleetRouter(heartbeat_timeout=60.0)
    for _ in range(2):
        fleet.add_replica(ServingEngine(sparams, cfg, slots=2,
                                        page_size=8, num_pages=16))
    fleet.start(interval=0.001)
    gw = ServingGateway(fleet, port=0, queue_limit=64,
                        max_occupancy=0.99)

    def gateway_loop(port):
        for _ in range(3):
            conn = _http_client.HTTPConnection("127.0.0.1", port,
                                               timeout=120)
            conn.request("POST", "/v1/generate", json.dumps({
                "prompt": [int(t) for t in rng.randint(1, cfg.vocab, 5)],
                "max_new_tokens": 4, "stream": False}))
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            assert resp.status == 200, (resp.status, body[:200])

    try:
        gateway_loop(gw.port)  # warm the gateway path on both replicas

        # tracing off => the WHOLE serving stack (gateway root span,
        # router dispatch/failover spans, journal delivery records,
        # replica request spans) must emit ZERO trace records
        assert not _distributed.trace_active()
        emitted = []
        orig_record = _distributed.record_span
        _distributed.record_span = emitted.append
        try:
            gateway_loop(gw.port)
        finally:
            _distributed.record_span = orig_record
        assert not emitted, (
            f"{len(emitted)} trace record(s) emitted by the "
            "gateway/router/replica path while tracing was off — the "
            "fleet trace path is not free")

        # /metrics federation sanity: rollups plus per-replica series
        # under the replica label, from one scrape of the gateway
        telemetry.enable()
        conn = _http_client.HTTPConnection("127.0.0.1", gw.port,
                                           timeout=120)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        fed = resp.read().decode()
        conn.close()
        assert resp.status == 200
        for needle in ("mxtpu_fleet_total_queue_depth",
                       "mxtpu_fleet_queue_depth",
                       "mxtpu_fleet_oldest_queued_seconds",
                       "mxtpu_fleet_page_occupancy",
                       'mxtpu_fleet_replica_health{replica="r1"',
                       'mxtpu_fleet_replica_page_occupancy{replica="r2"'):
            assert needle in fed, f"/metrics federation missing {needle}"
        telemetry.disable()

        # disabled-overhead gate over the gateway+fleet loop: the
        # telemetry-off HTTP round trip must stay within the same 5%
        # bound (paired rounds absorb the loopback-HTTP noise)
        t_gw_off, t_gw_on = timed_ab(steps, telemetry.disable,
                                     telemetry.enable, (gw.port,),
                                     loop=gateway_loop)
        telemetry.disable()
        print(f"fleet observatory: off={t_gw_off * 1e3:.2f}ms "
              f"on={t_gw_on * 1e3:.2f}ms (best of {steps})")
        assert t_gw_off <= t_gw_on * TOLERANCE, (
            f"gateway+fleet loop with telemetry disabled is "
            f">{(TOLERANCE - 1) * 100:.0f}% slower than enabled "
            f"({t_gw_off:.4f}s vs {t_gw_on:.4f}s) — the fleet "
            f"observatory is not short-circuiting")
    finally:
        gw.close()
        fleet.stop()

    # -- runtime sanitizers (zero-cost-when-off contract) ---------------
    import threading as _threading

    from incubator_mxnet_tpu.analysis import sanitizers as _sanitizers

    # structural half of the contract: with MXTPU_SANITIZERS unset the
    # factories hand back PLAIN stdlib primitives (no wrapper object, no
    # per-acquire indirection), no blocking-op patches are installed,
    # and the allocator carries no shadow state
    os.environ.pop("MXTPU_SANITIZERS", None)
    _sanitizers.refresh_from_env()
    assert type(_sanitizers.san_lock("smoke")) is type(_threading.Lock()), (
        "san_lock() must return a plain threading.Lock while "
        "MXTPU_SANITIZERS is unset")
    assert _sanitizers._real_sleep is None, (
        "blocking-op patches installed while the locks sanitizer is off")
    eng_plain = ServingEngine(sparams, cfg, slots=2, page_size=8,
                              num_pages=16)
    assert eng_plain._page_san is None
    assert eng_plain.allocator.sanitizer is None

    # timed half: the sanitizer-off serving loop must stay within the
    # same 5% bound against a fully armed engine (same gate shape as the
    # telemetry off/on pairs above — if the off path secretly did
    # sanitizer work it would show up as off NOT being faster)
    os.environ["MXTPU_SANITIZERS"] = "locks,pages"
    _sanitizers.refresh_from_env()
    eng_armed = ServingEngine(sparams, cfg, slots=2, page_size=8,
                              num_pages=16)
    assert eng_armed._page_san is not None
    os.environ.pop("MXTPU_SANITIZERS", None)
    _sanitizers.refresh_from_env()

    serve_loop(eng_plain)  # warm both engines before timing
    serve_loop(eng_armed)
    best_plain = best_armed = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        serve_loop(eng_plain)
        best_plain = min(best_plain, time.perf_counter() - t0)
        t0 = time.perf_counter()
        serve_loop(eng_armed)
        best_armed = min(best_armed, time.perf_counter() - t0)
    print(f"sanitizers: off={best_plain * 1e3:.2f}ms "
          f"armed={best_armed * 1e3:.2f}ms (best of {steps})")
    assert best_plain <= best_armed * TOLERANCE, (
        f"serving loop with sanitizers OFF is "
        f">{(TOLERANCE - 1) * 100:.0f}% slower than with lockdep + page "
        f"shadow state armed ({best_plain:.4f}s vs {best_armed:.4f}s) — "
        f"the disabled path is not free")
    assert not _sanitizers.report(), (
        f"armed smoke engine produced findings: {_sanitizers.report()}")

    print("telemetry smoke OK")


if __name__ == "__main__":
    main()
