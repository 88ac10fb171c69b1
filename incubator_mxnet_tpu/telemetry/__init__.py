"""Unified runtime telemetry: metrics registry + tracing spans + exporters.

The engine/executor hot path is one fused XLA program, so framework
observability lives host-side: this package instruments Executor
forward/backward, gluon.Trainer.step, kvstore push/pull (bytes + latency),
gluon DataLoader batch fetch, engine.waitall barriers, and per-device
memory watermarks, all feeding one thread-safe registry with Prometheus
and JSON exporters.

Off by default. `MXNET_TELEMETRY=1` (or `telemetry.enable()`) turns it on;
while off the module helpers return before touching the registry (one
cached boolean check per site) and `span()` hands back a bare
`jax.profiler.TraceAnnotation`: under a microsecond with no profiler
session, and inside one — whoever started it — the program's spans sit in
the `.xplane.pb` on the device trace's clock.

    import incubator_mxnet_tpu as mx
    mx.telemetry.enable()
    ... train ...
    print(mx.telemetry.prometheus_text())
    mx.telemetry.dump_json("metrics.json")

`MXNET_TELEMETRY_PORT=9090` additionally serves /metrics for scrapers.
"""
from __future__ import annotations

import threading

from .. import config as _config
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, REGISTRY, DEFAULT_BUCKETS,
    BYTES_BUCKETS,
)
from jax.profiler import TraceAnnotation as _TraceAnnotation

from .names import (  # noqa: F401
    METRIC_NAMES, SPAN_NAMES, SPAN_LABEL_KEYS, SPANS_OFF_THE_RING,
    is_registered_metric, is_registered_span,
)
from . import distributed  # noqa: F401
from . import recorder  # noqa: F401
from .spans import Span, current_span, SPAN_HISTOGRAM  # noqa: F401
from .recorder import log_event  # noqa: F401
from .exporters import (  # noqa: F401
    dump_json, prometheus_text, start_http_server, to_dict,
    register_debug_handler, unregister_debug_handler,
)
from .memory import sample_device_memory, step_boundary  # noqa: F401
from . import stepstats  # noqa: F401
from . import ledger  # noqa: F401
from . import compilereg  # noqa: F401
from . import slo  # noqa: F401
from .tb import LogTelemetryCallback  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_BUCKETS", "BYTES_BUCKETS",
    "Span", "current_span", "span",
    "distributed", "recorder", "log_event",
    "dump_json", "prometheus_text", "start_http_server", "to_dict",
    "register_debug_handler", "unregister_debug_handler",
    "sample_device_memory", "step_boundary", "LogTelemetryCallback",
    "stepstats", "ledger", "compilereg", "slo",
    "enabled", "enable", "disable", "refresh_from_env",
    "counter", "gauge", "histogram", "inc", "observe", "set_gauge",
    "METRIC_NAMES", "SPAN_NAMES", "SPAN_LABEL_KEYS", "SPANS_OFF_THE_RING",
    "is_registered_metric", "is_registered_span",
]

_state_lock = threading.Lock()
_enabled = None  # None = not yet resolved from MXNET_TELEMETRY
_http_server = None


def enabled():
    """Master switch. First call resolves MXNET_TELEMETRY (and starts the
    /metrics endpoint when MXNET_TELEMETRY_PORT is set); afterwards this
    is a cached-boolean read — the whole cost of the disabled path."""
    e = _enabled
    if e is None:
        e = _set_enabled(bool(_config.get("MXNET_TELEMETRY")))
    return e


def _set_enabled(value):
    global _enabled
    with _state_lock:
        _enabled = bool(value)
        if _enabled:
            _maybe_start_http()
        return _enabled


def _maybe_start_http():
    global _http_server
    if _http_server is not None:
        return
    port = _config.get("MXNET_TELEMETRY_PORT")
    if port > 0:
        _http_server = start_http_server(port)


def enable(port=None):
    """Turn telemetry on for this process (overrides the env default).
    `port` additionally starts a /metrics endpoint there — bound BEFORE
    the enable flag flips, so an explicit port wins over
    MXNET_TELEMETRY_PORT (processes sharing an env, e.g. PS servers on a
    rank-offset port, would otherwise race onto the base port)."""
    global _http_server
    if port is not None and _http_server is None:
        with _state_lock:
            if _http_server is None:
                _http_server = start_http_server(port)
    _set_enabled(True)
    return _http_server


def disable():
    """Turn telemetry off: instrumented sites go back to the no-op stubs.
    Already-recorded metrics stay in the registry (reset it explicitly)."""
    _set_enabled(False)


def refresh_from_env():
    """Re-resolve MXNET_TELEMETRY (mainly for tests that monkeypatch env)."""
    global _enabled
    _enabled = None
    return enabled()


def span(name, **attrs):
    """Timed, nestable tracing region; see spans.Span. Keywords in
    SPAN_LABEL_KEYS are tags (metric labels), the rest attributes of the
    trace event. While both telemetry and distributed tracing are off the
    span is profiler-only, a bare TraceAnnotation: no registry write, no
    flight event. With only MXTPU_TRACE_DIR set it is trace-only (no
    registry or aggregate-table sinks)."""
    if enabled():
        return Span(name, attrs)
    if distributed.trace_active():
        return Span(name, attrs, metrics=False)
    return _TraceAnnotation(name, **attrs)


# -- registry conveniences (always live; instrument through the helpers
#    below when the call must be free while disabled) -----------------------

def counter(name, help=""):
    return REGISTRY.counter(name, help)


def gauge(name, help=""):
    return REGISTRY.gauge(name, help)


def histogram(name, help="", buckets=DEFAULT_BUCKETS):
    return REGISTRY.histogram(name, help, buckets)


# -- guarded fast-path helpers for instrumented framework sites ------------

def inc(name, amount=1.0, help="", **labels):
    if not enabled():
        return
    REGISTRY.counter(name, help).inc(amount, **labels)


def observe(name, value, help="", buckets=DEFAULT_BUCKETS, **labels):
    if not enabled():
        return
    REGISTRY.histogram(name, help, buckets).observe(value, **labels)


def set_gauge(name, value, help="", **labels):
    if not enabled():
        return
    REGISTRY.gauge(name, help).set(value, **labels)
