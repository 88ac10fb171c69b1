"""Readers of the program's always-on records: the flight recorder's ring
(`telemetry.recorder.snapshot()`), which the serving engine gives one
`serving_request_finish` event per finished request.

Such an event carries `submitted`, a stamp on the ENGINE's clock
(`time.monotonic` unless the engine was built with another), and the parts
of the request's time to first token in seconds: `queue_wait_s` (submit ->
admitted), `prefill_s` (admitted -> first token on the host) and
`first_token_held_s` (first token on the host -> return of the `step()` that
made it, the first moment a caller can read it). The job's
`facts["window_start"]` is on `time.perf_counter`; both clocks are
monotonic, so their difference now is their difference then, and that is
how `submitted` is carried over. A request counts when it was submitted
after the window opened.

The ring is process-wide and of fixed size (`MXTPU_FLIGHT_RECORDER_EVENTS`,
4096): with telemetry off, as in the window, it gets nothing but these
events and the rare `serving_step_slow`. A program that does not stamp its
requests (any commit before it did) leaves nothing to read, and the
readers return None.
"""
import time


def clock_offset():
    """Seconds to add to a `time.monotonic` stamp to set it against
    `time.perf_counter`."""
    return time.perf_counter() - time.monotonic()


def window_requests(events, window_start, offset=0.0):
    """The `serving_request_finish` events of `events` that carry the
    stamps and whose request was submitted at or after `window_start`."""
    return [e for e in events
            if e.get("kind") == "serving_request_finish"
            and e.get("submitted") is not None
            and e["submitted"] + offset >= window_start]


def _ring():
    from incubator_mxnet_tpu.telemetry import recorder

    return recorder.snapshot()


def request_mean_ms(run, field):
    """Mean of `field` (seconds in the event, ms here) over the window's
    finished requests that have it."""
    start = run.facts.get("window_start")
    if start is None:
        return None
    values = [e[field] for e in window_requests(_ring(), start,
                                                clock_offset())
              if e.get(field) is not None]
    return 1e3 * sum(values) / len(values) if values else None
