"""The benchmark's own spans, and the profiler around the end of a window.

Spans are `jax.profiler.TraceAnnotation`s named `bench.*`: they cost next
to nothing while no trace is on, and in a traced run they land in the
profiler's own file on the same clock as the device's ops, which is what
lets `tracered.attribute_gaps` say what the host was doing while the
device sat idle. They are opened around the calls into the program, from
the benchmark's side, and must not nest. Spans inside the program are a
later PR's.
"""
import os

import jax

from . import tracered


def span(name):
    return jax.profiler.TraceAnnotation(name)


class TailTrace:
    """Traces the last `trace_seconds` of a window `seconds` long, so that
    starting and stopping the profiler disturb no earlier part of it. The
    traced stretch is marked by one `bench.window` span. Does nothing when
    the run is not traced."""

    def __init__(self, ctx, trace_seconds):
        self._on = ctx.trace
        self._dir = ctx.trace_dir
        self._start_at = max(0.0, ctx.seconds - float(trace_seconds))
        self._window = None
        self.started_at = None  # elapsed seconds when the trace began

    def tick(self, elapsed):
        """Call between steps with the seconds elapsed in the window."""
        if self._on and self._window is None and elapsed >= self._start_at:
            os.makedirs(self._dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # no per-call Python events
            options.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=options)
            self._window = span(tracered.WINDOW_SPAN)
            self._window.__enter__()
            self.started_at = elapsed
            return True
        return False

    def stop(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._window = None
