"""Readers of what `ServingEngine.step()` says it did.

The engine counts, per `step()`, five things it observes anyway:
`dispatched` (decode programs sent: 0 or 1), `ahead` (of those, sent before
the step in flight was read), `landed` (flights read), `prefills` (requests
whose prefill the step ran to its first token) and `finished` (requests that
ended in it). It says them in two places:

- as attributes of the `serving.step` span (and `ahead` on the decode's
  `serving.dispatch`), so in a traced run they are event stats in the
  `.xplane.pb`, on the device trace's clock. `program_spans.read_lines`
  keeps names and times only, so this file opens the trace once more and
  keeps the stats of the few spans it reads;
- on the always-on `serving_step_slow` event of the flight recorder's ring
  (a step over 3 x the rolling median of 64), with `at`, the step's start
  on the ENGINE's clock (`time.monotonic`; carried over to the job's
  `time.perf_counter` as `readers/flight.py` carries `submitted`), the
  step's seconds by phase (`phases`) and `other_s`.

The engine computes no kind of step. A reader classes a step from the five
(`step_class`):

  plain      lands one flight, dispatches one program, admits nobody, ends
             nobody: the steady step of either loop (run ahead or not)
  landing    dispatches nothing and reads a flight: the step in flight was
             some request's last, so nothing could be sent ahead of it
  admitting  ran a prefill to its first token (the restart of a run ahead
             is one: it dispatches and reads nothing back yet)
  finishing  ended a request and dispatched all the same (the synchronous
             loop's finish)
  starting   dispatched and read nothing back, without a prefill
  idle       did nothing: a poll of an empty engine

Everything but `plain` is a step a finish or an admission touched: what a
finish costs the device is the idle inside those steps over the requests
that finished (`idle_ms_per_finish`). The split uses the same gaps (50 us
and more, the least busy device) and the same attribution as
`engine_exposed_idle_ms_per_step.*`, so idle in plain steps plus idle in
the others IS that metric times the steps: one measurement, split.

A slow step that carried a prefill is slow by its work; one with
`prefills == 0` stalled (`stall_share`), and `phases["fetch"]` of its
`step_s` says where (`stall_fetch_share`; 0 beside a `stall_share` of 0).
The phase alone does not say whose the wait is: the host sits in the
blocking fetch nine tenths of a step, so a pause of the whole process lands
there nine times in ten (PERF.md section 6, PR 37: 17 of 24 stalls in
`fetch`, 4 in `h2d`, 2 in `dispatch`, 1 in no phase). A stall inside a
traced stretch says more (below).

A program that says none of this (any commit before it did) leaves nothing
to read: every reader returns None and the metric stays out of the line.

By hand, for the last traced run of a cell in this checkout:

    python3 benchmark/readers/step_record.py <cell>

prints the traced stretch's steps by class (count, host ms, device idle ms),
the share of dispatches that went ahead, the idle a finish cost, the
window's slow steps as the ring held them when the run's metrics were read
(kept beside the trace, in `slow_steps.json`), and every STALL of the
stretch: a `serving.step` over three medians of the stretch's steps with
`prefills == 0`, with its duration, its `serving.fetch` time and the
device's busy and idle time inside it. Device busy through the stall: the
chip's or the program's (a long step). Device idle while the host sits in
`fetch`: the runtime did not hand the tokens back. A stretch is 4-8 s of a
40 s window, so it catches a stall in one traced run of five or ten.
"""
import functools
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness import loader, stats, tracered
from benchmark.readers import flight
from benchmark.readers import program_spans

COUNTS = ("dispatched", "ahead", "landed", "prefills", "finished")
STEP, FETCH = "serving.step", "serving.fetch"
SLOW_KIND = "serving_step_slow"
STALL_FACTOR = 3.0          # the engine's SLOW_STEP_FACTOR
KEPT = "slow_steps.json"    # beside a traced run's trace, for main()


def step_class(rec):
    """The class of a step (module docstring) from its five counts."""
    dispatched, _, landed, prefills, finished = (rec[k] for k in COUNTS)
    if prefills:
        return "admitting"
    if dispatched == 1 and landed == 1:
        return "finishing" if finished else "plain"
    if landed:
        return "landing"
    if dispatched:
        return "starting"
    return "finishing" if finished else "idle"


# -- the traced stretch -------------------------------------------------------

def read_spans(path, names=(STEP, FETCH)):
    """[[name, start_ns, dur_ns, {stat: value}], ...] per host line of the
    .xplane.pb at `path`, for the events named in `names`."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracered.DEVICE_PLANE):
            continue
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns,
                       {k: v for k, v in e.stats}]
                      for e in line.events if e.name in names]
            if events:
                lines.append(events)
    return lines


@functools.lru_cache(maxsize=None)
def _spans_of(path):
    return read_spans(path)


def steps_of(lines, window):
    """The `serving.step` spans of `lines` that start inside `window` and
    say what they did, in order of start: [{"start", "end", "fetch_ns",
    "class", the five counts}], `fetch_ns` the time of the `serving.fetch`
    spans inside each. [] when no step carries the counts."""
    lo, hi = window
    out = []
    for events in lines:
        steps = sorted(
            ({"start": s, "end": s + d, "fetch_ns": 0,
              **{k: int(st[k]) for k in COUNTS}}
             for name, s, d, st in events
             if name == STEP and lo <= s < hi
             and all(k in st for k in COUNTS)), key=lambda r: r["start"])
        fetches = sorted((s, s + d) for name, s, d, _ in events
                         if name == FETCH)
        i = 0
        for rec in steps:
            while i < len(fetches) and fetches[i][0] < rec["start"]:
                i += 1
            while i < len(fetches) and fetches[i][1] <= rec["end"]:
                rec["fetch_ns"] += fetches[i][1] - fetches[i][0]
                i += 1
            rec["class"] = step_class(rec)
        out.extend(steps)
    return sorted(out, key=lambda r: r["start"])


def _steps(run):
    """steps_of the run's own trace; [] without a trace, or when the
    program left no step that says what it did."""
    if run.trace is None:
        return []
    try:
        path = tracered.find_xplane(program_spans.trace_dir(run.cell.name))
    except FileNotFoundError:
        return []
    return steps_of(_spans_of(path), run.trace["window"])


def _gaps(run):
    """The idle gaps `program_spans.idle_ms_per_root` takes: those of the
    device that is busy least."""
    return tracered.idle_gaps(program_spans._worst_device(run),
                              run.trace["window"])


def idle_s_by_class(run, steps):
    """{class: device idle seconds inside the steps of that class}."""
    spans = [[r["class"], r["start"], r["end"] - r["start"]] for r in steps]
    idle = tracered.attribute_gaps(_gaps(run), spans)
    idle.pop("(no span)", None)
    return idle


def ahead_dispatch_share(run):
    """Share (%) of the traced stretch's decode dispatches that went out
    before the step in flight was read: sum of `ahead` over sum of
    `dispatched` of its `serving.step` spans."""
    steps = _steps(run)
    dispatched = sum(r["dispatched"] for r in steps)
    if not dispatched:
        return None
    return 100.0 * sum(r["ahead"] for r in steps) / dispatched


def idle_ms_per_finish(run):
    """Device idle (ms) inside the traced stretch's steps that are not
    plain, per request that finished in the stretch; None when nothing
    finished in it."""
    steps = _steps(run)
    finished = sum(r["finished"] for r in steps)
    if not finished:
        return None
    idle = idle_s_by_class(run, steps)
    return 1e3 * sum(s for cls, s in idle.items()
                     if cls != "plain") / finished


def stalls_of(steps, run=None):
    """The stalls among `steps`: those over STALL_FACTOR medians of the
    steps that did something, with `prefills == 0`. With `run`, each gets
    the device's busy and idle time inside it."""
    ran = [r for r in steps if r["class"] != "idle"]
    if not ran:
        return []
    median = stats.median([r["end"] - r["start"] for r in ran])
    out = []
    for r in ran:
        dur = r["end"] - r["start"]
        if r["prefills"] or dur <= STALL_FACTOR * median:
            continue
        row = {"class": r["class"], "step_ms": dur / 1e6,
               "median_ms": median / 1e6, "fetch_ms": r["fetch_ns"] / 1e6}
        if run is not None:
            idle_ns = tracered.total(tracered.clip(
                _gaps(run), r["start"], r["end"]))
            row["device_idle_ms"] = idle_ns / 1e6
            row["device_busy_ms"] = (dur - idle_ns) / 1e6
        out.append(row)
    return out


# -- the ring -----------------------------------------------------------------

def _program_counts():
    """Whether the program under the benchmark counts what a step does:
    a run without one slow step leaves an empty ring on either side."""
    from incubator_mxnet_tpu.serving import engine

    return hasattr(engine, "STEP_COUNTS")


def window_slow_steps(events, window_start, window_s, offset=0.0):
    """The `serving_step_slow` events of `events` whose step began inside
    the window, `at` carried from the engine's clock by `offset`. None
    when such an event lacks `at` or a count: a program that does not say
    what its steps did."""
    slow = [e for e in events if e.get("kind") == SLOW_KIND]
    if any(e.get(k) is None for e in slow for k in ("at",) + COUNTS):
        return None
    return [e for e in slow
            if window_start <= e["at"] + offset < window_start + window_s]


def _window_slow(run):
    """The window's slow steps; None when the run or the program gives
    nothing to read them from."""
    start, seconds = run.facts.get("window_start"), run.facts.get("window_s")
    if start is None or not seconds or not _program_counts():
        return None
    return window_slow_steps(flight._ring(), start, seconds,
                             flight.clock_offset())


def _stalls(slow):
    return [e for e in slow if e["prefills"] == 0]


def _keep(run, slow):
    """Leaves a traced run's slow steps beside its trace: the ring ends
    with the process, main() comes after it."""
    if run.trace is None:
        return
    directory = program_spans.trace_dir(run.cell.name)
    if os.path.isdir(directory):
        with open(os.path.join(directory, KEPT), "w") as f:
            json.dump({"window_s": run.facts["window_s"], "slow_steps": [
                {k: v for k, v in e.items() if k not in ("kind", "lane")}
                for e in slow]}, f)


def stall_share(run):
    """Share (%) of the window that true stalls took: what every slow step
    that carried no prefill took beyond the median it was judged by. 0 in
    a window without one."""
    slow = _window_slow(run)
    if slow is None:
        return None
    _keep(run, slow)
    return 100.0 * sum(e["step_s"] - e["median_s"]
                       for e in _stalls(slow)) / run.facts["window_s"]


def stall_fetch_share(run):
    """Of the time of the window's true stalls, the share (%) inside the
    blocking token fetch. 0 in a window without a stall (no stalled time
    lay in `fetch`; `stall_share` is 0 beside it then): a listed cell's
    line holds every entry, whatever the run drew."""
    slow = _window_slow(run)
    if slow is None:
        return None
    stalls = _stalls(slow)
    if not stalls:
        return 0.0
    return 100.0 * sum(e["phases"]["fetch"] for e in stalls) / sum(
        e["step_s"] for e in stalls)


# -- by hand ------------------------------------------------------------------

def report(run):
    """What main() prints: the traced stretch by class of step."""
    steps = _steps(run)
    idle = idle_s_by_class(run, steps) if steps else {}
    by_class = {}
    for r in steps:
        row = by_class.setdefault(r["class"], {
            "count": 0, "host_ms": 0.0, "fetch_ms": 0.0, "finished": 0,
            "device_idle_ms": 1e3 * idle.get(r["class"], 0.0)})
        row["count"] += 1
        row["host_ms"] += (r["end"] - r["start"]) / 1e6
        row["fetch_ms"] += r["fetch_ns"] / 1e6
        row["finished"] += r["finished"]
    return {"steps": len(steps), "by_class": by_class,
            "idle_ms_in_steps": 1e3 * sum(idle.values()),
            "ahead_dispatch_share": ahead_dispatch_share(run),
            "idle_ms_per_finish": idle_ms_per_finish(run),
            "stalls_in_stretch": stalls_of(steps, run)}


def main(argv):
    """The last traced run of cell argv[0], read from this checkout."""
    import types

    cell = loader.load_cell(argv[0])
    directory = program_spans.trace_dir(cell.name)
    trace = tracered.read_xplane(tracered.find_xplane(directory),
                                 rehearse="--rehearse" in argv)
    reduced = tracered.summarize(
        trace, loader.load_opclasses(cell.traffic["job"]))
    run = types.SimpleNamespace(cell=cell, trace=reduced, facts={},
                                peaks=None)
    out = {"cell": cell.name, "window_s": reduced["window_s"],
           "busy_s": reduced["busy_s"], **report(run)}
    try:
        with open(os.path.join(directory, KEPT)) as f:
            out["ring"] = json.load(f)
    except FileNotFoundError:
        out["ring"] = None
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
