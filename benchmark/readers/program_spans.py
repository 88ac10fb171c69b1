"""Readers of the PROGRAM's own spans in a traced run's profile.

The program opens `telemetry.span(...)` at its layer boundaries
(`serving.step` and what nests in it, `trainstep.call` and its phases); in
a profiler session each is a host event in the same `.xplane.pb` as the
device's ops, on the same clock. `harness/tracered.read_xplane` keeps only
the benchmark's own `bench.*` events, so this file opens the run's
`.xplane.pb` once more (host planes only, once per process) and keeps the
events named in `telemetry.names.SPAN_NAMES`.

Spans nest by containment on their thread's line. A span's SELF time is its
duration less what its children cover; flattened, a line is the innermost
span open at each instant, which is what `tracered.attribute_gaps` needs to
say which span the host was in while the device sat idle.

A program without such spans (any commit before they were added) leaves
nothing to read: every reader here then returns None and the metric stays
out of the line.

By hand, for the last traced run of a cell in this checkout:

    python3 benchmark/readers/program_spans.py <cell>

prints each span's count, total and self time, and the device's idle gaps
by innermost program span.
"""
import functools
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness import loader, tracered


def trace_dir(cell_name, root=loader.ROOT):
    """Where run.py leaves the trace of a cell's last traced run."""
    return os.path.join(root, ".bench_trace", cell_name)


def read_lines(path, names=None):
    """{(plane, line): [[name, start_ns, dur_ns], ...]} of the host events of
    the .xplane.pb at `path` whose names are in `names` (default: the
    program's registered span names), lines without any left out."""
    from jax.profiler import ProfileData

    if names is None:
        from incubator_mxnet_tpu.telemetry.names import SPAN_NAMES as names
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracered.DEVICE_PLANE):
            continue
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns]
                      for e in line.events if e.name in names]
            if events:
                lines.setdefault((plane.name, line.name), []).extend(events)
    return lines


@functools.lru_cache(maxsize=None)
def _lines_of(path):
    return read_lines(path)


def nest(events):
    """One line's events nested by containment, in order of start:
    [{"name", "start", "end", "self_ns", "path"}], `path` the names of the
    spans around it, outermost first. An event that only overlaps the one
    before it (clocks of two ends read out of order) counts as its
    sibling."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1]["end"] <= start:
            stack.pop()
        node = {"name": name, "start": start, "end": start + dur,
                "self_ns": dur, "path": tuple(n["name"] for n in stack)}
        if stack:
            stack[-1]["self_ns"] -= min(dur, stack[-1]["end"] - start)
        stack.append(node)
        out.append(node)
    for node in out:
        node["self_ns"] = max(node["self_ns"], 0)
    return out


def flatten(nodes):
    """[[name, start_ns, dur_ns]] without overlap: for every instant some
    span of `nodes` (one line, from `nest`) is open, the innermost one."""
    flat, stack = [], []

    def emit(name, lo, hi):
        if hi > lo:
            flat.append([name, lo, hi - lo])

    cursor = None
    for node in nodes + [None]:
        start = node["start"] if node else float("inf")
        while stack and stack[-1]["end"] <= start:
            done = stack.pop()
            emit(done["name"], cursor, done["end"])
            cursor = max(cursor, done["end"])
        if node is None:
            break
        if stack:
            emit(stack[-1]["name"], cursor, start)
        cursor = start
        stack.append(node)
    return flat


def _under(node, root):
    return node["name"] == root or root in node["path"]


def _nested_lines(run):
    """[nodes per line] of the run's own trace, inside its traced window;
    [] when there is no trace or the program left no span in it."""
    if run.trace is None:
        return []
    try:
        path = tracered.find_xplane(trace_dir(run.cell.name))
    except FileNotFoundError:
        return []
    lo, hi = run.trace["window"]
    return [[n for n in nest(events) if lo <= n["start"] < hi]
            for events in _lines_of(path).values()]


def self_ms_per_root(run, root, exclude=()):
    """Host time per `root` span: the self time of every `root` in the
    traced window and of all that nests in it, spans named in `exclude`
    left out, over the number of `root` spans (ms). With nothing excluded
    that is the mean duration of `root`."""
    roots = self_ns = 0
    for nodes in _nested_lines(run):
        for n in nodes:
            if not _under(n, root):
                continue
            roots += n["name"] == root
            if n["name"] not in exclude:
                self_ns += n["self_ns"]
    return self_ns / 1e6 / roots if roots else None


def _worst_device(run):
    """The device with the least busy time, as tracered.summarize picks it
    for the breakdown."""
    return min(run.trace["devices"], key=lambda d: d["busy_s"])["device"]


def idle_ms_per_root(run, root):
    """Device idle time (gaps of at least tracered.MIN_GAP_NS, on the
    device that is busy least) that falls inside a `root` span, per `root`
    span in the traced window (ms): the part of the host's time in `root`
    that the chip waits for."""
    spans = [[n["name"], n["start"], n["end"] - n["start"]]
             for nodes in _nested_lines(run) for n in nodes
             if n["name"] == root]
    if not spans:
        return None
    gaps = tracered.idle_gaps(_worst_device(run), run.trace["window"])
    idle_s = tracered.attribute_gaps(gaps, spans).get(root, 0.0)
    return 1e3 * idle_s / len(spans)


def idle_by_span(run):
    """{span: idle seconds}: the device's idle gaps by the innermost
    program span open during them, "(no span)" for the rest. Spans of
    several threads each get the gaps they cover."""
    gaps = tracered.idle_gaps(_worst_device(run), run.trace["window"])
    flat = [seg for nodes in _nested_lines(run) for seg in flatten(nodes)]
    return tracered.attribute_gaps(gaps, flat)


def table(run):
    """{span: {"count", "total_ms", "self_ms"}} over the traced window."""
    out = {}
    for nodes in _nested_lines(run):
        for n in nodes:
            row = out.setdefault(n["name"],
                                 {"count": 0, "total_ms": 0.0, "self_ms": 0.0})
            row["count"] += 1
            row["total_ms"] += (n["end"] - n["start"]) / 1e6
            row["self_ms"] += n["self_ns"] / 1e6
    return out


def main(argv):
    """The last traced run of cell argv[0], read from this checkout."""
    import types

    cell = loader.load_cell(argv[0])
    rehearse = "--rehearse" in argv
    trace = tracered.read_xplane(
        tracered.find_xplane(trace_dir(cell.name)), rehearse=rehearse)
    reduced = tracered.summarize(
        trace, loader.load_opclasses(cell.traffic["job"]))
    run = types.SimpleNamespace(cell=cell, trace=reduced, facts={},
                                peaks=None)
    print(json.dumps({
        "cell": cell.name, "window_s": reduced["window_s"],
        "busy_s": reduced["busy_s"], "spans": table(run),
        "idle_s_by_program_span": idle_by_span(run),
        "idle_s_by_bench_span": dict(reduced["breakdown"]["idle_gaps"])},
        indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
