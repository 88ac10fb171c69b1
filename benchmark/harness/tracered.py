"""From a profiler trace (.xplane.pb) to device busy time, idle gaps, time
per op class and per XLA module — the only reading of a trace in the repo.

`read_xplane` turns the file into plain lists with nothing but
`jax.profiler.ProfileData`; every reduction below works on those lists, so
the tests check them on a small hand-made trace
(benchmark/harness/testdata/) with numbers worked out by hand.

The plain form ("trace"):

  {"devices": [{"name": "/device:TPU:0",
                "ops":     [[name, start_ns, dur_ns], ...],   "XLA Ops" line
                "modules": [[name, start_ns, dur_ns], ...]}], "XLA Modules"
   "host":    [[name, start_ns, dur_ns], ...]}   the benchmark's own spans

All times are nanoseconds on the profiler's one clock. On a TPU an op's
name is its whole HLO instruction, `%fusion.84 = (...) fusion(...),
kind=kOutput, calls=...`; it carries no category, so the op classes
(benchmark/opclasses/) are patterns over that text. Ops nest (a `while`
covers the ops of its body), so time per op is SELF time: an op's duration
less what its children cover. Asynchronous copies and collectives run on a
line of their own ("Async XLA Ops"), which is not read: what they cost the
device's one instruction stream is their `-start` and `-done` ops.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
MIN_GAP_NS = 50_000
SHORT_NAME = 96  # characters of an op's HLO text kept in a breakdown


def find_xplane(trace_dir):
    """The one .xplane.pb under a jax.profiler log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path, rehearse=False):
    """The plain form of an .xplane.pb file.

    Device planes are those named /device:*. A trace without one is an
    error, unless `rehearse` says it was recorded on the CPU backend: then
    the XLA ops that the host's executor threads ran stand in as one
    device, with no modules (a walk-through, never a measurement)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = {"devices": [], "host": []}
    cpu_ops = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE)
        dev = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                dest = dev["ops" if line.name == OPS_LINE else "modules"]
                for e in line.events:
                    dest.append([e.name, e.start_ns, e.duration_ns])
            elif not is_device:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        trace["host"].append(
                            [e.name, e.start_ns, e.duration_ns])
                    elif rehearse and e.duration_ns and any(
                            k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.name, e.start_ns, e.duration_ns])
        if is_device and dev["ops"]:
            trace["devices"].append(dev)
    if not trace["devices"] and not rehearse:
        raise ValueError(f"{path} has no {DEVICE_PLANE}* plane with an "
                         f"'{OPS_LINE}' line: no device was traced")
    if not trace["devices"] and cpu_ops:
        trace["devices"].append({"name": "host-executed XLA ops (rehearsal)",
                                 "ops": cpu_ops, "modules": []})
    trace["host"].sort(key=lambda e: e[1])
    return trace


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def merge(intervals):
    """Sorted, disjoint [start, end) intervals covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """`intervals` (merged) less the points of `holes` (merged)."""
    out = []
    first = 0  # holes before this one end before the current interval
    for start, end in intervals:
        while first < len(holes) and holes[first][1] <= start:
            first += 1
        cur, j = start, first
        while j < len(holes) and holes[j][0] < end:
            if holes[j][0] > cur:
                out.append([cur, holes[j][0]])
            cur = max(cur, holes[j][1])
            j += 1
        if cur < end:
            out.append([cur, end])
    return out


def _spans(events):
    return [[s, s + d] for _, s, d in events]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window(trace):
    """[start_ns, end_ns] of the traced window: the benchmark's own
    `bench.window` span when the trace has it, else the extent of the
    device ops."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return [start, start + dur]
    starts = [e[1] for d in trace["devices"] for e in d["ops"]]
    ends = [e[1] + e[2] for d in trace["devices"] for e in d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device op")
    return [min(starts), max(ends)]


def busy_intervals(device, win):
    """Merged intervals inside `win` in which an op ran on `device`."""
    return merge(clip(_spans(device["ops"]), *win))


def idle_gaps(device, win):
    """The intervals of `win` in which no op ran on `device`."""
    return subtract([list(win)], busy_intervals(device, win))


def self_times(ops):
    """[(name, self_ns)] per op event: its duration less the part its
    nested ops cover. Events are nested by containment on one line."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    stack = []  # [name, end, self_ns]

    def close_until(t):
        while stack and stack[-1][1] <= t:
            name, _, self_ns = stack.pop()
            out.append((name, max(self_ns, 0)))

    for name, start, dur in order:
        close_until(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close_until(float("inf"))
    return out


def leaf_ops(ops):
    """The op events that contain no other: what actually occupied the
    device's units, as opposed to the `while` or `conditional` around."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (name, start, dur) in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        # sorted by start, longest first: an op holds another exactly when
        # it holds the next one whole (a partial overlap is two leaves)
        if nxt is None or nxt[1] + nxt[2] > start + dur or nxt[1] >= start + dur:
            leaves.append([name, start, dur])
    return leaves


def classifier(opclasses):
    """op name -> class name by the first pattern of `opclasses` that the
    name (on a TPU, the op's HLO text) matches; "other" when none does."""
    compiled = [(c["name"], re.compile(c["pattern"]))
                for c in opclasses["classes"]]
    cache = {}

    def classify(name):
        if name not in cache:
            cache[name] = next((cls for cls, pat in compiled
                                if pat.search(name)), "other")
        return cache[name]

    return classify


def op_seconds(device, win):
    """{op name: seconds of self time} of the ops of `device` that start
    inside `win`."""
    inside = [e for e in device["ops"] if win[0] <= e[1] < win[1]]
    out = {}
    for name, self_ns in self_times(inside):
        out[name] = out.get(name, 0.0) + self_ns / 1e9
    return out


def by_class(op_s, classify):
    """{class: seconds} from op_seconds' {op name: seconds}."""
    out = {}
    for name, seconds in op_s.items():
        cls = classify(name)
        out[cls] = out.get(cls, 0.0) + seconds
    return out


def module_stats(device, win, pattern):
    """(calls, seconds) of the XLA modules of `device` whose name matches
    `pattern` and that start inside `win`."""
    pat = re.compile(pattern)
    durs = [d for name, s, d in device["modules"]
            if win[0] <= s < win[1] and pat.search(name)]
    return len(durs), sum(durs) / 1e9


def exposed_seconds(device, win, classify, cls):
    """Seconds inside `win` in which an op of class `cls` ran on `device`
    and no op of another class did — a collective that nothing hides."""
    leaves = leaf_ops(device["ops"])
    mine = merge(clip(_spans([e for e in leaves if classify(e[0]) == cls]),
                      *win))
    others = merge(clip(_spans([e for e in leaves
                                if classify(e[0]) != cls]), *win))
    return total(subtract(mine, others)) / 1e9


def attribute_gaps(gaps, host_spans, min_ns=MIN_GAP_NS):
    """{span name: idle seconds}: each gap of at least `min_ns`, shared out
    among the benchmark's host spans open during it by overlap; what no
    span covers goes to "(no span)". `bench.window` itself is not a
    candidate. Spans are expected not to nest."""
    spans = sorted(((n, s, s + d) for n, s, d in host_spans
                    if n != WINDOW_SPAN), key=lambda sp: sp[1])
    out = {}
    first = 0  # spans before this one end before the current gap
    for gs, ge in sorted(gaps):
        if ge - gs < min_ns:
            continue
        while first < len(spans) and spans[first][2] <= gs:
            first += 1
        covered, j = 0, first
        while j < len(spans) and spans[j][1] < ge:
            name, ss, se = spans[j]
            j += 1
            ov = min(ge, se) - max(gs, ss)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov / 1e9
                covered += ov
        rest = (ge - gs) - covered
        if rest > 0:
            out["(no span)"] = out.get("(no span)", 0.0) + rest / 1e9
    return out


def _top(d, n):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summarize(trace, opclasses):
    """Everything the readers and the result line take from a trace:

      window_s, busy_s (mean over devices),
      per device: busy_s, op_s {op: s}, class_s {class: s}, its lines,
      breakdown {"device_ops": [[name, s]...], "idle_gaps": [[span, s]...]}

    The breakdown is that of the worst device, the one with the least
    busy time: its op classes and heaviest single ops, and its idle gaps
    by the host span open during them."""
    win = window(trace)
    classify = classifier(opclasses)
    per_device = []
    for dev in trace["devices"]:
        busy = total(busy_intervals(dev, win)) / 1e9
        op_s = op_seconds(dev, win)
        per_device.append({
            "name": dev["name"], "busy_s": busy, "op_s": op_s,
            "class_s": by_class(op_s, classify), "device": dev})
    if not per_device:
        raise ValueError("the trace holds no device plane with ops")
    window_s = (win[1] - win[0]) / 1e9
    worst = min(per_device, key=lambda d: d["busy_s"])
    classes = {f"class {k}": v for k, v in worst["class_s"].items()}
    ops = {}
    for name, seconds in worst["op_s"].items():
        short = f"op {name[:SHORT_NAME]}"
        ops[short] = ops.get(short, 0.0) + seconds
    gaps = attribute_gaps(idle_gaps(worst["device"], win), trace["host"])
    return {
        "window": win, "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "devices": per_device, "classify": classify,
        "breakdown": {"device_ops": _top(classes, 6) + _top(ops, 4),
                      "idle_gaps": _top(gaps, 10)},
    }
