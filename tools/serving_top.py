#!/usr/bin/env python
"""Polling text UI over the serving engine's /debug/engine endpoint —
`top` for the continuous-batching engine.

The telemetry HTTP server (MXNET_TELEMETRY_PORT / telemetry.enable(port))
serves the engine's live snapshot at /debug/engine when
MXTPU_DEBUG_ENDPOINTS=1; this tool polls it and renders the slot table,
queue, page-pool health, goodput split, compile counters, and SLO state:

    python tools/serving_top.py http://localhost:9090
    python tools/serving_top.py localhost:9090 --interval 0.5
    python tools/serving_top.py http://localhost:9090 --once
    python tools/serving_top.py --file snapshot.json   # offline render

When the process also runs a serving FLEET (serving/fleet.py), its
/debug/fleet snapshot is rendered below the engine view: one row per
replica (state, slots, queue, in-flight, pool occupancy, heartbeat
age) plus the failover/drain counters — the operator's view of a
rolling restart. A target without /debug/fleet just renders the engine
view; `--file` dispatches on the snapshot's embedded schema.

Stdlib-only (urllib), same no-new-deps rule as the exporters it reads.
"""
import argparse
import json
import sys
import time
import urllib.error
import urllib.request

CLEAR = "\x1b[2J\x1b[H"


def snapshot_url(target, endpoint="/debug/engine"):
    """Normalize a host[:port] or URL into a /debug/* endpoint."""
    if "://" not in target:
        target = "http://" + target
    target = target.rstrip("/")
    if not target.endswith(endpoint):
        target += endpoint
    return target


def fetch(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _bar(fraction, width=20):
    fraction = min(1.0, max(0.0, float(fraction)))
    filled = int(round(fraction * width))
    return "#" * filled + "-" * (width - filled)


def render(snap):
    """The whole screen as one string — pure function of the snapshot,
    so tests render without a server."""
    lines = []
    pages = snap.get("pages", {})
    tokens = snap.get("tokens", {})
    lines.append(
        f"serving engine  step {snap.get('steps', 0)}  "
        f"slots {snap.get('slots_in_use', 0)}/{len(snap.get('slots', []))}  "
        f"queue {snap.get('queue_depth', 0)}  "
        f"finished {snap.get('requests_finished', 0)}")
    lines.append(
        f"pages  {pages.get('in_use', 0)}/{pages.get('capacity', 0)} "
        f"[{_bar(pages.get('occupancy', 0.0))}] "
        f"occupancy {pages.get('occupancy', 0.0):.2f}  "
        f"fragmentation {pages.get('fragmentation', 0.0):.2f}")
    lines.append(
        f"tokens prefill {tokens.get('prefill', 0)}  "
        f"decode {tokens.get('decode', 0)}  pad {tokens.get('pad', 0)}  "
        f"evicted {tokens.get('wasted_evicted', 0)}  "
        f"goodput {tokens.get('fraction', 1.0):.3f}")
    cache = snap.get("cache") or {}
    if cache.get("decode_steps"):
        n, ahead = cache["decode_steps"], cache.get("decode_steps_ahead", 0)
        lines.append(
            f"decode steps {n}  ahead {ahead} ({ahead / n:.2f})"
            + ("  one in flight" if snap.get("decode_in_flight") else ""))
    slow = snap.get("slow_steps") or {}
    if slow.get("count"):
        last = slow["last"]
        lines.append(
            f"slow steps {slow['count']}  "
            f"+{slow['excess_s']:.3f} s over the median  "
            f"last: step {last['step']} {last['step_s']:.3f} s "
            f"(fetch {last['phases']['fetch']:.3f}, "
            f"prefills {last['prefills']})")
    prefix = snap.get("prefix_cache")
    if prefix:
        hist = prefix.get("refcount_histogram") or {}
        hist_str = " ".join(
            f"{k}x{hist[k]}" for k in sorted(hist, key=int)) or "-"
        lines.append(
            f"prefix cached {prefix.get('cached_pages', 0)} pages  "
            f"hit_rate {prefix.get('hit_rate', 0.0):.2f} "
            f"({prefix.get('hits', 0)}/{prefix.get('lookups', 0)})  "
            f"saved {prefix.get('tokens_saved', 0)} tok  "
            f"cow {prefix.get('cow_copies', 0)}  "
            f"evictions {prefix.get('evictions', 0)}  "
            f"refs {hist_str}")
    spec = snap.get("speculation")
    if spec:
        lines.append(
            f"spec n={spec.get('ngram', 0)} k={spec.get('lookahead', 0)}  "
            f"acceptance {spec.get('acceptance', 0.0):.2f} "
            f"({spec.get('accepted', 0)}/{spec.get('proposed', 0)})")
    chunked = snap.get("chunked_prefill")
    if chunked:
        lines.append(
            f"chunked prefill C={chunked.get('chunk', 0)}  "
            f"in_flight {chunked.get('in_flight', 0)}  "
            f"chunks {chunked.get('chunks_total', 0)}")
    lines.append("")
    lines.append(f"{'slot':<6}{'state':<10}{'request':>9}{'age_s':>9}"
                 f"{'prompt':>8}{'tokens':>8}{'pos':>6}{'pages':>7}")
    for row in snap.get("slots", []):
        if row.get("state") == "idle":
            lines.append(f"{row['slot']:<6}{'idle':<10}")
        else:
            lines.append(
                f"{row['slot']:<6}{row['state']:<10}"
                f"{row['request_id']:>9}{row['age_s']:>9.3f}"
                f"{row['prompt_len']:>8}{row['tokens_out']:>8}"
                f"{row['position']:>6}{row['pages_held']:>7}")
    queue = snap.get("queue", [])
    if queue:
        lines.append("")
        lines.append(f"{'queued':<9}{'age_s':>9}{'prompt':>8}{'max_new':>9}")
        for row in queue:
            lines.append(f"{row['request_id']:<9}{row['age_s']:>9.3f}"
                         f"{row['prompt_len']:>8}"
                         f"{row['max_new_tokens']:>9}")
    compile_rows = snap.get("compile") or {}
    if compile_rows:
        lines.append("")
        lines.append(f"{'program':<26}{'signatures':>12}{'retraces':>10}")
        for fn in sorted(compile_rows):
            row = compile_rows[fn]
            lines.append(f"{fn:<26}{row.get('signatures', 0):>12}"
                         f"{row.get('retraces', 0):>10}")
    slo = snap.get("slo")
    if slo:
        lines.append("")
        lines.append(f"{'objective':<18}{'state':<10}{'burn_s':>9}"
                     f"{'burn_l':>9}{'breaches':>10}")
        for name in sorted(slo):
            row = slo[name]
            lines.append(
                f"{name:<18}{row.get('state', '?'):<10}"
                f"{row.get('burn_short', 0.0):>9.2f}"
                f"{row.get('burn_long', 0.0):>9.2f}"
                f"{row.get('breaches', 0):>10}")
    return "\n".join(lines)


def render_fleet(snap):
    """The fleet section as one string — pure function of a
    /debug/fleet snapshot (mxtpu-serving-fleet-debug-v1)."""
    lines = []
    counters = snap.get("counters", {})
    lines.append(
        f"serving fleet  {'DRAINING  ' if snap.get('draining') else ''}"
        f"failovers {counters.get('failovers', 0)}  "
        f"resubmits {counters.get('resubmits', 0)}  "
        f"drains {counters.get('drains', 0)}  "
        f"hb_timeout {snap.get('heartbeat_timeout_s', 0.0):g}s")
    journal = snap.get("journal", {})
    states = journal.get("states", {})
    states_str = " ".join(
        f"{k}:{states[k]}" for k in sorted(states)) or "-"
    lines.append(
        f"journal {journal.get('entries', 0)} entries ({states_str})  "
        f"dup_dropped {journal.get('dup_tokens_dropped', 0)}  "
        f"lost {journal.get('lost', 0)}")
    front = snap.get("front_queue")
    if front:
        lines.append(
            f"front queue {front.get('depth', 0)} waiting  "
            f"oldest {front.get('oldest_s', 0.0):.2f}s")
    tenants = snap.get("tenants", {})
    if tenants:
        lines.append("queued  " + "  ".join(
            f"{t}:{n}" for t, n in sorted(tenants.items())))
    lines.append("")
    lines.append(f"{'replica':<10}{'state':<10}{'slots':>8}{'queue':>7}"
                 f"{'inflight':>10}{'occupancy':>24}{'hb_age':>9}"
                 f"{'pumps':>8}")
    for row in snap.get("replicas", []):
        age = row.get("heartbeat_age_s")
        lines.append(
            f"{row.get('replica', '?'):<10}{row.get('state', '?'):<10}"
            f"{row.get('slots_in_use', 0)}/{row.get('slots', 0):<5}"
            f"{row.get('queue_depth', 0):>6}"
            f"{row.get('inflight', 0):>10}"
            f"  [{_bar(row.get('occupancy', 0.0))}]"
            f"{(f'{age:.2f}' if age is not None else '-'):>9}"
            f"{row.get('pumps', 0):>8}")
    return "\n".join(lines)


def render_any(snap):
    """Schema dispatch for --file mode: fleet snapshots render the
    fleet view, anything else the engine view."""
    if snap.get("schema") == "mxtpu-serving-fleet-debug-v1":
        return render_fleet(snap)
    return render(snap)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="polling text UI over /debug/engine")
    ap.add_argument("target", nargs="?",
                    help="telemetry server URL or host:port")
    ap.add_argument("--file", help="render a snapshot JSON file instead "
                                   "of polling a server")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="poll interval in seconds (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit")
    args = ap.parse_args(argv)

    if args.file:
        with open(args.file, encoding="utf-8") as f:
            print(render_any(json.load(f)))
        return 0
    if not args.target:
        ap.error("need a server target or --file")
    url = snapshot_url(args.target)
    fleet_endpoint = snapshot_url(args.target, "/debug/fleet")
    while True:
        try:
            snap = fetch(url)
        except (urllib.error.URLError, OSError) as e:
            print(f"serving_top: {url}: {e}", file=sys.stderr)
            return 1
        try:
            fleet = fetch(fleet_endpoint)
        except (urllib.error.URLError, OSError):
            fleet = None  # engine-only process: no fleet section
        screen = render(snap)
        if fleet:
            screen += "\n\n" + render_fleet(fleet)
        if args.once:
            print(screen)
            return 0
        sys.stdout.write(CLEAR + screen + "\n")
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
