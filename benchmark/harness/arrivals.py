"""The one traffic generator: a mix file of parameters in, requests out.

Everything is drawn from seeds, and the amount of work is fixed by the
mix and the window, not by the draw: the number of requests due in a
window is round(rate x seconds), and lengths are stratified over their
distribution, so two draws differ in order and content but not in load.

A mix with a `trace_seed` is a recorded trace in all but the file: the due
times and the lengths come from that seed, the same in every run, and the
run's own seed makes only the token ids (and with them nothing that the
timing depends on). Tails over the few dozen requests that fit a window
differ by 10-20 % between two Poisson draws (measured, PR 22), which would
hide any regression a PR could cause; replaying one trace leaves the
system's own noise. Without `trace_seed` the run's seed draws everything.

A replayed open loop also takes `due_jitter_s`: the run's seed moves each
due time by up to that much either way, far less than the gap between
arrivals. An engine that works in steps is sensitive to whether a request
lands just before or just after a step's end; one trace replayed to the
microsecond walks the same path nearly every time and another path once
in a while (measured, PR 22: five runs within 0.3 %, the sixth 2-5 % off),
which a spread between quartiles does not see and a later change to the
engine would meet at once. With the jitter every run walks its own path
and the spread says how much that matters.

A mix (benchmark/traffic/<name>.json) gives

  arrivals    {"process": "backlog"} — no schedule, the job keeps the queue
              full — or {"process": "gamma", "rate_per_s": r, "cv": c}: an
              open loop whose gaps are gamma distributed with coefficient
              of variation c (c = 1 is a Poisson process, c > 1 is bursty)
  prompt_len  a length distribution (below)
  output_len  a length distribution
  trace_seed, due_jitter_s   optional, see above
  prefix      optional {"groups": g, "len": n}: each request takes the first
              n tokens of one of g shared prefixes (system prompts)
  stagger_first  optional {"min": a, "max": b}: the first `slots` requests
              have their output length scaled by evenly spread factors in
              [a, b], so that a full batch does not finish in one step

A length distribution is {"dist": "uniform", "min", "max"},
{"dist": "lognormal", "median", "sigma", "min", "max"} (clipped), or
{"dist": "mixture", "parts": [{"weight": w, ...a distribution...}, ...]}.
"""
import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None        # None under a backlog
    prompt: np.ndarray         # (T,) int32, tokens in [1, vocab)
    max_new_tokens: int


def _quantile(spec, u):
    """Inverse CDF of one length distribution at u in (0, 1)."""
    dist = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    elif dist == "lognormal":
        x = float(spec["median"]) * math.exp(
            float(spec["sigma"]) * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return min(hi, max(lo, int(round(x))))


def draw_lengths(spec, n, rng):
    """n lengths from `spec`: one draw from each of n equal-probability
    strata, in seeded random order."""
    if spec["dist"] == "mixture":
        parts = spec["parts"]
        weights = np.array([float(p["weight"]) for p in parts])
        counts = np.floor(weights / weights.sum() * n).astype(int)
        counts[: n - counts.sum()] += 1
        out = np.concatenate([draw_lengths(p, int(c), rng)
                              for p, c in zip(parts, counts)])
        return rng.permutation(out)
    u = (np.arange(n) + rng.random(n)) / max(n, 1)
    u = np.clip(u, 1e-9, 1 - 1e-9)
    return rng.permutation(np.array([_quantile(spec, x) for x in u],
                                    dtype=np.int64))


def schedule(arrivals, seconds, rng):
    """Due times (s, sorted, in [0, seconds)) of an open loop: exactly
    round(rate x seconds) arrivals whose gaps are gamma distributed with
    the mix's cv, scaled to fill the window. cv 1 is a Poisson process
    conditioned on its count."""
    if arrivals["process"] != "gamma":
        raise ValueError(f"no schedule for process {arrivals['process']!r}")
    n = int(round(float(arrivals["rate_per_s"]) * seconds))
    if n < 1:
        raise ValueError("the rate gives no arrival inside the window")
    shape = 1.0 / float(arrivals.get("cv", 1.0)) ** 2
    gaps = rng.gamma(shape, size=n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def requests(mix, vocab, seed, *, slots=0, seconds=None):
    """The mix's requests for `seed`, in the order they are sent: token
    ids from `seed`; due times, lengths and prefix groups from the mix's
    `trace_seed` where it has one, else from `seed` too.

    Under a backlog: `mix["requests"]` of them, with no due time. Under an
    open loop: those due in a window of `seconds` (due_s in [0, seconds)),
    preceded by those of the lead-in (`mix["lead_in_s"]`, due_s < 0). The
    window's requests are drawn first and by themselves, so their number
    and their lengths do not depend on the lead-in."""
    tokens = np.random.default_rng(seed)
    rng = np.random.default_rng(mix.get("trace_seed", seed))
    arr = mix["arrivals"]
    before = np.zeros((0,))
    if arr["process"] == "backlog":
        n_window, due = int(mix["requests"]), None
    else:
        jitter = float(mix.get("due_jitter_s", 0.0))

        def jittered(times, lo, hi):
            moved = times + tokens.uniform(-jitter, jitter, size=len(times))
            return np.sort(np.clip(moved, lo, hi - 1e-6))

        due = jittered(schedule(arr, seconds, rng), 0.0, seconds)
        n_window = len(due)
        lead = float(mix.get("lead_in_s", 0.0))
        if lead:
            before = jittered(schedule(arr, lead, rng) - lead, -lead, 0.0)
            due = np.concatenate([before, due])

    def lengths(spec):
        in_window = draw_lengths(spec, n_window, rng)
        return np.concatenate([draw_lengths(spec, len(before), rng),
                               in_window])

    prompt_len = lengths(mix["prompt_len"])
    output_len = lengths(mix["output_len"])
    n = n_window + len(before)
    stagger = mix.get("stagger_first")
    if stagger and slots:
        k = min(slots, n)
        factors = stagger["min"] + (stagger["max"] - stagger["min"]) * (
            (rng.permutation(k) + 0.5) / k)
        output_len[:k] = np.maximum(1, np.round(output_len[:k] * factors))
    prefix = mix.get("prefix")
    shared = (tokens.integers(1, vocab, size=(int(prefix["groups"]),
                                              int(prefix["len"])))
              if prefix else None)
    out = []
    for i in range(n):
        prompt = tokens.integers(1, vocab, size=int(prompt_len[i]))
        if shared is not None:
            head = shared[rng.integers(len(shared))][: prompt.size - 1]
            prompt[: head.size] = head
        out.append(Request(i, None if due is None else float(due[i]),
                           prompt.astype(np.int32), int(output_len[i])))
    return out
