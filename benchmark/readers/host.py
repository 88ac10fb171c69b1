"""Readers of what the job counted and timed on the host: the client's
stamps, the engine's own counters, the step loop's tallies. Each takes the
run and the metric file's args and returns a number, or None when the run
holds nothing to read it from."""
from benchmark.harness import stats


def _get(facts, path):
    """facts["a"]["b"] for "a.b"; None when any part is missing."""
    cur = facts
    for key in path.split("."):
        if not isinstance(cur, dict) or cur.get(key) is None:
            return None
        cur = cur[key]
    return cur


def fact(run, key):
    return _get(run.facts, key)


def rate(run, count, per_chip=False, over="window_s"):
    """facts[count] per second of facts[over] (the window), per chip if
    asked."""
    n, seconds = _get(run.facts, count), _get(run.facts, over)
    if n is None or not seconds:
        return None
    per_s = n / seconds
    return per_s / run.facts["chips"] if per_chip else per_s


def mean(run, samples):
    """The mean of the list facts[samples]."""
    xs = _get(run.facts, samples)
    return sum(xs) / len(xs) if xs else None


def percentile(run, samples, q):
    """The q-th percentile (nearest rank) of the list facts[samples]."""
    xs = _get(run.facts, samples)
    return stats.percentile(xs, q) if xs else None


def excess_share(run, samples, over_median):
    """Share (%) of the total of the list facts[samples] that its members
    over `over_median` x the median took beyond the median."""
    xs = _get(run.facts, samples)
    if not xs:
        return None
    return 100.0 * stats.excess_over_median(xs, over_median) / sum(xs)


def ratio(run, num, den, percent=False):
    """facts[num] over the product of facts[d] for d in `den`."""
    top = _get(run.facts, num)
    bottom = 1.0
    for d in den:
        v = _get(run.facts, d)
        if v is None:
            return None
        bottom *= v
    if top is None or not bottom:
        return None
    return (100.0 if percent else 1.0) * top / bottom
