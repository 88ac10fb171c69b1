"""Order statistics for the benchmark's timings.

A timing is reported as its median and as the highest percentile that has
at least ten samples beyond it, with the sample count (choosing-metrics
guide, section 1). Percentiles are nearest-rank on the sorted samples: a
reported value is always one that was measured."""
import statistics

PERCENTILES = (50.0, 75.0, 90.0, 95.0, 99.0, 99.9)
MIN_BEYOND = 10


def _rank(n, q):
    """Nearest rank of the q-th percentile among n samples, in integers
    (q to a tenth of a percent): 0.9 * 100 is not 90 in floating point."""
    return max(1, -(-round(q * 10) * n // 1000))


def percentile(samples, q):
    """Nearest-rank q-th percentile (0 < q <= 100) of a non-empty list."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    return xs[_rank(len(xs), q) - 1]


def median(samples):
    return percentile(samples, 50.0)


def excess_over_median(samples, factor):
    """What the samples above `factor` x their median took beyond the
    median, summed: the time lost to stalls among steps of one kind."""
    typical = median(samples)
    return sum(x - typical for x in samples if x > factor * typical)


def supported_percentile(n):
    """The highest of PERCENTILES with at least MIN_BEYOND of `n` samples
    beyond it; 50.0 when even the 75th has fewer."""
    best = 50.0
    for q in PERCENTILES:
        if n - _rank(n, q) >= MIN_BEYOND:
            best = q
    return best


def summary(samples):
    """{"n", "median", "q", "tail"}: the sample count, the median, and the
    highest supported percentile with its value. Empty input gives n 0."""
    n = len(samples)
    if not n:
        return {"n": 0}
    q = supported_percentile(n)
    return {"n": n, "median": median(samples), "q": q,
            "tail": percentile(samples, q)}


def spread(values):
    """Distance between the quartiles over the median — the run-to-run
    spread the bounds in BENCHMARK.json are set from. Quartiles are
    interpolated (a handful of runs has no measured quartile)."""
    med = statistics.median(values)
    if not med:
        raise ValueError("spread is undefined at a median of 0")
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / abs(med)
