"""The benchmark's own arithmetic: percentiles, pooled gaps, spreads."""
import statistics


def percentile(values, q):
    """``q``-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default rule). Raises on an empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def pooled_gaps(token_times):
    """All gaps between consecutive token times of each request, pooled
    over the requests: ``[[t0, t1, t2], [u0, u1]] -> [t1-t0, t2-t1, u1-u0]``."""
    return [b - a for times in token_times for a, b in zip(times, times[1:])]


def iqr_spread(values):
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the builder's rule."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
