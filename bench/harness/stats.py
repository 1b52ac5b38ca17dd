"""Percentiles, as the benchmark computes them everywhere."""
from __future__ import annotations


def nearest_rank(xs, q: float) -> float:
    """Nearest-rank percentile q in [0, 100], as `serve/metrics._pct` takes
    it: the sample at rank round(q/100 * (n-1)). +inf entries count (a
    failed request misses every latency limit)."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    i = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[i])
