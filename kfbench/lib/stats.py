"""The little arithmetic every metric shares."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values) -> float:
    return statistics.median(values)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the driver's check reads."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def dist_quantile(dist: dict, u: float) -> int:
    """The u-quantile (0 < u < 1) of a length distribution a traffic
    file states, clipped to its ``min``/``max`` and rounded."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(
            dist["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))
