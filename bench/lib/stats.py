"""Order statistics as the benchmark reports them."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule) of a
    non-empty sequence; an infinite value (a request that never came
    back) sorts last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def beyond(values, q: float) -> int:
    """How many samples lie above the q-th percentile."""
    p = percentile(values, q)
    return sum(1 for v in values if v > p)


# The served cell's readings, shared by its metric readers, the driver's
# notes and the knee sweep so that each is defined once.
def latencies(rows) -> list:
    """Each request's latency (ms) from its due time to its result."""
    return [r["latency_ms"] for r in rows]


def gen_late_p95(rows) -> float:
    """95th percentile of how late the generator sent each request."""
    return percentile([r["late_ms"] for r in rows], 95.0)


def served_median(rows, key: str) -> float:
    """Median of a service counter over the requests that were served."""
    return median([r[key] for r in rows if r["ok"]])


def sweeps_per_launch(launches) -> float:
    """Mean over launches of the most sweeps among their riders."""
    return sum(lch["sweeps"] for lch in launches) / len(launches)
