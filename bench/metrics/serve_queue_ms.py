"""Median time the window's served requests waited in the service's
queue before their launch (``ClusterResponse.queue_ms``)."""
from lib.stats import served_median


def read(run, trace):
    rows = run.data.get("rows", ())
    return served_median(rows, "queue_ms") if any(
        r["ok"] for r in rows) else None
