"""Median launch wall of the window's served requests, as the service
reports it (``ClusterResponse.solve_ms``: pad, run the compiled batch,
fetch)."""
from lib.stats import served_median


def read(run, trace):
    rows = run.data.get("rows", ())
    return served_median(rows, "solve_ms") if any(
        r["ok"] for r in rows) else None
