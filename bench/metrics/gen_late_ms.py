"""95th percentile of how late the load generator sent each request
after its due time. Latency is timed from the due time, so a late
generator shows here and not as a faster server."""
from lib.stats import gen_late_p95


def read(run, trace):
    rows = run.data.get("rows", ())
    return gen_late_p95(rows) if rows else None
