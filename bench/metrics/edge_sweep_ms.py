"""Device milliseconds per sparse HAP sweep of the edge-list cell: the
self time of the ops in the ``run_topk`` program over the window's
solves times their sweeps, from the trace."""
from lib.trace import module_seconds


def read(run, trace):
    if trace is None or run.data.get("solves", 0) < 1:
        return None
    t = module_seconds(trace, "run_topk")
    d = run.data
    return t / (d["solves"] * d["sweeps"]) * 1e3 if t > 0 else None
