"""Device milliseconds per sparse HAP sweep in the column sums of the
availability and tau updates: the self time of the ``run_topk`` ops
whose scope path holds ``hap_colsum`` (a scatter-add over the stored
edges), from the trace (``lib.sweep_scopes``)."""
from lib import sweep_scopes


def read(run, trace):
    return sweep_scopes.read(run, trace, "colsum")
