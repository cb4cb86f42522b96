"""Device milliseconds per sparse HAP sweep in the row-wise work: rho's
top-2, phi, c, damping, the assignment decode and the change count (the
self time of the ``run_topk`` ops under a sweep scope other than
``hap_colsum`` and ``hap_gather``), from the trace
(``lib.sweep_scopes``)."""
from lib import sweep_scopes


def read(run, trace):
    return sweep_scopes.read(run, trace, "rowwise")
