"""Device milliseconds per sparse HAP sweep in the alpha update's
gathers of the column statistics through the column map: the self time
of the ``run_topk`` ops whose scope path holds ``hap_gather``, from the
trace (``lib.sweep_scopes``)."""
from lib import sweep_scopes


def read(run, trace):
    return sweep_scopes.read(run, trace, "gather")
