"""Share of the edge-list cell's window in which no op ran on the
device: 1 minus the union of device op intervals over the window, from
the trace."""


def read(run, trace):
    if trace is None or trace["devices"] == 0:
        return None
    return 100.0 * trace["idle_share"]
