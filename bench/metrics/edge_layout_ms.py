"""Host milliseconds per solve that the program spends laying the edge
list out for the sweep: the mean over the window's solves of its
``edge_layout.host_s`` counter (``repro.runtime.trace.edge_layout``)."""


def read(run, trace):
    host_s = run.data.get("layout_host_s")
    if not host_s:
        return None
    return 1e3 * sum(host_s) / len(host_s)
