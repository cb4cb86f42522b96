"""Share of the roofline of one sparse HAP sweep over an edge list: the
least time the chip could take for the bytes and operations of the
graph's stored entries (``lib.edge_roofline``), over the device time of
a sweep (as ``edge_sweep_ms``). The bytes bound it."""
from lib import edge_roofline, roofline
from lib.peaks import peaks_for
from lib.trace import module_seconds


def read(run, trace):
    if trace is None or run.data.get("solves", 0) < 1:
        return None
    d = run.data
    t = module_seconds(trace, "run_topk") / (d["solves"] * d["sweeps"])
    if t <= 0:
        return None
    least, _ = roofline.bound(
        edge_roofline.edge_sweep_bytes(d["entries"], d["levels"]),
        edge_roofline.edge_sweep_flops(d["entries"], d["levels"]),
        peaks_for(d["device_kind"]))
    return 100.0 * least / t
