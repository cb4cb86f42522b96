"""Share of the roofline of one sparse HAP sweep: the least time the
chip could take for the bytes a sweep must move (``lib.roofline.
topk_sweep_bytes``, counted from the (L, N, k + 1) shapes) and its
operations, over the device time of a sweep (as ``topk_sweep_ms``). The
bytes bound it: a sweep does about 20 operations per 4-byte entry."""
from lib import roofline
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
        roofline.topk_sweep_bytes(d["n"], d["kk"], d["levels"]),
        roofline.topk_sweep_flops(d["n"], d["kk"], d["levels"]),
        peaks_for(d["device_kind"]))
    return 100.0 * least / t
