"""Share of the roofline of the served dense sweeps: the bytes the
window's launches moved, from their bucket shapes, launch batch and the
sweeps each ran (``lib.roofline.dense_sweep_bytes`` per request and
sweep), at the chip's peak bandwidth, over the device time of the
batched solve program (``jit__solve_fn``) in the trace."""
from lib import roofline
from lib.peaks import peaks_for
from lib.trace import module_seconds

SOLVE_MODULE = "jit__solve_fn"


def read(run, trace):
    launches = run.data.get("launches", ())
    if trace is None or not launches:
        return None
    t = module_seconds(trace, SOLVE_MODULE)
    if t <= 0:
        return None
    levels = run.data["levels"]
    moved = sum(lch["batch"] * lch["sweeps"]
                * roofline.dense_sweep_bytes(lch["bucket"][0], levels)
                for lch in launches)
    flops = sum(lch["batch"] * lch["sweeps"]
                * roofline.dense_sweep_flops(lch["bucket"][0], levels)
                for lch in launches)
    least, _ = roofline.bound(moved, flops,
                              peaks_for(run.data["device_kind"]))
    return 100.0 * least / t
