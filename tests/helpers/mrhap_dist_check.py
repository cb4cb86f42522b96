"""Subprocess helper: distributed MR-HAP vs dense parallel HAP equivalence
on 8 forced host devices. Exits nonzero on mismatch; prints max deltas."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    pad_similarity, pairwise_similarity, run_hap, run_mrhap, set_preferences,
    stack_levels,
)
from repro.core.mrhap import run_mrhap_2d
from repro.core.preferences import median_preference
from repro.data import gaussian_blobs
from repro.launch.mesh import make_mesh


def main() -> int:
    x, _ = gaussian_blobs(n=160, k=5, seed=3)
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    s3 = stack_levels(s, 3)
    dense = run_hap(s3, iterations=25, damping=0.6, order="parallel")
    mesh = make_mesh((8,), ("workers",))
    ok = True
    for mode in ("stats", "transpose"):
        dist = run_mrhap(s3, mesh, iterations=25, damping=0.6,
                         comm_mode=mode)
        dr = float(np.max(np.abs(np.asarray(dist.r)
                                 - np.asarray(dense.state.r))))
        agree = float(np.mean(np.asarray(dist.exemplars)
                              == np.asarray(dense.exemplars)))
        print(f"{mode}: max|dr|={dr:.3e} exemplar_agree={agree:.4f}")
        scale = float(np.max(np.abs(np.asarray(dense.state.r))))
        if dr > 1e-4 * max(scale, 1.0) or agree < 0.99:
            ok = False

    # 2-D tile decomposition (rows x cols) — beyond the paper's M <= LN
    mesh2d = make_mesh((4, 2), ("rows", "cols"))
    dist2d = run_mrhap_2d(s3, mesh2d, iterations=25, damping=0.6)
    agree2d = float(np.mean(np.asarray(dist2d.exemplars)
                            == np.asarray(dense.exemplars)))
    print(f"2d(4x2): exemplar_agree={agree2d:.4f}")
    if agree2d < 0.99:
        ok = False

    # padding inertness
    s3p, n0 = pad_similarity(s3, 64)
    distp = run_mrhap(s3p, mesh, iterations=25, damping=0.6)
    agree = float(np.mean(np.asarray(distp.exemplars[:, :n0])
                          == np.asarray(dense.exemplars)))
    print(f"padded: exemplar_agree={agree:.4f} (N={s3p.shape[1]})")
    if agree < 0.99:
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
