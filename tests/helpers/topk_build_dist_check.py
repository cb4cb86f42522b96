"""Subprocess helper: sharded top-k build parity on 8 forced host
devices. N=1000 does not divide 8 workers evenly once rows are padded to
the mesh — the driver must pad, build per worker, and strip, selecting
the single-device reference build's edges with values within the
numeric contract (``parity.py``). Also
runs the full dense_topk solve through build='sharded'. Exits nonzero on
any mismatch."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax.numpy as jnp
import numpy as np
from parity import values_close

from repro.data import gaussian_blobs
from repro.kernels.topk_similarity import topk_similarity
from repro.launch.mesh import make_worker_mesh
from repro.solver import SolveConfig, solve
from repro.solver.topk_build import sharded_topk_similarity


def main() -> int:
    x, _ = gaussian_blobs(n=1000, k=5, seed=4)
    xj = jnp.asarray(x)
    k = 24
    mesh = make_worker_mesh()
    assert mesh.shape["workers"] == 8, mesh.shape
    vr, ir = topk_similarity(xj, k)
    ok = True
    for inner in ("reference", "twostage"):
        v, i = sharded_topk_similarity(xj, k, SolveConfig(), mesh=mesh,
                                       inner=inner)
        same = (np.array_equal(np.asarray(i), np.asarray(ir))
                and values_close(v, vr))
        print(f"sharded[{inner}] x 8 workers: edges_equal={same}")
        ok &= same

    ref = solve(x, backend="dense_topk", k=k, levels=2, max_iterations=15,
                preference="median", build="reference")
    res = solve(x, backend="dense_topk", k=k, levels=2, max_iterations=15,
                preference="median", build="sharded")
    same = np.array_equal(res.exemplars, ref.exemplars)
    print(f"solve(build='sharded') x 8 workers: exemplars_equal={same}")
    ok &= same
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
