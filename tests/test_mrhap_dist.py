"""Distributed MR-HAP equivalence — run in a subprocess so the forced
8-device host platform never leaks into this test session (the rest of the
suite must see 1 device)."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    comm_bytes_per_iteration, pad_similarity, pairwise_similarity, run_hap,
    run_mrhap, set_preferences, stack_levels,
)
from repro.core.mrhap import run_mrhap_2d
from repro.core.preferences import median_preference
from repro.launch.mesh import make_mesh
from repro.data import gaussian_blobs

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "mrhap_dist_check.py")


@pytest.mark.slow
def test_distributed_equivalence_8_workers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, HELPER], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_single_worker_mesh_equals_dense():
    """W=1 degenerate mesh: distributed path must equal dense exactly."""
    x, _ = gaussian_blobs(n=48, k=3, seed=1)
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    s3 = stack_levels(s, 2)
    dense = run_hap(s3, iterations=15, damping=0.5, order="parallel")
    mesh = make_mesh((1,), ("workers",))
    for mode in ("stats", "transpose"):
        dist = run_mrhap(s3, mesh, iterations=15, damping=0.5,
                         comm_mode=mode)
        # shard_map lowering reorders float reductions slightly even at W=1
        np.testing.assert_allclose(np.asarray(dist.r),
                                   np.asarray(dense.state.r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(dist.exemplars),
                                      np.asarray(dense.exemplars))


def test_indivisible_n_raises():
    s3 = jnp.zeros((2, 10, 10))
    mesh = make_mesh((1,), ("workers",))
    # 10 % 1 == 0 fine; fake worker count via pad_similarity contract instead
    s3p, n0 = pad_similarity(s3, 4)
    assert s3p.shape[1] == 12 and n0 == 10


def test_comm_model_stats_much_cheaper():
    n, levels, w = 8192, 3, 64
    t = comm_bytes_per_iteration(n, levels, w, "transpose")
    s = comm_bytes_per_iteration(n, levels, w, "stats")
    assert t / s > 20  # O(N^2/W) vs O(N) per iteration


def test_mrhap_2d_degenerate_mesh_equals_dense():
    """(1,1) tile mesh: the 2-D decomposition must reproduce dense HAP."""
    x, _ = gaussian_blobs(n=48, k=3, seed=2)
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    s3 = stack_levels(s, 2)
    dense = run_hap(s3, iterations=15, damping=0.5, order="parallel")
    mesh = make_mesh((1, 1), ("rows", "cols"))
    dist = run_mrhap_2d(s3, mesh, iterations=15, damping=0.5)
    np.testing.assert_allclose(np.asarray(dist.r),
                               np.asarray(dense.state.r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(dist.exemplars),
                                  np.asarray(dense.exemplars))
