"""Similarity-build pipeline: every backend must select the identical
edge set (docs/solver.md "similarity build").

Contracts:

* parity of the two-stage (threshold-gated) build, the fused Pallas
  kernel (interpret mode on CPU), and the sharded driver against the
  reference scan AND the dense compression oracle (``topk_from_dense``)
  under the numeric contract (``tests/helpers/parity.py``: identical
  edge index sets, values within tolerance) — odd N, non-divisor tile
  shapes, k past the tile row count, and full coverage (k = N-1)
  included;
* tie-break determinism: duplicate similarity values (duplicated points)
  select the same edges on every path at any tile shape — the
  (value desc, col asc) contract that keeps k = N-1 parity meaningful;
* the build backend knob threads through ``SolveConfig``/``solve()`` and
  is validated at the front door;
* the sharded driver holds the same contract on a 1-device mesh here
  and on a real 8-worker mesh in the slow tier (subprocess helper).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from helpers.parity import assert_values_close
from repro.core.similarity import pairwise_similarity
from repro.data import gaussian_blobs
from repro.kernels.topk_build_fused import topk_similarity_fused
from repro.kernels.topk_similarity import (
    kd_order, topk_from_dense, topk_select_exact, topk_similarity,
    topk_similarity_twostage,
)
from repro.launch.mesh import make_worker_mesh
from repro.solver import SolveConfig, solve
from repro.solver.topk_build import (
    BUILD_BACKENDS, resolve_build_backend, sharded_topk_similarity,
)


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def _assert_edges(a, b):
    """The numeric contract on an edge list: identical column index
    sets, similarity values within tolerance."""
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert_values_close(a[0], b[0])


# ------------------------------------------------------------ bit parity
@pytest.mark.parametrize("n,d,k,seed", [
    (97, 3, 9, 0),       # odd N
    (200, 2, 32, 1),
    (130, 5, 129, 2),    # k = N-1 (full coverage)
    (64, 2, 63, 3),
    (257, 4, 40, 4),     # k past the fused/reference tile row count
])
def test_all_builds_match_dense_oracle(n, d, k, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    oracle = topk_from_dense(pairwise_similarity(x), k)
    _assert_edges(topk_similarity(x, k, block_rows=16, block_cols=24),
                  oracle)
    _assert_edges(topk_similarity_twostage(x, k, block_rows=32, chunk=16,
                                           round_chunks=3, max_rounds=2,
                                           residual_chunks=4), oracle)
    _assert_edges(topk_similarity_fused(x, k, block_rows=16,
                                        block_cols=32), oracle)


@pytest.mark.parametrize("br,bc", [(16, 24), (97, 97), (8, 8), (32, 130),
                                   (97, 13)])
def test_tiebreak_identical_under_duplicates(br, bc):
    """Duplicated points produce exactly-equal similarities; every build
    path must resolve them to the same (value desc, col asc) edge set at
    any tile shape."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 3, (97, 2)).astype(np.float32)
    x[40:60] = x[0:20]                     # exact duplicate points
    x = jnp.asarray(x)
    for k in (5, 16, 60):
        oracle = topk_from_dense(pairwise_similarity(x), k)
        _assert_same(topk_similarity(x, k, block_rows=br, block_cols=bc),
                     oracle)
        _assert_same(topk_similarity_twostage(
            x, k, block_rows=br, chunk=8, round_chunks=2, max_rounds=2,
            residual_chunks=3), oracle)
        _assert_same(topk_similarity_fused(x, k, block_rows=br,
                                           block_cols=max(bc, k + 1)),
                     oracle)


@pytest.mark.parametrize("metric", ["neg_euclidean", "cosine"])
def test_twostage_other_metrics(metric):
    """The two-stage gate runs in (normalized) squared-distance space but
    the survivor values use the metric's own formula — the same edges as
    the reference scan, values within the numeric contract."""
    x = jnp.asarray(np.random.default_rng(7)
                    .standard_normal((150, 4)).astype(np.float32))
    ref = topk_similarity(x, 12, metric=metric, block_rows=32,
                          block_cols=48)
    _assert_edges(topk_similarity_twostage(x, 12, metric=metric, chunk=16),
                  ref)


def test_select_exact_orders_ties_by_column():
    rng = np.random.default_rng(0)
    v = rng.integers(0, 4, (50, 40)).astype(np.float32)   # heavy ties
    c = np.tile(np.arange(40, dtype=np.int32), (50, 1))
    for r in range(50):
        rng.shuffle(c[r])
    sv, sc = topk_select_exact(jnp.asarray(v), jnp.asarray(c), 7)
    sv, sc = np.asarray(sv), np.asarray(sc)
    for r in range(50):
        ref = sorted(zip(-v[r], c[r]))[:7]
        got = sorted(zip(-sv[r], sc[r]))
        assert ref == got, f"row {r}: {ref} != {got}"


def test_kd_order_is_a_permutation():
    x = np.random.default_rng(1).standard_normal((501, 3)).astype(np.float32)
    perm = kd_order(x, 32)
    assert sorted(perm.tolist()) == list(range(501))


# --------------------------------------------------------- row sharding
def test_row_offset_splits_reproduce_full_build():
    x = jnp.asarray(np.random.default_rng(9)
                    .standard_normal((120, 3)).astype(np.float32))
    vr, ir = topk_similarity(x, 11)
    for build in (topk_similarity, topk_similarity_twostage):
        va, ia = build(x[:50], 11, cols=x, row_offset=0)
        vb, ib = build(x[50:], 11, cols=x, row_offset=50)
        np.testing.assert_array_equal(np.asarray(ir),
                                      np.vstack([ia, ib]))
        np.testing.assert_array_equal(np.asarray(vr),
                                      np.vstack([va, vb]))


def test_sharded_build_single_worker_bit_exact():
    """W=1 degenerate mesh: the shard_map driver must equal the local
    build exactly (the 8-worker case runs in the nightly slow tier)."""
    x = jnp.asarray(gaussian_blobs(n=300, k=4, seed=2)[0])
    ref = topk_similarity(x, 16)
    got = sharded_topk_similarity(x, 16, SolveConfig(),
                                  mesh=make_worker_mesh())
    _assert_same(got, ref)


HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "topk_build_dist_check.py")


@pytest.mark.slow
def test_sharded_build_8_workers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, HELPER], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------- knob + routing
def test_build_backends_agree_through_solve():
    x, _ = gaussian_blobs(n=300, k=4, seed=2)
    ref = solve(x, backend="dense_topk", k=24, levels=2,
                max_iterations=20, preference="median",
                build="reference")
    for b in ("twostage", "fused", "sharded", "auto"):
        res = solve(x, backend="dense_topk", k=24, levels=2,
                    max_iterations=20, preference="median", build=b)
        np.testing.assert_array_equal(res.exemplars, ref.exemplars)
        np.testing.assert_array_equal(res.n_clusters, ref.n_clusters)


def test_invalid_build_knob_rejected_at_entry():
    x = np.zeros((10, 2), np.float32)
    with pytest.raises(ValueError, match="SolveConfig.build"):
        solve(x, backend="dense_topk", build="nope")
    with pytest.raises(ValueError, match="build_block_rows"):
        solve(x, backend="dense_topk", build_block_rows=0)


def test_auto_resolution_rules():
    assert set(BUILD_BACKENDS) == {"auto", "reference", "twostage",
                                   "fused", "sharded"}
    r = lambda **kw: resolve_build_backend("auto", **kw)
    assert r(n=1000, k=32, n_devices=1, platform="cpu") == "reference"
    # below the measured clusterable crossover the gate machinery is pure
    # overhead — twostage must not be auto-picked there
    assert r(n=16384, k=32, n_devices=1, platform="cpu") == "reference"
    assert r(n=50_000, k=32, n_devices=1, platform="cpu") == "twostage"
    # no pruning headroom between k and N -> reference
    assert r(n=50_000, k=20_000, n_devices=1, platform="cpu") == "reference"
    assert r(n=50_000, k=32, n_devices=8, platform="cpu") == "sharded"
    assert r(n=50_000, k=32, n_devices=1, platform="tpu") == "fused"
    # fused is neg-sqeuclidean only: auto on TPU must fall through for
    # other metrics instead of routing to a backend that rejects them
    assert r(n=1000, k=8, metric="cosine", n_devices=1,
             platform="tpu") == "reference"
    assert r(n=50_000, k=32, metric="neg_euclidean", n_devices=1,
             platform="tpu") == "twostage"
    assert resolve_build_backend(
        "reference", n=50_000, k=32, n_devices=8,
        platform="cpu") == "reference"      # explicit beats auto


def test_twostage_rejects_oversized_n_for_exact_keys():
    class FakeShape:
        shape = (1 << 25, 2)
    with pytest.raises(ValueError, match="N <= "):
        topk_similarity_twostage(FakeShape(), 4)
