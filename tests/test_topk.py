"""dense_topk backend: build correctness, parity, quality, early stop.

Contracts (docs/solver.md):

* the tiled top-k build selects the true row-wise top-k (dense argsort
  reference), never materializing the N x N matrix;
* at k = N - 1 (full coverage) the sparse sweep reproduces
  ``dense_parallel`` assignments exactly — missing-edge-as-(-inf)
  semantics make the compressed updates the dense updates restricted to
  stored positions, and at full coverage nothing is restricted;
* at k = 32 purity stays within 2 points of dense on the synthetic
  suites (the Xia et al. sparsification result);
* convergence-driven early stopping works on the compressed layout
  (same ``drive_sweeps`` loop as the dense family).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    pairwise_similarity, purity, set_preferences, stack_levels,
)
from repro.core.preferences import median_preference
from repro.data import aggregation_like, gaussian_blobs, two_moons
from repro.kernels.topk_similarity import topk_from_dense, topk_similarity
from repro.solver import SolveConfig, auto_select, list_backends, solve


@pytest.fixture(scope="module")
def fixture96():
    x, y = gaussian_blobs(n=96, k=4, seed=6, spread=0.4)
    return x, y


@pytest.fixture(scope="module")
def dense_ref96(fixture96):
    x, _ = fixture96
    return solve(x, backend="dense_parallel", levels=3, max_iterations=30,
                 damping=0.6, preference="median")


# ------------------------------------------------------------------- build
@pytest.mark.parametrize("n,k,d,seed", [
    (17, 1, 2, 0), (50, 7, 3, 1), (96, 32, 2, 2), (64, 63, 5, 3),
    (130, 40, 4, 4),
])
def test_tiled_build_selects_true_topk(n, k, d, seed):
    """Property: for every row, the tiled pass returns exactly the k
    largest off-diagonal similarities (dense argsort reference), with
    indices ascending. Small odd tile sizes force the padded/multi-tile
    merge paths.

    Coordinates sit on a 1/8 grid, so every product and sum in a
    similarity is exact in f32: a tile's dot and the full matrix's dot
    then agree on any host, whatever order the GEMM sums in (on some
    hosts XLA's CPU GEMM rounds a 16x24 tile and the 64x64 matrix
    apart), and the grid's many exact ties exercise the merge's tie
    handling."""
    rng = np.random.default_rng(seed)
    x = (np.round(rng.standard_normal((n, d)) * 8) / 8).astype(np.float32)
    vals, idx = topk_similarity(jnp.asarray(x), k,
                                block_rows=16, block_cols=24)
    vals, idx = np.asarray(vals), np.asarray(idx)
    s = np.array(pairwise_similarity(jnp.asarray(x)))   # writable copy
    np.fill_diagonal(s, -np.inf)
    ref_vals = -np.sort(-s, axis=1)[:, :k]
    np.testing.assert_array_equal(-np.sort(-vals, axis=1), ref_vals)
    assert np.all(np.diff(idx, axis=1) > 0)          # ascending, no dupes
    assert np.all(idx != np.arange(n)[:, None])      # self never stored
    # indices actually point at their values
    np.testing.assert_array_equal(
        np.take_along_axis(s, idx, axis=1), vals)


def test_build_matches_dense_compression(fixture96):
    """The streaming build and the compress-a-dense-matrix path agree."""
    x, _ = fixture96
    s = pairwise_similarity(jnp.asarray(x))
    v1, i1 = topk_similarity(jnp.asarray(x), 13)
    v2, i2 = topk_from_dense(s, 13)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_build_rejects_bad_k():
    x = jnp.zeros((10, 2))
    with pytest.raises(ValueError, match="k must be"):
        topk_similarity(x, 0)
    with pytest.raises(ValueError, match="k must be"):
        topk_similarity(x, 10)


# ------------------------------------------------------------------ parity
def test_full_coverage_bit_matches_dense_parallel(fixture96, dense_ref96):
    """k = N - 1 stores every off-diagonal entry: assignments (and the
    whole per-sweep trace) must match dense_parallel exactly — points
    input, median preference computed from the compressed values."""
    x, _ = fixture96
    res = solve(x, backend="dense_topk", k=95, levels=3, max_iterations=30,
                damping=0.6, preference="median")
    assert res.backend == "dense_topk"
    np.testing.assert_array_equal(res.exemplars, dense_ref96.exemplars)
    np.testing.assert_array_equal(res.n_clusters, dense_ref96.n_clusters)
    np.testing.assert_array_equal(res.trace, dense_ref96.trace)


def test_full_coverage_parity_similarity_input(fixture96):
    """Same contract through the (L, N, N) stack input path (row-wise
    compression of a caller-built matrix, diagonal = preferences)."""
    x, _ = fixture96
    s = pairwise_similarity(jnp.asarray(x))
    s3 = stack_levels(set_preferences(s, median_preference(s)), 3)
    ref = solve(s3, backend="dense_parallel", max_iterations=30, damping=0.6)
    res = solve(s3, backend="dense_topk", k=95, max_iterations=30,
                damping=0.6)
    np.testing.assert_array_equal(res.exemplars, ref.exemplars)
    np.testing.assert_array_equal(res.n_clusters, ref.n_clusters)


@pytest.mark.parametrize("mode", ["evidence", "paper"])
def test_full_coverage_parity_with_similarity_refinement(fixture96, mode):
    """Eq 2.7 similarity refinement (both printed and prose readings)
    stays bit-exact on the compressed layout at full coverage."""
    x, _ = fixture96
    ref = solve(x, backend="dense_parallel", levels=3, max_iterations=25,
                damping=0.6, preference="median", s_mode=mode, kappa=0.05)
    res = solve(x, backend="dense_topk", k=95, levels=3, max_iterations=25,
                damping=0.6, preference="median", s_mode=mode, kappa=0.05)
    np.testing.assert_array_equal(res.exemplars, ref.exemplars)


def test_k_validation_rejects_out_of_range(fixture96):
    """solve() validates k at the front door: k < 1 and k >= N are
    errors with the problem size in the message; k = N - 1 (the lossless
    maximum) still runs."""
    x, _ = fixture96
    with pytest.raises(ValueError, match="k must be >= 1"):
        solve(x, backend="dense_topk", k=0)
    with pytest.raises(ValueError, match="k must be < N"):
        solve(x, backend="dense_topk", k=96)
    with pytest.raises(ValueError, match="k must be < N"):
        solve(x, backend="dense_topk", k=10_000)


def test_sampled_preference_deterministic_under_seed():
    """The N > 4096 string-preference dense subsample is seeded from
    SolveConfig.seed: two identical runs agree bit-for-bit."""
    from repro.solver.topk import build_from_points

    x, _ = gaussian_blobs(n=4200, k=6, seed=9, spread=0.5)
    import jax

    key = jax.random.PRNGKey(7)
    _, idx_a = build_from_points(jnp.asarray(x), 16, 1, key=key)
    s_a, _ = build_from_points(jnp.asarray(x), 16, 1, key=key)
    s_b, idx_b = build_from_points(jnp.asarray(x), 16, 1, key=key)
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
    np.testing.assert_array_equal(np.asarray(idx_a), np.asarray(idx_b))
    # the self slot carries the sampled preference: identical across runs
    res1 = solve(x, backend="dense_topk", k=16, levels=1,
                 max_iterations=4, seed=3, preference="median")
    res2 = solve(x, backend="dense_topk", k=16, levels=1,
                 max_iterations=4, seed=3, preference="median")
    np.testing.assert_array_equal(res1.exemplars, res2.exemplars)


# ----------------------------------------------------------------- quality
@pytest.mark.parametrize("dataset", ["aggregation", "blobs", "moons"])
def test_k32_purity_within_2pct_of_dense(dataset):
    """The sparsification contract: k = 32 holds level-0 purity within 2
    points of the dense run on each synthetic suite."""
    x, y = {
        "aggregation": lambda: aggregation_like(),
        "blobs": lambda: gaussian_blobs(n=600, k=6, seed=2, spread=0.5),
        "moons": lambda: two_moons(n=400, seed=3),
    }[dataset]()
    dense = solve(x, backend="dense_parallel", levels=3, max_iterations=40,
                  damping=0.7, preference="median")
    sparse = solve(x, backend="dense_topk", k=32, levels=3,
                   max_iterations=40, damping=0.7, preference="median")
    p_dense = purity(dense.labels[0], y)
    p_sparse = purity(sparse.labels[0], y)
    assert p_sparse >= p_dense - 0.02, (
        f"{dataset}: topk purity {p_sparse:.3f} vs dense {p_dense:.3f}")


# -------------------------------------------------------------- early stop
def test_topk_converged_stops_before_budget(fixture96):
    x, _ = fixture96
    res = solve(x, backend="dense_topk", k=32, levels=3, stop="converged",
                max_iterations=300, patience=10, damping=0.6,
                preference="median")
    assert res.converged is True
    assert res.n_sweeps < 300
    assert res.trace.shape == (res.n_sweeps,)
    assert np.all(res.trace[-10:] == 0)
    # fixed-budget run over the same data agrees on the final assignment
    ref = solve(x, backend="dense_topk", k=32, levels=3,
                max_iterations=res.n_sweeps, damping=0.6,
                preference="median")
    np.testing.assert_array_equal(res.exemplars, ref.exemplars)


def test_topk_respects_budget(fixture96):
    x, _ = fixture96
    res = solve(x, backend="dense_topk", k=16, levels=2, stop="converged",
                max_iterations=4, patience=100, preference="median")
    assert res.converged is False and res.n_sweeps == 4


# ---------------------------------------------------------------- registry
def test_registered_and_auto_selected_for_big_n_points():
    assert "dense_topk" in list_backends()
    # big-N multi-level points (or early stopping) route to the sparse
    # backend; the single-level fixed-budget case keeps streaming
    cfg = SolveConfig()
    assert auto_select(20_000, 3, n_devices=1, has_points=True,
                       platform="cpu", cfg=cfg) == "dense_topk"
    assert auto_select(20_000, 1, n_devices=1, has_points=True,
                       platform="cpu", cfg=cfg) == "sharded_streaming"
    early = SolveConfig(stop="converged")
    assert auto_select(20_000, 1, n_devices=1, has_points=True,
                       platform="cpu", cfg=early) == "dense_topk"
    # small problems keep the dense family
    assert auto_select(96, 3, n_devices=1, has_points=True,
                       platform="cpu", cfg=cfg) == "dense_parallel"


def test_keep_state_carries_compressed_layout(fixture96):
    x, _ = fixture96
    res = solve(x, backend="dense_topk", k=8, levels=2, max_iterations=5,
                keep_state=True, preference="median")
    assert res.state is not None
    assert res.state.hap.r.shape == (2, 96, 9)       # (L, N, k+1)
    assert res.state.idx.shape == (96, 9)
    np.testing.assert_array_equal(np.asarray(res.state.idx[:, 0]),
                                  np.arange(96))
