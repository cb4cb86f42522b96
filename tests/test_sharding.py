import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding.partitioning import (
    _divisible_spec, filter_spec, maybe_shard, shape_safe_shardings,
)


def _mesh():
    return make_mesh((1,), ("data",))


def test_filter_spec_drops_missing_axes():
    s = P(("pod", "data"), "model", None)
    out = filter_spec(s, ("data", "model"))
    assert out == P("data", "model", None)
    out2 = filter_spec(s, ("model",))
    assert out2 == P(None, "model", None)


def test_divisible_spec_drops_indivisible():
    mesh = jax.sharding.AbstractMesh((2,), ("data",))
    assert _divisible_spec(P("data"), (3,), mesh) == P(None)
    assert _divisible_spec(P("data"), (4,), mesh) == P("data")


def test_divisible_spec_tuple_prefix():
    mesh = jax.sharding.AbstractMesh((2, 2), ("a", "b"))
    # dim 2: only the first axis of ("a","b") fits
    assert _divisible_spec(P(("a", "b")), (2,), mesh) == P("a")
    assert _divisible_spec(P(("a", "b")), (4,), mesh) == P(("a", "b"))


def test_shape_safe_shardings_tree():
    mesh = _mesh()
    sds = {"x": jax.ShapeDtypeStruct((4, 4), jnp.float32),
           "y": jax.ShapeDtypeStruct((3,), jnp.float32)}
    specs = {"x": P("data", None), "y": P("data")}
    out = shape_safe_shardings(mesh, sds, specs)
    assert out["x"].spec == P("data", None)


def test_maybe_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = maybe_shard(x, P("data", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- kd median-cut partition
def test_kd_median_cut_perm_and_splits_cover_everything():
    from repro.sharding.partitioning import kd_median_cut
    rng = np.random.default_rng(0)
    x = rng.normal(size=(517, 3)).astype(np.float32)
    perm, splits = kd_median_cut(x, 64)
    assert sorted(perm.tolist()) == list(range(517))
    assert splits[0] == 0 and splits[-1] == 517
    sizes = np.diff(splits)
    assert np.all(sizes >= 1) and np.all(sizes <= 64)
    # median splits halve: no cell smaller than leaf // 2
    assert np.all(sizes >= 32)


def test_kd_cells_are_sorted_disjoint_and_tight():
    from repro.sharding.partitioning import kd_cells
    rng = np.random.default_rng(1)
    # two well-separated clumps: no cell may straddle them once
    # leaf < clump size
    a = rng.normal(0.0, 0.5, size=(128, 2))
    b = rng.normal(100.0, 0.5, size=(128, 2))
    x = np.concatenate([a, b]).astype(np.float32)
    cells = kd_cells(x, 64)
    seen = np.concatenate(cells)
    assert sorted(seen.tolist()) == list(range(256))
    for c in cells:
        assert np.all(np.diff(c) > 0)          # sorted, duplicate-free
        assert len(c) <= 64
        sides = set((c < 128).tolist())
        assert len(sides) == 1                 # never straddles the gap


def test_kd_single_cell_is_identity_ordering():
    from repro.sharding.partitioning import kd_cells
    x = np.random.default_rng(2).normal(size=(40, 4)).astype(np.float32)
    (cell,) = kd_cells(x, 64)
    np.testing.assert_array_equal(cell, np.arange(40))


def test_kd_median_cut_validates_input():
    from repro.sharding.partitioning import kd_median_cut
    with pytest.raises(ValueError, match=r"\(N, d\)"):
        kd_median_cut(np.zeros((4,), np.float32), 2)
    with pytest.raises(ValueError, match="leaf"):
        kd_median_cut(np.zeros((4, 2), np.float32), 0)


def test_kd_order_delegates_to_partitioner():
    """The twostage build's historical entry point and the factored
    utility must stay the same permutation (the build's pruning quality
    and coarsen's partitions are the same cells)."""
    from repro.kernels.topk_similarity import kd_order
    from repro.sharding.partitioning import kd_median_cut
    x = np.random.default_rng(3).normal(size=(300, 5)).astype(np.float32)
    np.testing.assert_array_equal(kd_order(x, 32),
                                  kd_median_cut(x, 32)[0])
