import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.standard_normal((4, 3)).astype(np.float32)),
            "nested": {"b": jnp.arange(5), "c": jnp.asarray(1.5)}}


def test_roundtrip(tmp_path):
    t = _tree()
    save_tree(str(tmp_path / "ck"), t, step=7)
    back = restore_tree(str(tmp_path / "ck"), t)
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_structure_mismatch_raises(tmp_path):
    save_tree(str(tmp_path / "ck"), _tree())
    with pytest.raises(ValueError):
        restore_tree(str(tmp_path / "ck"), {"different": jnp.zeros(3)})


def test_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for step in (1, 2, 3, 4):
        mgr.save(step, {"x": jnp.full((2,), float(step))})
    assert mgr.steps() == [3, 4]
    step, tree = mgr.restore_latest({"x": jnp.zeros((2,))})
    assert step == 4
    np.testing.assert_array_equal(np.asarray(tree["x"]), [4.0, 4.0])


def test_manager_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(10, _tree())
    mgr.wait()
    assert mgr.steps() == [10]


def test_mesh_agnostic_restore_via_elastic(tmp_path):
    """Save, then 'reshard' onto the (single-device) mesh — the elastic
    path used after losing capacity."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.elastic import reshard_state
    t = _tree()
    save_tree(str(tmp_path / "ck"), t)
    back = restore_tree(str(tmp_path / "ck"), t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    specs = {"a": P("data", None), "nested": {"b": P(None), "c": P()}}
    placed = reshard_state(back, specs, mesh)
    np.testing.assert_array_equal(np.asarray(placed["a"]),
                                  np.asarray(t["a"]))


def test_elastic_validate_warnings():
    from repro.runtime.elastic import validate_mesh_change
    w = validate_mesh_change({"data": 16, "model": 16},
                             {"data": 7, "model": 8}, global_batch=256)
    assert any("divisible" in x for x in w)
    assert any("model-parallel" in x for x in w)
