"""Real-width compiles of the Pallas kernels for a described TPU v5e.

Nothing runs here: the TPU compiler that ships with jax compiles each
kernel for a chip that is described, not attached, and refuses what the
chip would refuse (tiling, VMEM, memory). Shapes are the ones
``chip_smoke.py`` and the sparse build use. The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and every test worker imports this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import similarity as similarity_mod
from repro.kernels.availability import availability_pallas
from repro.kernels.responsibility import responsibility_pallas
from repro.kernels.similarity import similarity_pallas
from repro.kernels.topk_build_fused import topk_similarity_fused
from repro.kernels.topk_similarity import topk_similarity

#: v5e HBM per chip; a compiled program must fit beside nothing else
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM
    return compiled


# 9216: chip_smoke phase A; 1000: unaligned (padding path)
@pytest.mark.parametrize("n", [9216, 1000])
def test_responsibility_compiles(one_chip, n):
    _compile(lambda s, a, tau, r: responsibility_pallas(
        s, a, tau, r, 0.7, interpret=False),
        one_chip, (n, n), (n, n), (n,), (n, n))


@pytest.mark.parametrize("n", [9216, 1000])
def test_availability_compiles(one_chip, n):
    _compile(lambda r, c, phi, a: availability_pallas(
        r, c, phi, a, 0.7, interpret=False),
        one_chip, (n, n), (n,), (n,), (n, n))


@pytest.mark.parametrize("n,d", [(9216, 2), (4096, 128)])
def test_similarity_compiles(one_chip, n, d):
    _compile(lambda x: similarity_pallas(x, interpret=False),
             one_chip, (n, d))


def test_topk_fused_compiles(one_chip):
    _compile(lambda x: topk_similarity_fused(x, 32, block_rows=256,
                                             block_cols=1024,
                                             interpret=False),
             one_chip, (65536, 128))


def test_topk_reference_pallas_tile_compiles(one_chip, monkeypatch):
    """The reference scan's Pallas similarity tiles — what the sharded
    build runs per worker on a TPU host."""
    monkeypatch.setattr(similarity_mod, "default_interpret",
                        lambda interpret=None: False)
    _compile(lambda x: topk_similarity(x, 32, use_pallas=True),
             one_chip, (65536, 128))


def test_topk_sweep_fits_one_chip(one_chip):
    """The sparse sweep at phase B's size: L=3, N=2^18, k=32. A level
    axis left minor by a vmapped scatter padded the state 40x (25 GiB);
    per-level scatters keep it a few GiB."""
    from repro.solver.topk import run_topk
    n, kk, levels = 1 << 18, 33, 3
    compiled = run_topk.lower(
        jax.ShapeDtypeStruct((levels, n, kk), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, kk), jnp.int32, sharding=one_chip),
        max_iterations=100, damping=0.7, stop="converged").compile()
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM // 2


def test_sharded_build_compiles_on_four_chips(topo, monkeypatch):
    """The sharded build as a TPU host runs it: per worker, the reference
    scan's Pallas similarity tiles inside shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.solver.config import SolveConfig
    from repro.solver.topk_build import sharded_topk_similarity
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(topo.devices, ("workers",))
    x = jax.ShapeDtypeStruct((1 << 18, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda x: sharded_topk_similarity(
        x, 32, SolveConfig(build="sharded"), mesh=mesh)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
