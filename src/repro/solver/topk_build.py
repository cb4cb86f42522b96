"""Top-k similarity build driver: backend selection + the sharded build.

The ``dense_topk`` backend's build phase was the N = 2e5 wall (the tiled
scan is O(N^2) with a full re-sort per tile; sweeps finish in seconds).
This module is its front door now:

* ``build_topk_similarity`` resolves ``SolveConfig.build`` — ``auto``
  picks the sharded driver on a multi-device host, the Pallas fused
  kernel on TPU, the threshold-gated two-stage merge for big clusterable
  single-device builds, and the reference scan for everything small — and
  returns the standard ``(vals (N, k), idx (N, k))`` layout.
* ``sharded_topk_similarity`` ``shard_map``s row blocks over a 1-D
  ``workers`` mesh: each device runs a full local build for the rows it
  owns against the (replicated) column set, so each device holds its
  rows' (n_shard, k) edge lists end-to-end — the first concrete step
  toward the ROADMAP's distributed (N, k+1) layout, and near-linear in
  worker count because the build is embarrassingly row-parallel.

Every path produces the identical edge set (value desc, col asc
tie-break; ``tests/test_topk_build.py`` holds them to it, values within
the numeric contract), so the backend knob is purely a throughput choice.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.topk_similarity import (
    SELECT_EXACT_MAX_N, kd_order, topk_similarity, topk_similarity_twostage,
)
from repro.solver.config import SolveConfig

#: every registered build backend; "auto" resolves to one of the rest
BUILD_BACKENDS = ("auto", "reference", "twostage", "fused", "sharded")

#: N below which the reference scan is already fast enough that the
#: two-stage machinery (kd ordering, chunk bounds) is pure overhead.
#: Measured crossover (CPU, k = 64): even on well-clustered data — the
#: cell-pruning gate's best case — twostage loses up to 16384 and first
#: wins (~1.5x) at 32768; unclusterable data never recovers the gate
#: cost, which auto-select cannot see, so the threshold sits at the
#: clusterable crossover rather than below it.
TWOSTAGE_N = 32768

#: N at which a multi-device host switches to the sharded driver.
SHARDED_N = 8192


def resolve_build_backend(name: str, *, n: int, k: int,
                          metric: str = "neg_sqeuclidean",
                          n_devices: Optional[int] = None,
                          platform: Optional[str] = None) -> str:
    """``cfg.build`` -> a concrete backend for this problem/host."""
    if name not in BUILD_BACKENDS:
        raise ValueError(
            f"unknown build backend {name!r}; known: {BUILD_BACKENDS}")
    if name != "auto":
        return name
    n_devices = len(jax.devices()) if n_devices is None else n_devices
    platform = jax.default_backend() if platform is None else platform
    if n_devices > 1 and n >= SHARDED_N:
        return "sharded"
    # the fused kernel is neg-sqeuclidean only; auto must never route a
    # metric it would reject
    if platform == "tpu" and metric == "neg_sqeuclidean":
        return "fused"
    # the two-stage gate needs headroom between k and N to prune, and its
    # exact tie-break keys cap N; otherwise the reference scan is optimal
    if TWOSTAGE_N <= n <= SELECT_EXACT_MAX_N and 4 * k <= n:
        return "twostage"
    return "reference"


def _local_build(x, k, cfg: SolveConfig, backend: str, *,
                 cols=None, row_offset=0, perm=None):
    if backend == "twostage":
        return topk_similarity_twostage(
            x, k, metric=cfg.metric, block_rows=cfg.build_block_rows,
            chunk=cfg.build_chunk, cols=cols, row_offset=row_offset,
            perm=perm)
    if backend == "fused":
        if cfg.metric != "neg_sqeuclidean":
            raise ValueError(
                "build='fused' supports metric='neg_sqeuclidean' only; "
                f"got {cfg.metric!r} (use 'twostage' or 'reference')")
        if cols is not None:
            raise ValueError("build='fused' is single-device; the sharded "
                             "driver runs jnp builds per worker")
        from repro.kernels.topk_build_fused import topk_similarity_fused
        try:
            from repro.runtime import faultinject
            faultinject.fire("build.fused", n=int(x.shape[0]), k=k)
            return topk_similarity_fused(
                x, k, block_rows=min(cfg.build_block_rows, 256),
                block_cols=min(cfg.build_block_cols, 1024))
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            # a platform that rejects the Pallas build falls back to the
            # reference scan — the same edge set, just slower
            from repro.runtime import degrade
            degrade.record("build.fused", "reference", exc)
            return topk_similarity(
                x, k, metric=cfg.metric, block_rows=cfg.build_block_rows,
                block_cols=cfg.build_block_cols, use_pallas=False,
                cols=cols, row_offset=row_offset)
    return topk_similarity(
        x, k, metric=cfg.metric, block_rows=cfg.build_block_rows,
        block_cols=cfg.build_block_cols,
        use_pallas=(jax.default_backend() == "tpu"
                    and cfg.metric == "neg_sqeuclidean"),
        cols=cols, row_offset=row_offset)


def sharded_topk_similarity(
    x: jnp.ndarray,
    k: int,
    cfg: SolveConfig,
    *,
    mesh=None,
    inner: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row-sharded top-k build over a 1-D ``workers`` mesh.

    Rows are padded to a worker multiple and partitioned; the column set
    (and, for a two-stage inner build, the host-computed kd permutation)
    is replicated, so each worker's output block is exactly its rows'
    edge lists: the single-device builds' edges.

    On a one-device mesh this degenerates to the inner build plus pure
    overhead (shard_map dispatch, the replicated column copy — measured
    6x slower at N = 2048), so it short-circuits straight to the inner
    build there; the output is the same either way.
    """
    if mesh is None:
        from repro.solver.engine import _prepare_mesh
        mesh, _ = _prepare_mesh("1d", cfg)
    w = mesh.shape["workers"]
    n = int(x.shape[0])
    inner = resolve_build_backend(
        "auto" if inner in ("auto", "sharded") else inner,
        n=n, k=k, metric=cfg.metric, n_devices=1,
        platform=jax.default_backend())
    if inner == "fused":                     # jnp builds per worker
        inner = "reference"
    if w == 1:
        return _local_build(x, k, cfg, inner)

    pad = (-n) % w
    xp = jnp.pad(jnp.asarray(x, jnp.float32), ((0, pad), (0, 0)))
    shard_rows = xp.shape[0] // w
    perm = (jnp.asarray(kd_order(np.asarray(x), cfg.build_chunk))
            if inner == "twostage" else jnp.zeros((0,), jnp.int32))

    def worker(rows_blk, full, perm_):
        off = jax.lax.axis_index("workers") * shard_rows
        return _local_build(
            rows_blk, k, cfg, inner, cols=full, row_offset=off,
            perm=perm_ if inner == "twostage" else None)

    with mesh:
        vals, idx = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P("workers", None), P(None, None), P(None)),
            out_specs=(P("workers", None), P("workers", None)))(
                xp, jnp.asarray(x, jnp.float32), perm)
    return vals[:n], idx[:n]


def build_topk_similarity(x: jnp.ndarray, k: int, cfg: SolveConfig
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The build front door ``repro.solver.topk`` calls: resolve the
    backend knob, run it, return the compressed off-diagonal layout."""
    n = int(x.shape[0])
    backend = resolve_build_backend(cfg.build, n=n, k=k, metric=cfg.metric,
                                    n_devices=cfg.device_count())
    if backend == "sharded":
        return sharded_topk_similarity(x, k, cfg)
    return _local_build(x, k, cfg, backend)
