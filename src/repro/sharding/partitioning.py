"""Partitioning helpers — mesh-spec projection and data partitioning.

Mesh side: logical specs are written against the *largest* mesh
(("pod", "data", "model")); ``filter_spec`` projects them onto whatever
mesh is actually in context (single-pod meshes have no "pod" axis; smoke
tests run mesh-less and all constraints become no-ops).

Data side: ``kd_median_cut``/``kd_cells`` is the recursive median-cut
point partitioner shared by the two-stage top-k build (which uses the
*ordering* — consecutive runs form tight cells for its pruning gate) and
the ``coarsen`` solver backend (which uses the *cells* themselves as the
local-solve partitions). Host-side numpy on purpose: partitioning is
correctness-neutral for both consumers — only pruning power / partition
locality depend on it — and median cuts beat anything expressible
cheaply in-graph.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------ kd point partition
def kd_median_cut(x: np.ndarray, leaf: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Recursive median-cut partition of ``(N, d)`` points.

    Splits the widest axis-aligned dimension at its median until every
    cell holds at most ``leaf`` points. Returns ``(perm, splits)``:
    ``perm (N,)`` is the cut ordering (consecutive runs are tight cells —
    what the two-stage build's pruning gate consumes) and ``splits
    (C+1,)`` are the cell boundaries, so cell ``c`` is
    ``perm[splits[c]:splits[c+1]]``. Cells are contiguous, disjoint,
    cover every point, and (for ``N > leaf``) hold at least
    ``leaf // 2`` points each — the median split always halves.
    """
    x = np.asarray(x, np.float32)
    if x.ndim != 2:
        raise ValueError(f"kd_median_cut needs (N, d) points; got {x.shape}")
    if leaf < 1:
        raise ValueError(f"leaf must be >= 1; got {leaf}")
    n = x.shape[0]
    perm = np.arange(n, dtype=np.int64)
    # LIFO with the left half pushed last -> leaves are visited (and cell
    # boundaries recorded) in left-to-right perm order
    stack = [(0, n)]
    bounds: list[int] = []
    while stack:
        lo, hi = stack.pop()
        if hi - lo <= leaf:
            bounds.append(lo)
            continue
        pts = x[perm[lo:hi]]
        dim = int(np.argmax(pts.max(axis=0) - pts.min(axis=0)))
        mid = (hi - lo) // 2
        part = np.argpartition(pts[:, dim], mid)
        perm[lo:hi] = perm[lo:hi][part]
        stack.append((lo + mid, hi))
        stack.append((lo, lo + mid))
    splits = np.asarray(bounds + [n], dtype=np.int64)
    return perm.astype(np.int32), splits


def kd_cells(x: np.ndarray, leaf: int) -> list[np.ndarray]:
    """Median-cut cells as index arrays, each sorted ascending.

    The ``coarsen`` backend's partitions: every cell holds at most
    ``leaf`` spatially-tight points; sorting within a cell makes the
    downstream local solves independent of the cut's internal point
    order (and the single-cell case exactly the identity ordering)."""
    perm, splits = kd_median_cut(x, leaf)
    return [np.sort(perm[splits[c]:splits[c + 1]])
            for c in range(len(splits) - 1)]


def filter_spec(spec: P, axis_names) -> P:
    """Drop mesh axes that do not exist on the current mesh."""
    names = set(axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        kept = tuple(a for a in entry if a in names)
        return kept or None

    return P(*(keep(e) for e in spec))


def maybe_shard(x, spec: P):
    """with_sharding_constraint iff a mesh is in context (set_mesh /
    ``with mesh:``). Shape-safe: axes the array cannot divide are dropped
    per dim."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = filter_spec(spec, mesh.axis_names)
    spec = _divisible_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    """PartitionSpec tree -> NamedSharding tree, axis-filtered for ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, filter_spec(s, mesh.axis_names)),
        spec_tree, is_leaf=lambda s: isinstance(s, P))


def _divisible_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the array cannot divide (e.g. batch=1 on a
    32-way data axis, 8 KV heads on a 16-way model axis): per dim, keep the
    longest prefix of axes whose product divides the dim."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        kept, prod = [], 1
        for a in axes:
            ext = mesh.shape[a]
            if shape[i] % (prod * ext) == 0:
                kept.append(a)
                prod *= ext
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    return P(*out)


def device_put_row_sharded(x, mesh: Mesh, axis_name: str, *, axis: int = 0):
    """Place ``x`` with one contiguous row block per device along ``axis``
    (all other dims replicated) — the input layout every row-sharded
    ``shard_map`` program expects. Placing before the jit call keeps the
    dispatch from first replicating the full array onto every device."""
    spec = [None] * x.ndim
    spec[axis] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def shape_safe_shardings(mesh: Mesh, sds_tree: Any, spec_tree: Any) -> Any:
    """NamedShardings whose specs are both axis-filtered and
    shape-divisibility-safe for the given ShapeDtypeStruct tree."""
    def one(sds, s):
        spec = filter_spec(s, mesh.axis_names)
        spec = _divisible_spec(spec, sds.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree.map(one, sds_tree, spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
