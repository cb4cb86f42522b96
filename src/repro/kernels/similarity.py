"""Pallas TPU kernel for blockwise negative squared-Euclidean similarity.

    s(i, j) = -(||x_i||^2 + ||y_j||^2 - 2 <x_i, y_j>)

Grid (ni, nj): each program computes a (bi, bj) output tile from a (bi, d)
row tile and a (bj, d) column tile; the inner product hits the MXU
(f32 accumulation via preferred_element_type). The feature dim is kept
whole per tile — clustering features are small (RGB=3, embeddings <= 4k);
with bi = bj = 256 and d = 4096 the operand tiles are 2 x 4 MiB, inside the
VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.similarity import MATMUL_PRECISION
from repro.kernels import default_interpret


def _sim_kernel(x_ref, y_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)                  # (bi, d)
    y = y_ref[...].astype(jnp.float32)                  # (bj, d)
    xx = jnp.sum(x * x, axis=1, keepdims=True)          # (bi, 1)
    yy = jnp.sum(y * y, axis=1, keepdims=True).T        # (1, bj)
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)             # (bi, bj) on the MXU
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    out_ref[...] = (-d2).astype(out_ref.dtype)


def similarity_pallas(
    x: jnp.ndarray, y: jnp.ndarray | None = None,
    *, block_i: int = 256, block_j: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x (N, d), y (M, d) -> (N, M) negative squared distances.

    ``interpret=None`` derives the mode from the backend (native on
    TPU, emulated elsewhere) — see ``repro.kernels.default_interpret``.
    """
    interpret = default_interpret(interpret)
    if y is None:
        y = x
    n, d = x.shape
    m = y.shape[0]
    bi, bj = min(block_i, n), min(block_j, m)
    pn, pm, pd = (-n) % bi, (-m) % bj, (-d) % 128
    if pn or pd:
        x = jnp.pad(x, ((0, pn), (0, pd)))
    if pm or pd:
        y = jnp.pad(y, ((0, pm), (0, pd)))
    npad, dpad = x.shape
    mpad = y.shape[0]

    out = pl.pallas_call(
        _sim_kernel,
        grid=(npad // bi, mpad // bj),
        in_specs=[
            pl.BlockSpec((bi, dpad), lambda i, j: (i, 0)),
            pl.BlockSpec((bj, dpad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        # inside shard_map (the sharded build's per-worker tiles) the
        # output varies over the manual axes its operands vary over
        out_shape=jax.ShapeDtypeStruct(
            (npad, mpad), x.dtype,
            vma=jax.typeof(x).vma | jax.typeof(y).vma),
        interpret=interpret,
    )(x, y)
    return out[:n, :m]
