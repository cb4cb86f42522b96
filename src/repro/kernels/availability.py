"""Pallas TPU kernel for the fused, damped availability update (Eq 2.2/2.3).

    col(j)  = sum_{k != j} max(0, r(k, j));   diag(j) = r(j, j)
    a_new(i != j) = min(0, c_j + phi_j + diag_j + col_j - max(0, r(i, j)))
    a_new(i == j) = c_j + phi_j + col_j
    out = lam * a_old + (1 - lam) * a_new

Pass 1 (``col_stats``) — grid (nc, nr), innermost over row tiles: streams
row tiles of r through VMEM accumulating the clamped column sums (diagonal
excluded) and harvesting the diagonal entries into (1, N) stats.
Pass 2 (``emit``) — grid (nr, nc), elementwise with broadcast stats; r and
a_old are read once, damping fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def _colstats_kernel(r_ref, col_ref, diag_ref, *, block_k: int, block_j: int):
    jc = pl.program_id(0)   # column-tile index (outer)
    kc = pl.program_id(1)   # row-tile index (inner, accumulated)
    r = r_ref[...].astype(jnp.float32)                     # (bk, bj)
    bk, bj = r.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, bj), 0) + kc * block_k
    cols = jax.lax.broadcasted_iota(jnp.int32, (bk, bj), 1) + jc * block_j
    eye = rows == cols
    rp = jnp.where(eye, 0.0, jnp.maximum(r, 0.0))
    part = jnp.sum(rp, axis=0, keepdims=True)              # (1, bj)
    dpart = jnp.sum(jnp.where(eye, r, 0.0), axis=0, keepdims=True)

    @pl.when(kc == 0)
    def _init():
        col_ref[...] = part
        diag_ref[...] = dpart

    @pl.when(kc > 0)
    def _acc():
        col_ref[...] += part
        diag_ref[...] += dpart


def _emit_kernel(r_ref, a_old_ref, base_ref, col_ref, diag_ref, out_ref,
                 *, block_i: int, block_j: int, lam: float):
    ic = pl.program_id(0)
    jc = pl.program_id(1)
    r = r_ref[...].astype(jnp.float32)                     # (bi, bj)
    bi, bj = r.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0) + ic * block_i
    cols = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1) + jc * block_j
    eye = rows == cols
    rp = jnp.where(eye, 0.0, jnp.maximum(r, 0.0))
    base = base_ref[...].astype(jnp.float32)               # (1, bj): c + phi
    col = col_ref[...]
    diag = diag_ref[...]
    a_off = jnp.minimum(0.0, base + diag + col - rp)
    a_diag = base + col
    new = jnp.where(eye, a_diag, a_off)
    out = lam * a_old_ref[...].astype(jnp.float32) + (1.0 - lam) * new
    out_ref[...] = out.astype(out_ref.dtype)


def availability_pallas(
    r: jnp.ndarray, c: jnp.ndarray, phi: jnp.ndarray, a_old: jnp.ndarray,
    lam: float,
    *, block_i: int = 256, block_j: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Shapes: r, a_old (N, N); c, phi (N,). Returns damped alpha (N, N).

    Padding neutral: r padded with -1 (clamped to 0 in the column sums and
    never on the diagonal of a real column).
    """
    interpret = default_interpret(interpret)
    n, m = r.shape
    bi, bj = min(block_i, n), min(block_j, m)
    pn, pm = (-n) % bi, (-m) % bj
    if pn or pm:
        r = jnp.pad(r, ((0, pn), (0, pm)), constant_values=-1.0)
        a_old = jnp.pad(a_old, ((0, pn), (0, pm)))
        c = jnp.pad(c, (0, pm))
        phi = jnp.pad(phi, (0, pm))
    npad, mpad = r.shape
    nr, nc = npad // bi, mpad // bj

    stats_spec = pl.BlockSpec((1, bj), lambda j, k: (0, j))
    col, diag = pl.pallas_call(
        functools.partial(_colstats_kernel, block_k=bi, block_j=bj),
        grid=(nc, nr),
        in_specs=[pl.BlockSpec((bi, bj), lambda j, k: (k, j))],
        out_specs=[stats_spec, stats_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, mpad), jnp.float32),
            jax.ShapeDtypeStruct((1, mpad), jnp.float32),
        ],
        interpret=interpret,
    )(r)

    base = (c.astype(jnp.float32) + phi.astype(jnp.float32))[None, :]
    tile = pl.BlockSpec((bi, bj), lambda i, j: (i, j))
    bcast = pl.BlockSpec((1, bj), lambda i, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_emit_kernel, block_i=bi, block_j=bj, lam=lam),
        grid=(nr, nc),
        in_specs=[tile, tile, bcast, bcast, bcast],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((npad, mpad), r.dtype),
        interpret=interpret,
    )(r, a_old, base, col, diag)
    return out[:n, :m]
