"""Pallas TPU kernel for the fused, damped responsibility update (Eq 2.1).

    r_new(i, j) = lam * r_old(i, j)
                + (1 - lam) * (s(i, j) + min(tau_i, -max_{k != j}(a(i,k)+s(i,k))))

Two-pass tiling (DESIGN §2: the row reduction is decomposable):

  pass 1 (``row_top2``)  — grid (nr, nc), innermost over column tiles,
      accumulates per-row (max, argmax, second-max) of v = a + s into
      (N, 1) VMEM-resident stats; the revisit pattern keeps the stat block
      pinned while the column tiles stream through VMEM.
  pass 2 (``emit``)      — grid (nr, nc), elementwise: selects max or
      runner-up per column, applies the tau clamp and damping in one fused
      pass so r_old/s/a are each read exactly once from HBM.

Block shapes default to (256, 256) f32 = 256 KiB per operand tile — four
streamed operands + stats fit comfortably in 16 MiB VMEM per core and keep
the lane dimension a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG_INF = float("-inf")


def _top2_kernel(v_ref, m1_ref, i1_ref, m2_ref, *, block_j: int):
    jc = pl.program_id(1)
    tile = v_ref[...].astype(jnp.float32)                   # (bi, bj)
    bi, bj = tile.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    t1 = jnp.max(tile, axis=1, keepdims=True)               # (bi, 1)
    targ = jnp.argmax(tile, axis=1).astype(jnp.int32)[:, None]
    masked = jnp.where(cols == targ, NEG_INF, tile)
    t2 = jnp.max(masked, axis=1, keepdims=True)
    targ = targ + jc * block_j                               # global col index

    @pl.when(jc == 0)
    def _init():
        m1_ref[...] = t1
        i1_ref[...] = targ
        m2_ref[...] = t2

    @pl.when(jc > 0)
    def _merge():
        m1, i1, m2 = m1_ref[...], i1_ref[...], m2_ref[...]
        take = t1 > m1  # strict: ties keep the earlier (first-occurrence) idx
        m1_ref[...] = jnp.where(take, t1, m1)
        i1_ref[...] = jnp.where(take, targ, i1)
        m2_ref[...] = jnp.where(take, jnp.maximum(m1, t2), jnp.maximum(m2, t1))


def _emit_kernel(s_ref, r_old_ref, tau_ref, m1_ref, i1_ref, m2_ref, out_ref,
                 *, block_j: int, lam: float):
    jc = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32)
    bi, bj = s.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1) + jc * block_j
    row_max = jnp.where(cols == i1_ref[...], m2_ref[...], m1_ref[...])
    new = s + jnp.minimum(tau_ref[...].astype(jnp.float32), -row_max)
    out = lam * r_old_ref[...].astype(jnp.float32) + (1.0 - lam) * new
    out_ref[...] = out.astype(out_ref.dtype)


def responsibility_pallas(
    s: jnp.ndarray, a: jnp.ndarray, tau: jnp.ndarray, r_old: jnp.ndarray,
    lam: float,
    *, block_i: int = 256, block_j: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Shapes: s, a, r_old (N, M); tau (N,). Returns damped rho (N, M).

    N, M need not be tile-aligned — inputs are padded with neutral values
    (-inf similarities never win the max; padded rows get tau = 0).
    """
    interpret = default_interpret(interpret)
    n, m = s.shape
    bi, bj = min(block_i, n), min(block_j, m)
    pn, pm = (-n) % bi, (-m) % bj
    if pn or pm:
        s = jnp.pad(s, ((0, pn), (0, pm)), constant_values=NEG_INF)
        a = jnp.pad(a, ((0, pn), (0, pm)))
        r_old = jnp.pad(r_old, ((0, pn), (0, pm)))
        tau = jnp.pad(tau, (0, pn))
    npad, mpad = s.shape
    grid = (npad // bi, mpad // bj)

    v = (a.astype(jnp.float32) + s.astype(jnp.float32))
    stats_spec = pl.BlockSpec((bi, 1), lambda i, j: (i, 0))
    m1, i1, m2 = pl.pallas_call(
        functools.partial(_top2_kernel, block_j=bj),
        grid=grid,
        in_specs=[pl.BlockSpec((bi, bj), lambda i, j: (i, j))],
        out_specs=[stats_spec, stats_spec, stats_spec],
        out_shape=[
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(v)

    tile = pl.BlockSpec((bi, bj), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_emit_kernel, block_j=bj, lam=lam),
        grid=grid,
        in_specs=[tile, tile, pl.BlockSpec((bi, 1), lambda i, j: (i, 0)),
                  stats_spec, stats_spec, stats_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((npad, mpad), s.dtype),
        interpret=interpret,
    )(s, r_old, tau[:, None], m1, i1, m2)
    return out[:n, :m]
