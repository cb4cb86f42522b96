"""Pallas fused similarity + top-k-select build kernel.

One ``pallas_call`` over a (row tiles, col tiles) grid computes each
(block_rows, block_cols) negative-squared-Euclidean tile *and* folds it
into that row block's running per-row top-k in the same kernel body: the
similarity tile lives only in VMEM and never round-trips through HBM —
the output the grid writes is the (rows, k) edge list itself. The output
block index map ignores the column grid axis, so the accumulator stays
resident in VMEM across the whole column sweep (the same revisiting
pattern as a flash-attention accumulator).

The in-kernel merge is a k-step extract-max over the (carry ++ tile)
candidate buffer with an explicit smallest-column argmin at each step, so
ties select exactly like every other build path: (value desc, col asc).
Each step is a masked row reduction — pure VPU work on a VMEM-resident
buffer, no sort network needed.

On CPU the kernel runs in interpret mode (``interpret=None`` derives the
mode from the backend, the repo's usual convention) — a correctness
harness, not a fast path; the jnp two-stage build owns CPU throughput.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.similarity import MATMUL_PRECISION
from repro.kernels import default_interpret

NEG_INF = float("-inf")
_COL_SENTINEL = 2 ** 30  # > any real column id; python int so the kernel
                         # closes over a literal, not a captured array


def _build_kernel(xr_ref, xc_ref, vals_ref, idx_ref, *, k, n, br, bc,
                  interpret):
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = xr_ref[...].astype(jnp.float32)                  # (br, d)
    y = xc_ref[...].astype(jnp.float32)                  # (bc, d)
    xx = jnp.sum(x * x, axis=1, keepdims=True)           # (br, 1)
    yy = jnp.sum(y * y, axis=1, keepdims=True).T         # (1, bc)
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)              # (br, bc) MXU
    s = -jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    if interpret:
        # bit-parity with the jnp reference build: stop XLA from fusing
        # the similarity formula separately into each consumer below
        # (reduce vs output write), which rounds the copies differently
        s = jax.lax.optimization_barrier(s)

    rows = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    dead = (cols == rows) | (cols >= n) | (rows >= n)
    s = jnp.where(dead, NEG_INF, s)

    # first column tile initializes the accumulator in place of whatever
    # the untouched output block holds
    first = j == 0
    prev_v = jnp.where(first, NEG_INF, vals_ref[...])
    prev_i = jnp.where(first, 0, idx_ref[...])
    cand_v = jnp.concatenate([prev_v, s], axis=1)        # (br, k + bc)
    cand_c = jnp.concatenate(
        [prev_i, jnp.broadcast_to(cols, (br, bc))], axis=1)

    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def step(t, st):
        cv, out_v, out_i = st
        m = jnp.max(cv, axis=1, keepdims=True)           # (br, 1)
        at_m = cv == m
        cm = jnp.min(jnp.where(at_m, cand_c, _COL_SENTINEL),
                     axis=1, keepdims=True)              # smallest col tie
        hit = slot == t
        out_v = jnp.where(hit, m, out_v)
        out_i = jnp.where(hit, cm, out_i)
        cv = jnp.where(at_m & (cand_c == cm), NEG_INF, cv)
        return cv, out_v, out_i

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, step,
        (cand_v, jnp.full((br, k), NEG_INF, jnp.float32),
         jnp.zeros((br, k), jnp.int32)))
    vals_ref[...] = out_v
    idx_ref[...] = out_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_rows", "block_cols", "interpret"))
def topk_similarity_fused(
    x: jnp.ndarray,
    k: int,
    *,
    block_rows: int = 256,
    block_cols: int = 1024,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, d) points -> (vals (N, k), idx (N, k)), neg-sqeuclidean only.

    Same output contract as ``repro.kernels.topk_similarity`` (ascending
    column layout, (value desc, col asc) tie-break) — the parity suite
    holds them to the same edges, values within the numeric contract. Block sizes default small enough that the
    (br, k + bc) candidate buffers sit comfortably in VMEM.
    """
    interpret = default_interpret(interpret)
    n, d = x.shape
    if not 1 <= k <= n - 1:
        raise ValueError(f"k must be in [1, N-1] = [1, {n - 1}]; got {k}")
    br = min(block_rows, n)
    bc = min(block_cols, n)
    # lane alignment only matters for the native TPU lowering; in
    # interpret mode the unpadded dot keeps the same rounding as the
    # jnp reference builds (bit-parity)
    pr, pc, pd = (-n) % br, (-n) % bc, 0 if interpret else (-d) % 128
    xr = jnp.pad(x.astype(jnp.float32), ((0, pr), (0, pd)))
    xc = jnp.pad(x.astype(jnp.float32), ((0, pc), (0, pd)))
    n_rt, n_ct = xr.shape[0] // br, xc.shape[0] // bc

    vals, idx = pl.pallas_call(
        functools.partial(_build_kernel, k=k, n=n, br=br, bc=bc,
                          interpret=interpret),
        grid=(n_rt, n_ct),
        in_specs=[
            pl.BlockSpec((br, xr.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, xc.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, k), lambda i, j: (i, 0)),
            pl.BlockSpec((br, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rt * br, k), jnp.float32),
            jax.ShapeDtypeStruct((n_rt * br, k), jnp.int32),
        ],
        interpret=interpret,
    )(xr, xc)
    vals, idx = vals[:n], idx[:n]
    order = jnp.argsort(idx, axis=1)
    return (jnp.take_along_axis(vals, order, axis=1),
            jnp.take_along_axis(idx, order, axis=1))
