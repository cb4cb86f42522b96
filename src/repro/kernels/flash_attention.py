"""Pallas TPU flash attention (forward) — the kernel that closes the
dense-train memory gap identified in EXPERIMENTS §Perf iteration 2.

The pure-JAX blockwise attention (models/layers/attention.py) bounds PEAK
memory but still round-trips every (q_blk, kv_blk) logits tile through HBM
because XLA cannot fuse across the two einsums. This kernel keeps the tile
in VMEM: grid (batch*heads, nq, nk), with the online-softmax state (m, l)
and the output accumulator held in VMEM scratch across the innermost
kv-block loop — one HBM write of O per (bh, qi), zero logits traffic.

Supports causal masking via position offsets (the causal test uses it) and
GQA by pre-broadcasting KV outside the kernel (the wrapper handles it).
Validated against ref.flash_attention in interpret mode on CPU; on TPU the
same pallas_call compiles natively.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        cols = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)[:, None]                  # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(-inf - -inf) -> use finite fill
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    p = jnp.exp(jnp.where(s == NEG_INF, NEG_INF, s - safe_m))
    l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _emit():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, block_q: int = 256, block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (BH, Sq, D); k, v: (BH, Sk, D) -> (BH, Sq, D).

    The wrapper in ops.py folds (batch, heads) into BH and broadcasts GQA
    KV heads. Sq/Sk padded to block multiples with masked tail (pad keys
    get -inf scores via the causal/row guard: pad rows emit zeros).
    """
    interpret = default_interpret(interpret)
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # pad keys far "in the future" so causal masking hides them; for
        # non-causal, pad with zeros and mask via a huge negative bias on
        # the padded scores by zero-ing k (score 0) — handled below.
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk
    if not causal and pk:
        raise ValueError("non-causal flash requires Sk % block_k == 0")

    grid = (bh, sq_p // bq, sk_p // bk)
    scale = 1.0 / math.sqrt(d)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=bq, block_k=bk,
                          causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
