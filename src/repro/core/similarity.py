"""Similarity-matrix construction for (H)AP.

The paper (§2) takes a dense negative-valued similarity matrix as the sole
input: ``s_ij = -||x_i - x_j||^2`` is the default metric, the diagonal holds
the *preferences* (how much each point wants to be an exemplar).

Builders here are tiled so the N x N matrix can be produced blockwise on
device (the O(N^2) similarity build is itself a MapReduce job in the paper's
pipeline; here it is a jitted blockwise map, with a Pallas kernel backend in
``repro.kernels.similarity`` for the TPU hot path).
"""
from __future__ import annotations

import functools
from typing import Callable, Literal

import jax
import jax.numpy as jnp

Metric = Literal["neg_sqeuclidean", "neg_euclidean", "cosine"]

# Finite stand-in for the paper's "-inf" (low preference); keeps arithmetic
# NaN-free under +/- and damping.
NEG_LARGE = -1.0e9

#: f32 matmul precision of every similarity build: the builders here, the
#: Pallas similarity and fused top-k kernels, and the two-stage top-k
#: build. ||x||^2 + ||y||^2 - 2 x.y cancels, so the cross term needs full
#: f32 products; a TPU's default single bf16 pass would move near
#: neighbours by far more than the gaps between them. CPU f32 dots are
#: full precision either way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def _sq_norms(x: jnp.ndarray) -> jnp.ndarray:
    """Row norms ||x||^2 summed left to right from separately rounded
    squares. The barrier keeps XLA from fusing a square into the next
    add as one fused multiply-add, which it does in some programs and
    not others: the same points then get the same norms in a batched
    serving program and in an unbatched solve."""
    sq = jax.lax.optimization_barrier(x * x)
    out = sq[..., 0]
    for k in range(1, x.shape[-1]):
        out = out + sq[..., k]
    return out


def _neg_sqeuclidean(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    # ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y  (MXU-friendly: one matmul)
    xx = _sq_norms(x)[:, None]
    yy = _sq_norms(y)[None, :]
    xy = jnp.matmul(x, y.T, precision=MATMUL_PRECISION)
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    return -d2


def _neg_euclidean(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return -jnp.sqrt(jnp.maximum(-_neg_sqeuclidean(x, y), 1e-12))


def _cosine(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    yn = y / (jnp.linalg.norm(y, axis=-1, keepdims=True) + 1e-12)
    # cosine similarity in [-1, 1]; shift to <= 0 per the paper's convention.
    return jnp.matmul(xn, yn.T, precision=MATMUL_PRECISION) - 1.0

_METRICS: dict[str, Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = {
    "neg_sqeuclidean": _neg_sqeuclidean,
    "neg_euclidean": _neg_euclidean,
    "cosine": _cosine,
}


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_similarity(
    x: jnp.ndarray, metric: Metric = "neg_sqeuclidean"
) -> jnp.ndarray:
    """Dense (N, N) similarity matrix, diagonal left at 0 (max preference)."""
    return _METRICS[metric](x, x)


@functools.partial(jax.jit, static_argnames=("metric", "block"))
def pairwise_similarity_blockwise(
    x: jnp.ndarray, metric: Metric = "neg_sqeuclidean", block: int = 512
) -> jnp.ndarray:
    """Blockwise builder: maps row-tiles so peak memory is O(block * N).

    Matches the paper's view of the similarity build as an embarrassingly
    parallel map over row shards.
    """
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    rows = xp.reshape(-1, block, x.shape[1])
    fn = _METRICS[metric]
    out = jax.lax.map(lambda r: fn(r, x), rows)
    return out.reshape(-1, n)[:n]


def set_preferences(s: jnp.ndarray, pref: jnp.ndarray | float) -> jnp.ndarray:
    """Write the diagonal (preference) entries of a similarity matrix."""
    n = s.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    pref = jnp.broadcast_to(jnp.asarray(pref, s.dtype), (n,))
    return jnp.where(eye, pref[None, :] * jnp.ones((n, 1), s.dtype), s)


def stack_levels(s: jnp.ndarray, levels: int) -> jnp.ndarray:
    """(N, N) -> (L, N, N): the paper replicates S across hierarchy levels."""
    return jnp.broadcast_to(s[None], (levels, *s.shape))
