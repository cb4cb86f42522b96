"""Plain reference for a sparse (top-k) HAP batch solve, in ``jax.numpy``
and ``numpy``, written from the paper (arXiv:1403.7394, Alg. 1 and the
section 3 Jacobi schedule) and the configuration's stated semantics. It
imports nothing of the system under test.

* ``build``: each point's k most similar other points, s = -||x - y||^2,
  from one matmul per block of rows at the stated precision, by
  ``lax.top_k`` (ties to the lower column).
* ``preference``: the "median" preference as the configuration states
  it: for N > 4096 (and k < N - 1) the median of the off-diagonal
  similarities among 2048 points drawn by ``jax.random.permutation``
  under ``fold_in(PRNGKey(seed), 0x5eed)``; otherwise the median of the
  stored similarities.
* ``sweeps``: HAP's message passing restricted to the stored edges (a
  missing edge is a similarity of -inf), all levels updated together
  from the previous sweep's messages, tau and c held on the first sweep,
  for a fixed number of sweeps, in the dtype given; exemplars are the
  argmax of a + r over each point's edges, ties to the lower column.
* ``edges_f64``: float64 numpy similarities of sampled rows against all
  points, for the edge check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "high": jax.lax.Precision.HIGH,
             "default": jax.lax.Precision.DEFAULT}


def _neg_sqdist(a, b, sq_a, sq_b, precision):
    d2 = sq_a[:, None] + sq_b[None, :] - 2.0 * jnp.matmul(
        a, b.T, precision=PRECISION[precision])
    return -jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("k", "block", "precision"))
def build(x, *, k: int, block: int, precision: str):
    """(N, d) -> (vals (N, k), idx (N, k)), self excluded."""
    n = x.shape[0]
    block = min(block, n)
    if n % block:
        raise ValueError(f"N={n} is not a multiple of the block {block}")
    sq = jnp.sum(x * x, axis=1)
    cols = jnp.arange(n)

    def rows(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        sb = jax.lax.dynamic_slice_in_dim(sq, i * block, block)
        s = _neg_sqdist(xb, x, sb, sq, precision)
        own = i * block + jnp.arange(block)
        s = jnp.where(cols[None, :] == own[:, None], -jnp.inf, s)
        return jax.lax.top_k(s, k)

    vals, idx = jax.lax.map(rows, jnp.arange(n // block))
    return vals.reshape(n, k), idx.reshape(n, k).astype(jnp.int32)


#: above this N (and for k < N - 1) the median preference comes from a
#: dense subsample, not from the stored similarities
PREF_EXACT_N = 4096


def preference(x, vals, seed, *, sample: int, precision: str):
    n, k = vals.shape
    if n > PREF_EXACT_N and k < n - 1:
        return _subsample_median(x, seed, sample=sample,
                                 precision=precision)
    flat = jnp.sort(vals.ravel())
    cnt = flat.shape[0]
    return 0.5 * (flat[(cnt - 1) // 2] + flat[cnt // 2])


@functools.partial(jax.jit, static_argnames=("sample", "precision"))
def _subsample_median(x, seed, *, sample: int, precision: str):
    n = x.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5eed)
    sel = jax.random.permutation(key, n)[:sample]
    xs = x[sel]
    sq = jnp.sum(xs * xs, axis=1)
    s = _neg_sqdist(xs, xs, sq, sq, precision)
    m = xs.shape[0]
    vals = jnp.sort(jnp.where(jnp.eye(m, dtype=bool), jnp.nan, s).ravel())
    cnt = m * m - m
    return 0.5 * (vals[(cnt - 1) // 2] + vals[cnt // 2])


@functools.partial(jax.jit, static_argnames=("levels", "iterations",
                                             "damping", "dtype"))
def sweeps(vals, idx, pref, *, levels: int, iterations: int,
           damping: float, dtype: str):
    """-> (L, N) int32 exemplars after ``iterations`` sweeps."""
    dt = jnp.dtype(dtype)
    n, k = vals.shape
    cols = jnp.concatenate([jnp.arange(n, dtype=jnp.int32)[:, None], idx],
                           axis=1)                      # slot 0 = self
    s0 = jnp.concatenate([jnp.full((n, 1), pref, vals.dtype), vals], axis=1)
    S = jnp.broadcast_to(s0.astype(dt)[None], (levels, n, k + 1))
    zero = jnp.zeros((levels, n, k + 1), dt)
    tau = jnp.full((levels, n), jnp.inf, dt)
    phi = jnp.zeros((levels, n), dt)
    c = jnp.zeros((levels, n), dt)
    lam = jnp.asarray(damping, dt)
    targets = cols[:, 1:].ravel()
    slot = jnp.arange(k + 1)

    def colsum(r):                  # sum over stored i -> j, i != j
        rp = jnp.maximum(r[:, 1:], 0).ravel()
        return jax.ops.segment_sum(rp, targets, num_segments=n)

    def rho(s, a, t):
        v = a + s
        i1 = jnp.argmax(v, axis=1)
        m1 = jnp.max(v, axis=1)
        m2 = jnp.max(jnp.where(slot[None, :] == i1[:, None], -jnp.inf, v),
                     axis=1)
        excl = jnp.where(slot[None, :] == i1[:, None], m2[:, None],
                         m1[:, None])
        return s + jnp.minimum(t[:, None], -excl)

    def alpha(r, cl, ph):
        col = colsum(r)
        base = cl + ph
        off = jnp.minimum(0, base[cols] + r[:, 0][cols] + col[cols]
                          - jnp.maximum(r, 0))
        return off.at[:, 0].set(base + col)

    def sweep(carry, it):
        R, A, tau, phi, c = carry
        tau_new = jnp.stack([tau[0]] + [c[l] + R[l][:, 0] + colsum(R[l])
                                        for l in range(levels - 1)])
        c_new = jnp.max(A + R, axis=2)
        first = it == 0
        tau = jnp.where(first, tau, tau_new)
        c = jnp.where(first, c, c_new)
        R = lam * R + (1 - lam) * jnp.stack(
            [rho(S[l], A[l], tau[l]) for l in range(levels)])
        phi = jnp.stack([jnp.max(A[l + 1] + S[l + 1], axis=1)
                         for l in range(levels - 1)] + [phi[-1]])
        A = lam * A + (1 - lam) * jnp.stack(
            [alpha(R[l], c[l], phi[l]) for l in range(levels)])
        return (R, A, tau, phi, c), None

    (R, A, _, _, _), _ = jax.lax.scan(sweep, (zero, zero, tau, phi, c),
                                      jnp.arange(iterations))
    v = A + R
    best = jnp.max(v, axis=2, keepdims=True)
    return jnp.min(jnp.where(v == best, cols[None], n), axis=2).astype(
        jnp.int32)


def canonical(e: np.ndarray) -> np.ndarray:
    """Each point follows its exemplar's exemplar (one pass per level)."""
    e = np.asarray(e)
    return np.stack([e[l][e[l]] for l in range(e.shape[0])])


def edges_f64(x: np.ndarray, rows: np.ndarray):
    """float64 similarities of ``rows`` against every point (self -inf)
    and the scale of each row's terms, ||x_i||^2 + max_j ||x_j||^2."""
    x64 = np.asarray(x, np.float64)
    sq = (x64 * x64).sum(axis=1)
    ref = -np.maximum(sq[rows, None] + sq[None, :]
                      - 2.0 * x64[rows] @ x64.T, 0.0)
    ref[np.arange(len(rows)), rows] = -np.inf
    return ref, sq[rows] + sq.max()


def edge_numbers(ref, scale, vals, idx, band: float) -> tuple:
    """(widest gap of a stored value from float64, over its row's
    scale; stored edges that are not among the row's k nearest, beyond
    a tie band of ``band`` times the scale)."""
    k = idx.shape[1]
    rows = np.arange(len(ref))[:, None]
    at = ref[rows, idx]
    gap = float(np.max(np.abs(vals.astype(np.float64) - at)
                       / scale[:, None]))
    kth = -np.sort(-ref, axis=1)[:, k - 1]
    miss = (at < (kth - band * scale)[:, None]).sum()
    dup = sum(len(r) - len(np.unique(r)) for r in idx)
    return gap, int(miss + dup)
