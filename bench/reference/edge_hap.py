"""Plain reference for a HAP batch solve over an edge list, in
``jax.numpy``, written from the paper (arXiv:1403.7394, Alg. 1 and the
section 3 Jacobi schedule) and the configuration's stated semantics. It
imports nothing of the system under test.

The graph is one flat COO list, (src, dst, weight) sorted by (src, dst),
as the benchmark's generator makes it. Every node gets one self entry
that holds its preference; a node's entries are its self entry and its
out-edges, and a missing edge is a similarity of -inf. Row maxima, the
top-2 of Eq 2.1 and the column sums are segment reductions over those
entries (``jax.ops.segment_*``): no padding, no blocks, no relabelling.

* ``preference``: the "median" preference, the median of the stored
  weights (the mean of the two middle ones for an even count).
* ``sweeps``: all levels updated together from the previous sweep's
  messages, tau and c held on the first sweep, for a fixed number of
  sweeps, in the dtype given; exemplars are the argmax of a + r over a
  node's entries, ties to the lower column.
* ``canonical``: each point follows its exemplar's exemplar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def preference(weight) -> float:
    w = np.sort(np.asarray(weight, np.float32))
    cnt = len(w)
    return float(np.float32(0.5) * (w[(cnt - 1) // 2] + w[cnt // 2]))


@functools.partial(jax.jit, static_argnames=("n", "levels", "iterations",
                                             "damping", "dtype"))
def sweeps(src, dst, weight, pref, *, n: int, levels: int, iterations: int,
           damping: float, dtype: str):
    """-> (L, N) int32 exemplars after ``iterations`` sweeps."""
    dt = jnp.dtype(dtype)
    nodes = jnp.arange(n, dtype=jnp.int32)
    row = jnp.concatenate([nodes, src])
    col = jnp.concatenate([nodes, dst])
    s = jnp.concatenate([jnp.full((n,), pref, jnp.float32),
                         weight.astype(jnp.float32)]).astype(dt)
    own = jnp.arange(row.shape[0]) < n                 # the self entries
    pos = jnp.arange(row.shape[0])
    lam = jnp.asarray(damping, dt)

    def rowmax(v):
        return jax.ops.segment_max(v, row, num_segments=n)

    def colsum(r):                   # sum over stored i -> j, i != j
        return jax.ops.segment_sum(jnp.where(own, 0, jnp.maximum(r, 0)),
                                   col, num_segments=n)

    def rho(a, t):
        v = a + s
        m1 = rowmax(v)
        first = jax.ops.segment_min(jnp.where(v == m1[row], pos, pos.size),
                                    row, num_segments=n)
        top = pos == first[row]
        m2 = rowmax(jnp.where(top, -jnp.inf, v))
        return s + jnp.minimum(t[row], -jnp.where(top, m2[row], m1[row]))

    def alpha(r, cl, ph):
        col_sum = colsum(r)
        base = cl + ph
        off = jnp.minimum(0, (base + r[:n] + col_sum)[col]
                          - jnp.maximum(r, 0))
        return jnp.where(own, (base + col_sum)[row], off)

    def sweep(carry, it):
        R, A, tau, phi, c = carry
        tau_new = jnp.stack([tau[0]] + [c[l] + R[l][:n] + colsum(R[l])
                                        for l in range(levels - 1)])
        c_new = jnp.stack([rowmax(A[l] + R[l]) for l in range(levels)])
        first = it == 0
        tau = jnp.where(first, tau, tau_new)
        c = jnp.where(first, c, c_new)
        R = lam * R + (1 - lam) * jnp.stack(
            [rho(A[l], tau[l]) for l in range(levels)])
        phi = jnp.stack([rowmax(A[l + 1] + s) for l in range(levels - 1)]
                        + [phi[-1]])
        A = lam * A + (1 - lam) * jnp.stack(
            [alpha(R[l], c[l], phi[l]) for l in range(levels)])
        return (R, A, tau, phi, c), None

    zero = jnp.zeros((levels, row.shape[0]), dt)
    init = (zero, zero, jnp.full((levels, n), jnp.inf, dt),
            jnp.zeros((levels, n), dt), jnp.zeros((levels, n), dt))
    (R, A, _, _, _), _ = jax.lax.scan(sweep, init, jnp.arange(iterations))

    def decode(v):
        best = rowmax(v)
        return jax.ops.segment_min(jnp.where(v == best[row], col, n), row,
                                   num_segments=n)

    return jnp.stack([decode(A[l] + R[l]) for l in range(levels)]).astype(
        jnp.int32)


def canonical(e: np.ndarray) -> np.ndarray:
    """Each point follows its exemplar's exemplar (one pass per level)."""
    e = np.asarray(e)
    return np.stack([e[l][e[l]] for l in range(e.shape[0])])
