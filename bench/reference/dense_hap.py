"""Plain reference for one served clustering request: dense HAP on the
request's own points, unpadded and unbatched, in ``jax.numpy``, written
from the paper (arXiv:1403.7394, Alg. 1 and the section 3 Jacobi
schedule) and the configuration's stated semantics. It imports nothing
of the system under test.

s = -||x - y||^2 from one matmul at the stated precision; the diagonal
holds the median of the off-diagonal similarities; every level starts
from the same matrix; all levels update together from the previous
sweep's messages, with tau and c held on the first sweep; messages are
damped by ``damping``. The loop stops once the exemplars (argmax of
a + r, ties to the lower column) have not changed for ``patience``
sweeps, or after ``max_iterations``. The state is kept in ``dtype``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "high": jax.lax.Precision.HIGH,
             "default": jax.lax.Precision.DEFAULT}


@functools.partial(jax.jit, static_argnames=(
    "levels", "max_iterations", "patience", "damping", "precision",
    "dtype"))
def solve_one(x, *, levels: int, max_iterations: int, patience: int,
              damping: float, precision: str, dtype: str):
    """(n, d) points -> ((L, n) int32 exemplars, sweeps run)."""
    dt = jnp.dtype(dtype)
    n = x.shape[0]
    sq = jnp.sum(x * x, axis=1)
    s = -jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
        x, x.T, precision=PRECISION[precision]), 0.0)
    eye = jnp.eye(n, dtype=bool)
    vals = jnp.sort(jnp.where(eye, jnp.nan, s).ravel())
    cnt = n * n - n
    pref = 0.5 * (vals[(cnt - 1) // 2] + vals[cnt // 2])
    s = jnp.where(eye, pref, s)
    S = jnp.broadcast_to(s.astype(dt)[None], (levels, n, n))
    lam = jnp.asarray(damping, dt)
    cols = jnp.arange(n)

    def rho(sl, al, t):
        v = al + sl
        i1 = jnp.argmax(v, axis=1)
        m1 = jnp.max(v, axis=1)
        hit = cols[None, :] == i1[:, None]
        m2 = jnp.max(jnp.where(hit, -jnp.inf, v), axis=1)
        return sl + jnp.minimum(t[:, None],
                                -jnp.where(hit, m2[:, None], m1[:, None]))

    def colsum(r):
        return jnp.sum(jnp.where(eye, 0, jnp.maximum(r, 0)), axis=0)

    def alpha(r, cl, ph):
        rp = jnp.where(eye, 0, jnp.maximum(r, 0))
        col = jnp.sum(rp, axis=0)
        base = cl + ph
        off = jnp.minimum(0, (base + jnp.diagonal(r) + col)[None, :] - rp)
        return jnp.where(eye, (base + col)[None, :], off)

    def exemplars(R, A):
        return jnp.argmax(A + R, axis=2).astype(jnp.int32)

    def sweep(R, A, tau, phi, c, it):
        tau_new = jnp.stack([tau[0]] + [
            c[l] + jnp.diagonal(R[l]) + colsum(R[l])
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
        return R, A, tau, phi, c

    def cond(carry):
        *_, stable, it = carry
        return (it < max_iterations) & (stable < patience)

    def body(carry):
        R, A, tau, phi, c, e_prev, stable, it = carry
        R, A, tau, phi, c = sweep(R, A, tau, phi, c, it)
        e = exemplars(R, A)
        stable = jnp.where(jnp.all(e == e_prev), stable + 1, 0)
        return R, A, tau, phi, c, e, stable, it + 1

    zero = jnp.zeros((levels, n, n), dt)
    carry = (zero, zero, jnp.full((levels, n), jnp.inf, dt),
             jnp.zeros((levels, n), dt), jnp.zeros((levels, n), dt),
             jnp.full((levels, n), -1, jnp.int32), jnp.int32(0),
             jnp.int32(0))
    out = jax.lax.while_loop(cond, body, carry)
    return out[5], out[7]


def canonical(e: np.ndarray) -> np.ndarray:
    """Each point follows its exemplar's exemplar (one pass per level)."""
    e = np.asarray(e)
    return np.stack([e[l][e[l]] for l in range(e.shape[0])])
