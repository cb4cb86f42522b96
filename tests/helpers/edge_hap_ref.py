"""A plain float64 numpy HAP over a flat COO edge list, and the seeded
Kronecker graphs of the benchmark to run it on.

``hap_edges`` is the sparse HAP of ``repro.solver.topk`` written from
the paper (Alg. 1, the section 3 Jacobi schedule) over one flat list of
entries: each node's self slot (its preference) and its stored edges,
nothing else. Row maxima and top-2 are segment reductions over the
entries sorted by row, the column sums a weighted ``bincount``. No
padding, no blocks, no relabelling: every id is the caller's.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def kronecker(scale: int, seed: int = 0):
    """A seeded Graph500 Kronecker graph at ``scale``, edgefactor 16, as
    the benchmark makes it (``bench/lib/kronecker.py``): symmetric, no
    self-loop, no isolated vertex, weights U[0, 1).
    -> (src, dst, weight, N), sorted (src, dst)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "bench", "lib", "kronecker.py")
    spec = importlib.util.spec_from_file_location("bench_kronecker", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(scale, 16, (0.57, 0.19, 0.19, 0.05), scale, seed)


def hap_edges(src, dst, weight, n: int, pref, *, levels: int,
              iterations: int, damping: float, state: bool = False):
    """-> (L, N) exemplars (argmax of a + r over each node's entries,
    ties to the lower column) after ``iterations`` sweeps; with
    ``state``, also the entries' (row, col) and the final (L, E') r, a."""
    row = np.concatenate([np.arange(n), np.asarray(src, np.int64)])
    col = np.concatenate([np.arange(n), np.asarray(dst, np.int64)])
    s = np.concatenate([np.broadcast_to(np.asarray(pref, np.float64), (n,)),
                        np.asarray(weight, np.float64)])
    order = np.lexsort((col, row))
    row, col, s = row[order], col[order], s[order]
    starts = np.searchsorted(row, np.arange(n))
    self_slot = row == col
    pos = np.arange(len(row))

    def rowmax(v):
        return np.maximum.reduceat(v, starts)

    def colsum(r):                  # sum over stored i -> j, i != j
        return np.bincount(col, weights=np.where(self_slot, 0.0,
                                                 np.maximum(r, 0.0)),
                           minlength=n)

    def rho(s_, a, tau):
        v = a + s_
        m1 = rowmax(v)
        first = np.minimum.reduceat(np.where(v == m1[row], pos, len(pos)),
                                    starts)
        m2 = rowmax(np.where(pos == first[row], -np.inf, v))
        excl = np.where(pos == first[row], m2[row], m1[row])
        return s_ + np.minimum(tau[row], -excl)

    def alpha(r, c, phi):
        colv = colsum(r)
        base = c + phi
        rdiag = r[self_slot]               # one self slot a row, in order
        off = np.minimum(0.0, (base + rdiag + colv)[col] - np.maximum(r, 0))
        return np.where(self_slot, (base + colv)[row], off)

    lam = float(damping)
    S = np.broadcast_to(s, (levels, len(s)))
    R = np.zeros((levels, len(s)))
    A = np.zeros((levels, len(s)))
    tau = np.full((levels, n), np.inf)
    phi = np.zeros((levels, n))
    c = np.zeros((levels, n))
    for it in range(iterations):
        if it:
            for lv in range(1, levels):
                tau[lv] = (c[lv - 1] + R[lv - 1][self_slot]
                           + colsum(R[lv - 1]))
            c = np.stack([rowmax(A[lv] + R[lv]) for lv in range(levels)])
        R = lam * R + (1 - lam) * np.stack(
            [rho(S[lv], A[lv], tau[lv]) for lv in range(levels)])
        for lv in range(levels - 1):
            phi[lv] = rowmax(A[lv + 1] + S[lv + 1])
        A = lam * A + (1 - lam) * np.stack(
            [alpha(R[lv], c[lv], phi[lv]) for lv in range(levels)])
    v = A + R
    best = np.stack([rowmax(v[lv]) for lv in range(levels)])
    e = np.stack([np.minimum.reduceat(
        np.where(v[lv] == best[lv][row], col, n), starts)
        for lv in range(levels)])
    if state:
        return e, (row, col, R, A)
    return e
