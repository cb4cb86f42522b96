"""Sparse HAP message updates on the top-k similarity layout.

Layout contract (produced by ``repro.solver.topk``): per level,

    s, r, a : (N, kk) with kk = k + 1
    idx     : (N, kk) i32, shared across levels;
              idx[i, 0] == i (the "self" slot — preference / rho_ii /
              alpha_ii live here), idx[i, 1:] the neighbor columns
              (ascending from the point build), padding pointing at i.

An edge list of uneven degrees comes as row blocks instead
(``repro.graph.edges.EdgeList.to_blocks``): a sequence of such (N_b,
kk_b) pairs whose rows are consecutive ranges of 0..N-1. The row-wise
ops run on each block as they are; the column statistics take blocks
(``col_stats_topk``, ``alpha_topk``, ``tau_topk``): one (N,) column sum
accumulated over the blocks' scatters, read back by one gather a block.

Semantics: a missing edge is a similarity of -inf. Under that convention
every dense update (Eqs 2.1-2.6) restricted to the stored positions is
*exact* — absent entries can never win a max and their clamped
responsibilities contribute 0 to column sums — so at full coverage
(k = N - 1) these ops reproduce the dense recurrence entry-for-entry,
and at k < N - 1 they are the sparsified AP of Xia et al. (0910.1650).

Row reductions (rho's top-2, phi, c) are O(N * kk) dense-on-compressed
work; the column-wise availability statistics become a scatter/segment
sum over the incoming-edge lists (the transpose of ``idx``), the one
genuinely sparse primitive in the sweep. Alpha reads them back with one
(N, kk) gather per level, of the three per-column statistics summed
first, plus an (N,) gather for the self slot. Tau needs the same sums of
the same rho one sweep later, so it takes the ones alpha scattered
(``tau_topk``'s ``col``): one scatter a level a sweep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.affinity import masked_top2
from repro.runtime import trace

NEG_INF = float("-inf")


def rho_topk(s: jnp.ndarray, a: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.1 on stored entries: rho_p = s_p + min(tau_i, -max_{q!=p}(a+s)).

    Identical formula to the dense update — the row max over "all columns
    but this one" is the row max over stored positions, since absent
    columns carry -inf similarity.
    """
    v = a + s
    m1, i1, m2 = masked_top2(v)
    pos = jnp.arange(s.shape[-1])
    row_max_excl = jnp.where(
        pos[None, :] == i1[:, None], m2[:, None], m1[:, None])
    return s + jnp.minimum(tau[:, None], -row_max_excl)


def as_blocks(x) -> tuple:
    """A layout given as one array or as a sequence of row blocks ->
    the tuple of its blocks."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def join_rows(parts) -> jnp.ndarray:
    """Per-block (..., N_b) vectors -> the (..., N) vector."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def col_partial_topk(r: jnp.ndarray, idx: jnp.ndarray, n_total: int,
                     col: jnp.ndarray | None = None) -> jnp.ndarray:
    """A row block's contributions to the (n_total,) availability column
    sum: scatter of max(0, rho) over the block's stored edges, self slot
    excluded, added into ``col`` (zeros when None). On one device
    (``n_total == N``, all rows) this IS the full column statistic; a
    row-sharded sweep psums the per-shard partials (or all-gathers rho
    and scatters the full edge set at once — the allgather exchange, same
    accumulation order as this single scatter).
    """
    with jax.named_scope(trace.SCOPE_COLSUM):
        rp = jnp.maximum(r, 0.0).at[:, 0].set(0.0)  # self slot excluded
        if col is None:
            col = jnp.zeros((n_total,), r.dtype)
        return col.at[idx.ravel()].add(rp.ravel())


def col_stats_topk(r, idx) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Column statistics over incoming edges (the scatter/segment sum).

    Returns ``col`` (N,) = sum over stored edges (i -> j), i != j, of
    max(0, rho_ij), indexed by target j, and ``rdiag`` (N,) = rho_jj
    (the self slot). ``col`` is the availability/tau column sum; only
    rows that actually keep an edge to j contribute — exactly the dense
    sum when absent responsibilities are -inf (clamped to 0). ``r`` and
    ``idx`` are one (N, kk) layout or matching sequences of row blocks,
    whose scatters accumulate into the one ``col``.
    """
    r, idx = as_blocks(r), as_blocks(idx)
    n = sum(i.shape[0] for i in idx)
    col = None
    for r_b, i_b in zip(r, idx):
        col = col_partial_topk(r_b, i_b, n, col)
    return col, join_rows([r_b[:, 0] for r_b in r])


def alpha_from_stats(r: jnp.ndarray, idx: jnp.ndarray, col: jnp.ndarray,
                     base: jnp.ndarray, rdiag: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.2/2.3 for a row block given full-length column statistics.

    ``r``/``idx`` may be any row slice; ``col`` (availability column
    sums), ``base`` (c + phi) and ``rdiag`` (rho self slot) are indexed
    by *global* column id, so a sharded caller hands in the exchanged
    full-length vectors and the local caller its own (N,) statistics —
    identical arithmetic either way (the self-slot gather is an identity
    gather on one device).

    The three statistics are summed in (N,) space and gathered once,
    ``(base + rdiag + col)[idx]``: the same f32 additions in the same
    order as summing the three gathered (N, kk) blocks, one walk through
    ``idx`` instead of three. The self slot likewise gathers
    ``base + col`` once.
    """
    with jax.named_scope(trace.SCOPE_GATHER):
        t = base + rdiag + col
        rp = jnp.maximum(r, 0.0)
        a_off = jnp.minimum(0.0, t[idx] - rp)
        rows = idx[:, 0]                             # global row per block row
        a_self = (base + col)[rows]                  # diagonal rule, no clamp
        return a_off.at[:, 0].set(a_self)


def alpha_topk(r, c: jnp.ndarray, phi: jnp.ndarray, idx):
    """Eq 2.2/2.3 on stored entries via gathered column statistics; one
    layout, or row blocks (then a tuple of blocks). Returns ``(alpha,
    col)``: ``col`` is the (N,) column sum it scattered, which the
    sparse loop carries to the next sweep's ``tau_topk``."""
    col, rdiag = col_stats_topk(r, idx)
    out = tuple(alpha_from_stats(r_b, i_b, col, c + phi, rdiag)
                for r_b, i_b in zip(as_blocks(r), as_blocks(idx)))
    return (out if isinstance(r, (tuple, list)) else out[0]), col


def tau_from_stats(c: jnp.ndarray, rdiag: jnp.ndarray,
                   col: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.4 for a row block: all three operands aligned to the block's
    rows (a sharded caller gathers its rows out of the exchanged column
    sum first)."""
    return c + rdiag + col


def tau_topk(r, c: jnp.ndarray, col: jnp.ndarray, idx) -> jnp.ndarray:
    """Eq 2.4: tau_j^{l+1} = c_j + rho_jj + sum_{k!=j} max(0, rho_kj),
    given that column sum ``col`` of this rho (``col_stats_topk``): the
    sparse loop carries the one its alpha update scattered at the
    previous sweep, so tau scatters nothing.

    The column sum is read back through the self slot's row ids, as a
    row-sharded caller reads its block: added straight to a scatter's
    result, ``c + rho_jj`` would be folded by XLA into the scatter's
    initial value, which sums the same terms in another order. One
    layout or row blocks."""
    rdiag = join_rows([r_b[:, 0] for r_b in as_blocks(r)])
    rows = join_rows([i_b[:, 0] for i_b in as_blocks(idx)])
    return tau_from_stats(c, rdiag, col[rows])


def phi_topk(a: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.5: phi_i^{l-1} = max over stored positions of (alpha + s)."""
    return jnp.max(a + s, axis=1)


def c_topk(a: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.6: c_i = max over stored positions of (alpha + rho)."""
    return jnp.max(a + r, axis=1)


def s_next_topk(s_next: jnp.ndarray, a: jnp.ndarray, r: jnp.ndarray,
                kappa: float, mode: str) -> jnp.ndarray:
    """Eq 2.7 on the compressed layout; the self slot (preference) is
    preserved, and the sparsity pattern is — refinement only reweights
    stored edges, mirroring ``repro.core.hap.s_next_level``."""
    if mode == "paper":
        v = (a + r).at[:, 0].set(NEG_INF)
        out = s_next + kappa * jnp.max(v, axis=1)[:, None]
    elif mode == "evidence":
        out = s_next + kappa * (a + r)
    else:
        return s_next
    return out.at[:, 0].set(s_next[:, 0])


def assignments_topk(a: jnp.ndarray, r: jnp.ndarray, idx: jnp.ndarray,
                     n_total: int | None = None) -> jnp.ndarray:
    """Eq 2.8 decode: argmax of (alpha + rho) over stored positions,
    mapped back to global column indices.

    Ties break on the *global* column index (dense ``argmax`` keeps the
    first, i.e. lowest, column) — stored-position order puts the self
    slot first, which would pick column i over a tied column j < i and
    silently break the k = N-1 bit-parity contract on duplicate points.

    ``n_total`` is the global point count when ``a``/``r``/``idx`` are a
    row *shard*: the non-maximal sentinel must sit past every global
    column, not just past the shard's row count.
    """
    v = a + r
    m = jnp.max(v, axis=1, keepdims=True)
    n = idx.shape[0] if n_total is None else n_total
    cand = jnp.where(v == m, idx, n)       # non-maximal -> past any column
    return jnp.min(cand, axis=1).astype(jnp.int32)
