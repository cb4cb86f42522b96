"""``dense_topk`` backend internals: compressed-layout build + driver.

The registry slot ROADMAP asked for: similarities live as a top-k-per-row
``(N, kk)`` pair (values + column indices, kk = k + 1 with slot 0 = self/
preference) instead of the dense ``(N, N)`` matrix, cutting per-level
message state from O(N^2) to O(N * k) and pushing single-device N past
10^5. The sweep is the *same* §3 Jacobi schedule as the dense family —
``repro.core.hap.jacobi_sweep`` with the ``repro.kernels.topk_ops``
updates and reducers injected — and the stopping loop is the same
``drive_sweeps`` the dense driver uses, so fixed budgets, convergence
early-exit, and the per-sweep trace all carry over unchanged.

Exactness contract: a dropped edge is a -inf similarity, under which the
sparse updates equal the dense updates restricted to stored positions.
At ``k = N - 1`` (full coverage) ``run_topk`` therefore reproduces
``dense_parallel`` assignments exactly; at small k it is the sparsified
AP of Xia et al. (arXiv:0910.1650) / Givoni et al. (arXiv:1202.3722),
which holds exemplar quality to within a couple of purity points.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import hap
from repro.core.preferences import random_preference
from repro.kernels.topk_ops import (
    alpha_topk, as_blocks, assignments_topk, c_topk, join_rows, phi_topk,
    rho_topk, s_next_topk, tau_topk,
)
from repro.kernels.topk_similarity import topk_from_dense
from repro.runtime.trace import SWEEP_PROGRAM
from repro.solver import dense

#: default neighbors per row (excluding self) when ``SolveConfig.k`` is
#: None — generous enough for clean exemplar structure on the synthetic
#: suites, small enough that N = 2e5 state stays ~100 MB.
DEFAULT_K = 64


class TopKState(NamedTuple):
    """Final message state of a ``dense_topk`` run (``keep_state``):
    ``hap`` carries (L, N, kk) s/r/a and (L, N) tau/phi/c; ``idx`` maps
    stored positions back to global column indices. An edge list laid
    out in row blocks (``repro.graph.edges.EdgeList.to_blocks``) keeps
    s/r/a and ``idx`` as tuples of blocks, in laid-out ids, and
    ``nodes[i]``, the original id of laid-out row i (None: rows are not
    relabelled)."""
    hap: hap.HAPState
    idx: jnp.ndarray
    nodes: Optional[object] = None


def resolve_k(k: Optional[int], n: int) -> int:
    """cfg.k -> effective neighbor count: default when None, clamped to
    the lossless maximum N - 1. ``solve()`` already rejects k outside
    [1, N) at entry; the clamp keeps direct callers of this module
    safe."""
    if k is None:
        return min(DEFAULT_K, n - 1)
    if k < 1:
        raise ValueError(f"k must be >= 1; got {k}")
    return min(k, n - 1)


#: above this N, string preference strategies switch from the stored
#: top-k values (biased toward near-neighbor similarities once k << N)
#: to a dense subsample — see ``sampled_preferences``.
PREF_EXACT_N = 4096
PREF_SAMPLE = 2048


def sampled_preferences(x: jnp.ndarray, strategy: str, metric: str,
                        key) -> jnp.ndarray:
    """Estimate the dense preference (median / range-mid of *all*
    off-diagonal similarities) from a random point subsample.

    At k << N the stored top-k values are each row's best similarities,
    so their median sits far above the full off-diagonal median and
    over-produces exemplars; a PREF_SAMPLE-point subsample's dense
    similarity matrix (O(PREF_SAMPLE^2), constant in N) recovers the
    Frey & Dueck calibration without materializing N x N.

    Deterministic under ``key``: the subsample is the only random draw,
    so two runs with the same key (the engine threads
    ``SolveConfig.seed`` here) produce bit-identical preferences.
    """
    from repro.core.preferences import make_preferences
    from repro.core.similarity import pairwise_similarity

    n = x.shape[0]
    sel = jax.random.permutation(key, n)[:PREF_SAMPLE]
    s = pairwise_similarity(x[sel], metric=metric)
    pref = make_preferences(s, strategy)[0]
    return jnp.full((n,), pref, jnp.float32)


def topk_preferences(vals: jnp.ndarray, strategy, *, key=None) -> jnp.ndarray:
    """Preference strategies over the compressed off-diagonal values.

    ``median``/``range_mid`` are computed from the *stored* similarities:
    at k = N - 1 the stored multiset is the full off-diagonal set, so
    both match the dense ``make_preferences`` result bit-for-bit; at
    smaller k they are biased toward near-neighbor values (stored rows
    only keep each point's best similarities) — ``build_from_points``
    switches to ``sampled_preferences`` past ``PREF_EXACT_N``, and
    calibrated sparse runs can always pass an explicit preference.
    """
    n, k = vals.shape
    if strategy is None:
        # dense-path convention: an untouched diagonal is 0 (max pref)
        return jnp.zeros((n,), vals.dtype)
    if not isinstance(strategy, str):
        return jnp.broadcast_to(jnp.asarray(strategy, vals.dtype), (n,))
    if strategy == "median":
        flat = jnp.sort(vals.ravel())
        cnt = n * k
        mid = 0.5 * (flat[(cnt - 1) // 2] + flat[cnt // 2])
        return jnp.full((n,), mid, vals.dtype)
    if strategy == "range_mid":
        return jnp.full((n,), 0.5 * (jnp.min(vals) + jnp.max(vals)),
                        vals.dtype)
    if strategy == "random":
        if key is None:
            raise ValueError("random preferences need a PRNG key")
        return random_preference(key, n, dtype=vals.dtype)
    if strategy == "constant":
        return jnp.zeros((n,), vals.dtype)
    raise ValueError(f"unknown preference strategy: {strategy}")


def _with_self_slot(vals, idx, pref, start: int = 0):
    """Prepend the self slot (preference, own row id) to rows ``start``
    onwards of the off-diagonal layout."""
    n = vals.shape[0]
    s_rows = jnp.concatenate([pref[:, None].astype(jnp.float32), vals],
                             axis=1)
    idx_full = jnp.concatenate(
        [jnp.arange(start, start + n, dtype=jnp.int32)[:, None], idx],
        axis=1)
    return s_rows, idx_full


def build_from_points(x: jnp.ndarray, k: int, levels: int, *,
                      metric: str = "neg_sqeuclidean", preference="median",
                      key=None, config=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Points -> ((L, N, kk) value stack, (N, kk) index map) without ever
    materializing the N x N matrix.

    The build itself runs through ``repro.solver.topk_build`` —
    ``config.build`` picks reference / two-stage / fused / sharded, all
    bit-identical; ``config`` defaults to an auto-select SolveConfig for
    direct callers."""
    from repro.solver.config import SolveConfig
    from repro.solver.topk_build import build_topk_similarity

    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    cfg = (config or SolveConfig()).replace(metric=metric)
    vals, idx = build_topk_similarity(x, k, cfg)
    if (isinstance(preference, str)
            and preference in ("median", "range_mid")
            and n > PREF_EXACT_N and k < n - 1):
        if key is None:
            key = jax.random.PRNGKey(0)
        # dedicated fold so the subsample draw is decoupled from any other
        # consumer of the caller's key (e.g. "random" preferences): the
        # same SolveConfig.seed always selects the same subsample
        pref = sampled_preferences(x, preference, metric,
                                   jax.random.fold_in(key, 0x5eed))
    else:
        pref = topk_preferences(vals, preference, key=key)
    if getattr(cfg, "preseed", "off") == "graph":
        # seed from a Borůvka pass over the edges just built — the graph
        # pass reuses (vals, idx), so preseeding never doubles the build
        from repro.graph.affinity import preseed_preferences
        pref = preseed_preferences(
            vals, idx, pref, target=cfg.graph_target_clusters,
            max_rounds=cfg.graph_rounds)
    s_rows, idx_full = _with_self_slot(vals, idx, pref)
    return jnp.broadcast_to(s_rows[None], (levels, *s_rows.shape)), idx_full


def compress_stack(s3: jnp.ndarray, k: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(L, N, N) dense stack -> compressed stack sharing one sparsity
    pattern (selected on level 0 — levels are replicas at build time and
    Eq 2.7 refinement preserves the pattern). The diagonal (caller-owned
    preferences) lands in the self slot untouched."""
    n = s3.shape[-1]
    _, idx = topk_from_dense(s3[0], k)
    idx_full = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32)[:, None], idx], axis=1)
    s3k = jnp.take_along_axis(
        s3.astype(jnp.float32), idx_full[None, :, :], axis=2)
    return s3k, idx_full


def make_topk_sweep(idx, *, damping: float, kappa: float, s_mode: str):
    """Build the ``(sweep, assign)`` pair for the compressed layout.

    One definition shared by ``run_topk`` and the checkpointed segment
    runner (``repro.solver.checkpointing``) — both must execute the
    identical op sequence per sweep for resume to be bit-exact. Both
    start from ``init_state``: at L >= 2 the sweep carries alpha's
    column sums of levels 0..L-2 to the next sweep's tau
    (``hap.jacobi_sweep``), one scatter a level a sweep.

    ``idx`` is one (N, kk) column map, or a tuple of (N_b, kk_b) row
    blocks (then s/r/a are tuples of (L, N_b, kk_b) blocks): the row-wise
    updates run on each block, the column statistics accumulate one (N,)
    sum over them, and (N,) vectors are the blocks' joined in row order.
    One block is the one-map program, op for op.

    The two updates that scatter and gather through ``idx`` (tau, alpha)
    run level by level (L is small and static: unrolled) rather than
    under ``vmap``: a vmapped scatter puts the level axis minor, (N*kk,
    L), which a TPU pads from L to 128 lanes (40x the state at N=2^18)."""
    blocks = as_blocks(idx)
    bounds = [0]
    for i_b in blocks:
        bounds.append(bounds[-1] + i_b.shape[0])
    n = bounds[-1]

    def per_block(fn, *xs):
        """``fn`` on each block of the row-blocked arguments -> a tuple."""
        return tuple(fn(*b) for b in zip(*map(as_blocks, xs)))

    def like(template, parts):
        return parts if isinstance(template, (tuple, list)) else parts[0]

    def split_rows(v):                       # (L, N) -> per block (L, N_b)
        if len(blocks) == 1:
            return (v,)
        return tuple(v[:, lo:hi] for lo, hi in zip(bounds, bounds[1:]))

    def level(x, l):
        return like(x, tuple(b[l] for b in as_blocks(x)))

    def tau_red(r, c, col=None):             # (L-1, N, kk), (L-1, N) x 2
        if col is None:                      # L=1: no level above
            return jnp.zeros(c.shape, c.dtype)
        return jnp.stack([tau_topk(level(r, l), c[l], col[l], idx)
                          for l in range(c.shape[0])])

    reducers = hap.SweepReducers(
        tau=tau_red,
        phi=lambda a, s: join_rows(per_block(jax.vmap(phi_topk), a, s)),
        c=lambda a, r: join_rows(per_block(jax.vmap(c_topk), a, r)),
        s_next=lambda s_up, a, r, kap, mode: like(s_up, per_block(
            jax.vmap(lambda su, al, rl: s_next_topk(su, al, rl, kap, mode)),
            s_up, a, r)))

    def update_r(s, a, tau, r):
        return like(r, per_block(
            lambda s_b, a_b, t_b, r_b: hap._damp(
                r_b, jax.vmap(rho_topk)(s_b, a_b, t_b), damping),
            s, a, split_rows(tau), r))

    def update_a(r, c, phi, a):
        new, cols = zip(*(alpha_topk(level(r, l), c[l], phi[l], idx)
                          for l in range(c.shape[0])))
        a = like(a, tuple(
            hap._damp(a_b, jnp.stack([as_blocks(lv)[b] for lv in new]),
                      damping)
            for b, a_b in enumerate(as_blocks(a))))
        if len(cols) == 1:                   # L=1: nothing carried
            return a
        return a, jnp.stack(cols[:-1])       # levels 0..L-2, for tau

    def sweep(state, it):
        return hap.jacobi_sweep(
            state, it == 0, lam=damping, kappa=kappa, s_mode=s_mode,
            update_r=update_r, update_a=update_a, reducers=reducers)

    def assign(state):
        return join_rows(per_block(
            lambda a_b, r_b, i_b: jax.vmap(
                lambda al, rl: assignments_topk(al, rl, i_b, n_total=n))(
                    a_b, r_b),
            state.a, state.r, idx))

    return sweep, assign


def init_state(s3k) -> hap.HAPState:
    """``hap.hap_init`` plus the column sums the sparse loop carries:
    (L-1, N) zeros, the scatter of rho = 0 (None at L = 1)."""
    init = hap.hap_init(s3k)
    levels, n = init.c.shape
    if levels == 1:
        return init
    return init._replace(col=jnp.zeros((levels - 1, n), init.c.dtype))


def run_topk(
    s3k: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    max_iterations: int,
    damping: float = 0.5,
    kappa: float = 0.0,
    s_mode: str = "off",
    stop: str = "fixed",
    patience: int = 5,
):
    """Run the sparse Jacobi schedule on a compressed (L, N, kk) stack
    and its (N, kk) column map, or on matching tuples of row blocks
    (``make_topk_sweep``).

    Same return contract as ``run_dense``:
    ``(state, exemplars, n_sweeps, converged, trace)``.
    """
    s3k = jax.tree.map(lambda b: b.astype(jnp.float32), s3k)
    init = init_state(s3k)
    levels, n = init.c.shape
    sweep, assign = make_topk_sweep(idx, damping=damping, kappa=kappa,
                                    s_mode=s_mode)

    state, e, n_sweeps, conv, trace = dense.drive_sweeps(
        init, sweep, assign, levels, n, max_iterations=max_iterations,
        stop=stop, patience=patience)
    return TopKState(state, idx), e, n_sweeps, conv, trace


run_topk.__name__ = SWEEP_PROGRAM         # the XLA program's name, see there
run_topk = jax.jit(run_topk, static_argnames=(
    "max_iterations", "damping", "kappa", "s_mode", "stop", "patience"))
