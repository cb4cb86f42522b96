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

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import hap
from repro.core.preferences import random_preference
from repro.kernels.topk_ops import (
    alpha_topk, assignments_topk, c_topk, phi_topk, rho_topk, s_next_topk,
    tau_topk,
)
from repro.kernels.topk_similarity import topk_from_dense
from repro.solver import dense

#: default neighbors per row (excluding self) when ``SolveConfig.k`` is
#: None — generous enough for clean exemplar structure on the synthetic
#: suites, small enough that N = 2e5 state stays ~100 MB.
DEFAULT_K = 64


class TopKState(NamedTuple):
    """Final message state of a ``dense_topk`` run (``keep_state``):
    ``hap`` carries (L, N, kk) s/r/a and (L, N) tau/phi/c; ``idx`` maps
    stored positions back to global column indices."""
    hap: hap.HAPState
    idx: jnp.ndarray


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


def _with_self_slot(vals, idx, pref):
    n = vals.shape[0]
    s_rows = jnp.concatenate([pref[:, None].astype(jnp.float32), vals],
                             axis=1)
    idx_full = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32)[:, None], idx], axis=1)
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


def make_topk_sweep(idx: jnp.ndarray, *, damping: float, kappa: float,
                    s_mode: str):
    """Build the ``(sweep, assign)`` pair for the compressed layout.

    One definition shared by ``run_topk`` and the checkpointed segment
    runner (``repro.solver.checkpointing``) — both must execute the
    identical op sequence per sweep for resume to be bit-exact.

    The two updates that scatter and gather through ``idx`` (tau, alpha)
    run level by level (L is small and static: unrolled) rather than
    under ``vmap``: a vmapped scatter puts the level axis minor, (N*kk,
    L), which a TPU pads from L to 128 lanes (40x the state at N=2^18)."""
    def tau_red(r, c):                       # (L-1, N, kk), (L-1, N)
        if r.shape[0] == 0:                  # L=1: no level above
            return jnp.zeros(c.shape, c.dtype)
        return jnp.stack([tau_topk(r[l], c[l], idx)
                          for l in range(r.shape[0])])

    reducers = hap.SweepReducers(
        tau=tau_red,
        phi=jax.vmap(phi_topk),
        c=jax.vmap(c_topk),
        s_next=lambda s_up, a, r, kap, mode: jax.vmap(
            lambda su, al, rl: s_next_topk(su, al, rl, kap, mode)
        )(s_up, a, r))

    def update_r(s, a, tau, r):
        return hap._damp(r, jax.vmap(rho_topk)(s, a, tau), damping)

    def update_a(r, c, phi, a):
        return hap._damp(a, jnp.stack([
            alpha_topk(r[l], c[l], phi[l], idx)
            for l in range(r.shape[0])]), damping)

    def sweep(state, it):
        return hap.jacobi_sweep(
            state, it == 0, lam=damping, kappa=kappa, s_mode=s_mode,
            update_r=update_r, update_a=update_a, reducers=reducers)

    def assign(state):
        return jax.vmap(lambda al, rl: assignments_topk(al, rl, idx))(
            state.a, state.r)

    return sweep, assign


@functools.partial(
    jax.jit,
    static_argnames=("max_iterations", "damping", "kappa", "s_mode",
                     "stop", "patience"))
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
    """Run the sparse Jacobi schedule on a compressed (L, N, kk) stack.

    Same return contract as ``run_dense``:
    ``(state, exemplars, n_sweeps, converged, trace)``.
    """
    s3k = s3k.astype(jnp.float32)
    levels, n, _ = s3k.shape
    init = hap.hap_init(s3k)
    sweep, assign = make_topk_sweep(idx, damping=damping, kappa=kappa,
                                    s_mode=s_mode)

    state, e, n_sweeps, conv, trace = dense.drive_sweeps(
        init, sweep, assign, levels, n, max_iterations=max_iterations,
        stop=stop, patience=patience)
    return TopKState(state, idx), e, n_sweeps, conv, trace
