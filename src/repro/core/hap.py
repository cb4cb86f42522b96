"""Hierarchical Affinity Propagation (paper §2, Alg. 1) — dense reference.

State is exactly the paper's six tensors:
    S, alpha, rho : (L, N, N)
    tau, phi, c   : (L, N)
with the boundary conventions (DESIGN §1): tau[0] = +inf forever (level 1 has
no lower level), phi[L-1] = 0 forever (top level has no upper level).

Two sweep orders are provided:

* ``sequential`` — Alg. 1 as printed: per iteration, levels are processed
  bottom-up and inter-level messages produced at level l (tau^{l+1}) are
  consumed *within the same iteration* (Gauss-Seidel).
* ``parallel``  — the MapReduce schedule of §3: all levels update
  simultaneously from the previous iteration's messages (Jacobi). Job 1
  updates tau, c, rho; Job 2 updates phi, alpha; tau and c are skipped on
  the first iteration (§3.0.1). This is the order the distributed runtime
  (``repro.core.mrhap``) implements, so dense-parallel vs distributed can be
  compared bit-for-bit in tests.

Both damp rho/alpha by ``lambda`` per level (paper §2).
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.affinity import masked_top2

SweepOrder = Literal["sequential", "parallel"]
SUpdateMode = Literal["off", "paper", "evidence"]


class HAPState(NamedTuple):
    s: jnp.ndarray    # (L, N, N) similarities (levels may diverge via eq 2.7)
    r: jnp.ndarray    # (L, N, N) responsibilities (rho)
    a: jnp.ndarray    # (L, N, N) availabilities (alpha)
    tau: jnp.ndarray  # (L, N) upward messages; tau[0] == +inf
    phi: jnp.ndarray  # (L, N) downward messages; phi[L-1] == 0
    c: jnp.ndarray    # (L, N) cluster preferences


class HAPResult(NamedTuple):
    exemplars: jnp.ndarray   # (L, N) int32
    n_clusters: jnp.ndarray  # (L,)   int32
    state: HAPState


# ---------------------------------------------------------------- per-level
def column_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over rows (axis 0) in one fixed pairwise order: the rows are
    zero-padded to a power of two and the lower half folded onto the upper
    half until one row is left.

    Only elementwise adds, so the rounding is the same in every program
    that computes it — batched under ``vmap`` or not, whatever XLA fuses —
    and trailing zero rows change nothing: a request padded with inert rows
    sums exactly as it does unpadded. (A ``jnp.sum`` leaves the order to
    the compiler, which picks it per program and shape.)"""
    rows = x.shape[0]
    half = (1 << (rows - 1).bit_length()) // 2     # pow2 >= rows, halved
    while half:
        lo, hi = x[:half], x[half:]
        if hi.shape[0] < half:
            hi = jnp.pad(hi, [(0, half - hi.shape[0])]
                         + [(0, 0)] * (x.ndim - 1))
        x, half = lo + hi, half // 2
    return x[0]


def rho_update(s: jnp.ndarray, a: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.1: rho_ij = s_ij + min(tau_i, -max_{k!=j}(a_ik + s_ik))."""
    v = a + s
    m1, i1, m2 = masked_top2(v)
    j = jnp.arange(s.shape[-1])
    row_max_excl = jnp.where(j[None, :] == i1[:, None], m2[:, None], m1[:, None])
    return s + jnp.minimum(tau[:, None], -row_max_excl)


def alpha_update(
    r: jnp.ndarray, c: jnp.ndarray, phi: jnp.ndarray
) -> jnp.ndarray:
    """Eq 2.2/2.3 via clamped column sums (single O(N^2) pass)."""
    n = r.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    rp = jnp.where(eye, 0.0, jnp.maximum(r, 0.0))  # max(0, rho_kj), k != j
    col = column_sum(rp)                           # (N,) sum_{k != j}
    rdiag = jnp.diagonal(r)
    base = c[None, :] + phi[None, :]
    a_off = jnp.minimum(0.0, base + rdiag[None, :] + col[None, :] - rp)
    a_diag = base + col[None, :]
    return jnp.where(eye, a_diag, a_off)


def tau_from_level(r: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.4: tau_j^{l+1} = c_j^l + rho_jj^l + sum_{k!=j} max(0, rho_kj^l)."""
    n = r.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    col = column_sum(jnp.where(eye, 0.0, jnp.maximum(r, 0.0)))
    return c + jnp.diagonal(r) + col


def phi_from_level(a: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.5: phi_i^{l-1} = max_k(alpha_ik^l + s_ik^l)."""
    return jnp.max(a + s, axis=1)


def c_update(a: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Eq 2.6: c_i^l = max_j(alpha_ij^l + rho_ij^l)."""
    return jnp.max(a + r, axis=1)


def s_next_level(
    s_next: jnp.ndarray, a: jnp.ndarray, r: jnp.ndarray, kappa: float,
    mode: SUpdateMode,
) -> jnp.ndarray:
    """Eq 2.7 (optional): level-wise similarity refinement.

    ``paper`` follows the equation as printed — a per-row shift by
    kappa * max_{j!=i}(a_ij + r_ij). ``evidence`` follows the prose (same
    cluster => reinforce, different => weaken) with the pairwise evidence
    kappa * (a_ij + r_ij); the diagonal (preferences) is preserved.
    """
    n = s_next.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    if mode == "paper":
        v = jnp.where(eye, -jnp.inf, a + r)
        shift = kappa * jnp.max(v, axis=1)
        out = s_next + shift[:, None]
    elif mode == "evidence":
        out = s_next + kappa * (a + r)
    else:
        return s_next
    return jnp.where(eye, s_next, out)


# ------------------------------------------------------------------- sweeps
def hap_init(s3: jnp.ndarray) -> HAPState:
    """Paper init: alpha = rho = 0, tau = +inf, phi = 0, c = 0."""
    levels, n, _ = s3.shape
    z3 = jnp.zeros_like(s3)
    zv = jnp.zeros((levels, n), s3.dtype)
    tau = jnp.full((levels, n), jnp.inf, s3.dtype)
    return HAPState(s=s3, r=z3, a=z3, tau=tau, phi=zv, c=zv)


def _damp(old: jnp.ndarray, new: jnp.ndarray, lam: float) -> jnp.ndarray:
    return lam * old + (1.0 - lam) * new


def hap_sweep_sequential(
    state: HAPState, lam: float, kappa: float, s_mode: SUpdateMode
) -> HAPState:
    """One Alg.-1 iteration: bottom-up Gauss-Seidel over levels."""
    levels = state.s.shape[0]
    s, r, a = state.s, state.r, state.a
    tau, phi, c = state.tau, state.phi, state.c
    for l in range(levels):  # L is small and static: unrolled
        r_l = _damp(r[l], rho_update(s[l], a[l], tau[l]), lam)
        a_l = _damp(a[l], alpha_update(r_l, c[l], phi[l]), lam)
        r, a = r.at[l].set(r_l), a.at[l].set(a_l)
        c = c.at[l].set(c_update(a_l, r_l))
        if l + 1 < levels:
            tau = tau.at[l + 1].set(tau_from_level(r_l, c[l]))
        if l > 0:
            phi = phi.at[l - 1].set(phi_from_level(a_l, s[l]))
        if s_mode != "off" and l + 1 < levels:
            s = s.at[l + 1].set(s_next_level(s[l + 1], a_l, r_l, kappa, s_mode))
    return HAPState(s, r, a, tau, phi, c)


class SweepReducers(NamedTuple):
    """The O(N)-output inter-level reductions a Jacobi sweep needs, each
    operating on level-stacked arrays. ``jacobi_sweep`` defaults to the
    dense (L, N, N) set below; the sparse top-k path injects the
    ``repro.kernels.topk_ops`` equivalents (closing over its index
    layout) so both share one schedule-defining sweep body."""
    tau: object      # (r[:-1], c[:-1]) -> (L-1, N)   Eq 2.4
    phi: object      # (a[1:], s[1:])   -> (L-1, N)   Eq 2.5
    c: object        # (a, r)           -> (L, N)     Eq 2.6
    s_next: object   # (s[1:], a[:-1], r[:-1], kappa, mode) -> (L-1, ...)


def _dense_reducers() -> SweepReducers:
    return SweepReducers(
        tau=jax.vmap(tau_from_level),
        phi=jax.vmap(phi_from_level),
        c=jax.vmap(c_update),
        s_next=lambda s_up, a, r, kappa, mode: jax.vmap(
            functools.partial(s_next_level, kappa=kappa, mode=mode)
        )(s_up, a, r))


def jacobi_sweep(
    state: HAPState, first_iter, *, lam: float, kappa: float,
    s_mode: SUpdateMode, update_r, update_a,
    reducers: SweepReducers | None = None,
) -> HAPState:
    """One MR-schedule iteration (§3) with injected tensor updates.

    The inter-level scaffolding — tau/c gated on ``first_iter`` (§3.0.1),
    phi from the previous iteration's alpha, the optional Eq 2.7
    similarity refinement — is schedule-defining and shared; the two
    heavy per-entry updates vary by backend:

        update_r(s, a, tau, r_old) -> damped rho   (level-stacked)
        update_a(r, c, phi, a_old) -> damped alpha

    ``hap_sweep_parallel`` injects the jnp reference pair; the solver's
    ``dense_fused`` backend injects the Pallas kernel pair; the sparse
    ``dense_topk`` backend injects compressed-layout updates plus its
    ``reducers``. One body keeps them numerically comparable by
    construction — the dense reductions are the default.
    """
    red = reducers if reducers is not None else _dense_reducers()
    s, r, a = state.s, state.r, state.a
    tau, phi, c = state.tau, state.phi, state.c

    # --- Job 1 ---------------------------------------------------------
    # tau^{l+1} from level l's previous-iteration rho/c; tau[0] stays +inf.
    tau_new = red.tau(r[:-1], c[:-1])                           # (L-1, N)
    tau_new = jnp.concatenate([tau[:1], tau_new], axis=0)
    c_new = red.c(a, r)                                         # (L, N)
    keep = jnp.asarray(first_iter)
    tau = jnp.where(keep, tau, tau_new)
    c = jnp.where(keep, c, c_new)
    r = update_r(s, a, tau, r)

    # --- Job 2 ---------------------------------------------------------
    # phi^{l-1} from level l's alpha (previous iteration); phi[L-1] stays 0.
    phi_new = red.phi(a[1:], s[1:])                             # (L-1, N)
    phi = jnp.concatenate([phi_new, phi[-1:]], axis=0)
    a = update_a(r, c, phi, a)

    if s_mode != "off":
        s_upd = red.s_next(s[1:], a[:-1], r[:-1], kappa, s_mode)
        s = jnp.concatenate([s[:1], s_upd], axis=0)
    return HAPState(s, r, a, tau, phi, c)


def hap_sweep_parallel(
    state: HAPState, lam: float, kappa: float, s_mode: SUpdateMode,
    first_iter: jnp.ndarray,
) -> HAPState:
    """One MR-schedule iteration (§3): all levels Jacobi, two fused jobs.

    Job 1: tau, c (skipped when ``first_iter``), then rho.
    Job 2: phi, then alpha.
    """
    return jacobi_sweep(
        state, first_iter, lam=lam, kappa=kappa, s_mode=s_mode,
        update_r=lambda s, a, tau, r: _damp(
            r, jax.vmap(rho_update)(s, a, tau), lam),
        update_a=lambda r, c, phi, a: _damp(
            a, jax.vmap(alpha_update)(r, c, phi), lam))


def extract_exemplars(state: HAPState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq 2.8 per level + cluster counts (Job 3)."""
    e = jnp.argmax(state.a + state.r, axis=2).astype(jnp.int32)   # (L, N)
    levels, n = e.shape
    hot = jax.vmap(lambda ei: jnp.zeros((n,), bool).at[ei].set(True))(e)
    return e, jnp.sum(hot, axis=1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("iterations", "order", "s_mode")
)
def run_hap(
    s3: jnp.ndarray,
    *,
    iterations: int = 30,
    damping: float = 0.5,
    order: SweepOrder = "sequential",
    kappa: float = 0.0,
    s_mode: SUpdateMode = "off",
) -> HAPResult:
    """Run HAP on an (L, N, N) similarity tensor for ``iterations`` sweeps.

    .. deprecated:: prefer ``repro.solver.solve`` (backends
       ``dense_sequential`` / ``dense_parallel``), which adds
       convergence-driven early stopping and a per-sweep trace. Kept as
       the registered backends' sweep implementation and for
       compatibility.
    """
    s3 = s3.astype(jnp.float32)
    init = hap_init(s3)

    if order == "sequential":
        def step(st, _):
            return hap_sweep_sequential(st, damping, kappa, s_mode), None
        state, _ = jax.lax.scan(step, init, None, length=iterations)
    else:
        def step(st, it):
            return hap_sweep_parallel(st, damping, kappa, s_mode, it == 0), None
        state, _ = jax.lax.scan(step, init, jnp.arange(iterations))

    e, k = extract_exemplars(state)
    return HAPResult(e, k, state)
