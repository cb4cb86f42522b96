"""Single-device dense sweep drivers for the solver engine.

Two pieces:

* ``fused_sweep`` — one Jacobi (§3-schedule) HAP iteration whose heavy
  O(L*N^2) tensor updates run through the Pallas kernels
  (``repro.kernels.responsibility`` / ``availability``) instead of the
  jnp reference ops. The O(N)-output inter-level reductions (tau, phi, c)
  stay as jnp reductions — they read the same tensors the kernels just
  streamed and are not the bottleneck. Matches
  ``hap_sweep_parallel`` numerically (same formulas, same tie rules; the
  kernel's tiled column sums can differ from ``hap.column_sum``'s fixed
  order by float-associativity ulps).

* ``run_dense`` — the jitted driver the engine calls for the whole dense
  family (``dense_sequential``, ``dense_parallel``, ``dense_fused``).
  ``stop="fixed"`` scans exactly ``max_iterations`` sweeps; per-sweep
  exemplar-change counts come back as the convergence trace.
  ``stop="converged"`` runs a single ``lax.while_loop`` that exits as soon
  as assignments have been stable for ``patience`` sweeps — early exit
  happens on device, inside jit, so converging in 19 sweeps costs 19
  sweeps, not ``max_iterations``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hap
from repro.kernels.availability import availability_pallas
from repro.kernels.responsibility import responsibility_pallas
from repro.runtime.trace import SCOPE_ASSIGN

DenseOrder = ("sequential", "parallel", "fused")


def fused_sweep(state: hap.HAPState, first_iter, *, lam: float,
                kappa: float, s_mode: str, block: int) -> hap.HAPState:
    """One MR-schedule iteration with Pallas-kernel tensor updates.

    Shares ``hap.jacobi_sweep``'s Job-1/Job-2 scaffolding with
    ``hap_sweep_parallel`` and injects the fused damped
    responsibility/availability kernels as the per-level heavy updates
    (L is small and static: the level loop is unrolled).
    """
    def update_r(s, a, tau, r):
        return jnp.stack([
            responsibility_pallas(s[l], a[l], tau[l], r[l], lam,
                                  block_i=block, block_j=block)
            for l in range(s.shape[0])])

    def update_a(r, c, phi, a):
        return jnp.stack([
            availability_pallas(r[l], c[l], phi[l], a[l], lam,
                                block_i=block, block_j=block)
            for l in range(r.shape[0])])

    return hap.jacobi_sweep(state, first_iter, lam=lam, kappa=kappa,
                            s_mode=s_mode, update_r=update_r,
                            update_a=update_a)


def _make_sweep(order: str, damping: float, kappa: float, s_mode: str,
                block: int):
    if order == "sequential":
        return lambda st, it: hap.hap_sweep_sequential(
            st, damping, kappa, s_mode)
    if order == "parallel":
        return lambda st, it: hap.hap_sweep_parallel(
            st, damping, kappa, s_mode, it == 0)
    if order == "fused":
        return lambda st, it: fused_sweep(
            st, it == 0, lam=damping, kappa=kappa, s_mode=s_mode,
            block=block)
    raise ValueError(f"unknown dense order {order!r}")


def _assignments(state: hap.HAPState) -> jnp.ndarray:
    return jnp.argmax(state.a + state.r, axis=2).astype(jnp.int32)


def drive_sweeps(init, sweep, assign, levels: int, n: int, *,
                 max_iterations: int, stop: str, patience: int,
                 count_mask=None, axis_name: str | None = None,
                 segmented: bool = False, carry=None, until=None):
    """The one stopping-rule loop every single-device backend shares.

    ``sweep(state, it) -> state`` and ``assign(state) -> (L, N) int32``
    are backend-specific (dense tensors or the compressed top-k layout);
    the fixed-budget scan, the convergence-driven ``lax.while_loop`` with
    its patience counter, and the per-sweep assignment-change trace are
    identical across layouts and live here. Returns
    ``(state, exemplars, n_sweeps, converged, trace)``; ``trace`` has
    length ``max_iterations`` with -1 past ``n_sweeps`` (the while_loop
    never wrote them).

    Sharded callers (``repro.solver.topk_sharded``) run this loop *inside*
    ``shard_map`` with ``n`` = their local row count: ``axis_name`` names
    the mesh axis to all-reduce the assignment-change counter over, so
    every worker sees the same global count and the while_loop exits in
    lockstep on the same sweep as the single-device run; ``count_mask``
    ((n,) bool) drops padding rows from the count, keeping the trace
    bit-identical to the unpadded oracle's.

    Checkpointed callers (``repro.solver.checkpointing``) set
    ``segmented=True`` to run one *segment* of the loop: ``carry`` is the
    raw while_loop carry ``(state, e_prev, stable, it, trace)`` from the
    previous segment (None = start fresh), ``until`` is a (possibly
    traced) sweep index to pause at, and the return value is the raw
    carry rather than the finished ``(state, e, n_sweeps, converged,
    trace)`` contract. Segments always take the while_loop path — also
    for ``stop="fixed"``, where the patience condition is disabled — so
    the checkpointed program is the *same* op sequence regardless of
    where the segment boundaries fall, which is what makes resume
    bit-exact by construction.
    """
    e0 = jnp.full((levels, n), -1, jnp.int32)
    if axis_name is not None:
        # match assign()'s device-varying type
        e0 = jax.lax.pcast(e0, (axis_name,), to="varying")

    def count_changes(e, e_prev):
        diff = e != e_prev
        if count_mask is not None:
            diff = diff & count_mask[None, :]
        changed = jnp.sum(diff.astype(jnp.int32))
        if axis_name is not None:
            changed = jax.lax.psum(changed, axis_name)
        return changed

    if stop == "fixed" and not segmented:
        def step(carry, it):
            state, e_prev = carry
            state = sweep(state, it)
            with jax.named_scope(SCOPE_ASSIGN):
                e = assign(state)
                return (state, e), count_changes(e, e_prev)

        (state, e), trace = jax.lax.scan(
            step, (init, e0), jnp.arange(max_iterations))
        return (state, e, jnp.int32(max_iterations), jnp.asarray(False),
                trace)

    # stop == "converged" (or a checkpoint segment of either stopping
    # rule): fused while_loop with a patience counter. Segments of
    # stop="fixed" disable the patience exit and bound the loop by
    # ``until`` instead of max_iterations.
    patience_eff = patience if stop == "converged" else max_iterations + 1
    until_val = jnp.int32(max_iterations if until is None else until)
    trace0 = jnp.full((max_iterations,), -1, jnp.int32)

    def cond(carry):
        _, _, stable, it, _ = carry
        return (it < until_val) & (stable < patience_eff)

    def body(carry):
        state, e_prev, stable, it, trace = carry
        state = sweep(state, it)
        with jax.named_scope(SCOPE_ASSIGN):
            e = assign(state)
            changed = count_changes(e, e_prev)
        stable = jnp.where(changed == 0, stable + 1, jnp.int32(0))
        trace = trace.at[it].set(changed)
        return (state, e, stable, it + 1, trace)

    if carry is None:
        carry = (init, e0, jnp.int32(0), jnp.int32(0), trace0)
    state, e, stable, it, trace = jax.lax.while_loop(cond, body, carry)
    if segmented:
        return state, e, stable, it, trace
    return state, e, it, stable >= patience, trace


@functools.partial(
    jax.jit,
    static_argnames=("order", "max_iterations", "damping", "kappa",
                     "s_mode", "stop", "patience", "block"))
def run_dense(
    s3: jnp.ndarray,
    *,
    order: str,
    max_iterations: int,
    damping: float = 0.5,
    kappa: float = 0.0,
    s_mode: str = "off",
    stop: str = "fixed",
    patience: int = 5,
    block: int = 256,
):
    """Run a dense backend on an (L, N, N) stack.

    Returns ``(state, exemplars, n_sweeps, converged, trace)`` — see
    ``drive_sweeps`` for the trace convention.
    """
    s3 = s3.astype(jnp.float32)
    levels, n, _ = s3.shape
    init = hap.hap_init(s3)
    sweep = _make_sweep(order, damping, kappa, s_mode, block)
    return drive_sweeps(init, sweep, _assignments, levels, n,
                        max_iterations=max_iterations, stop=stop,
                        patience=patience)
