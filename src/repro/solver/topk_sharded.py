"""Row-sharded ``dense_topk`` sweeps: the distributed message-passing loop.

PR 5 sharded the top-k similarity *build*; this module shards the
*sweeps* — the piece that makes per-device runtime AND per-device state
linear in worker count (the paper's 80-VM experiment, realized on the
compressed layout). The (L, N, k+1) message tensors are row-sharded over
the 1-D ``workers`` mesh and the whole Jacobi loop — ``hap.jacobi_sweep``
bodies through ``dense.drive_sweeps``'s stopping rule — runs inside ONE
``shard_map``, so a converged run launches a single device program, not
one dispatch per sweep.

Per-sweep dataflow on each worker (B = N/W local rows):

* rho (Eq 2.1), phi (2.5), c (2.6), the Eq 2.7 refinement, and the
  Eq 2.8 decode are row reductions — shard-local, unchanged ops from
  ``repro.kernels.topk_ops``.
* the availability/tau column statistics (Eqs 2.2-2.4) sum max(0, rho)
  over *incoming* edges, whose sources live on other workers. That one
  primitive becomes an explicit exchange (``SolveConfig.exchange``):

  ``allgather`` — workers all-gather the (B, k+1) rho blocks and re-run
  the oracle's own scatter over the full edge set. Accumulation order is
  identical to the single-device scatter, so the sharded sweep matches
  ``run_topk``'s exemplars and trace exactly, its messages within the
  numeric contract (docs/solver.md). O(N*k) gathered per level per
  sweep.

  ``psum`` — each worker scatters its rows' contributions into a
  full-length (N,) partial and the partials are all-reduced. O(N)
  traffic — the scalable mode (exchange buffers stop growing with k) —
  but cross-worker addition associates per *worker block* instead of per
  edge, a float-associativity divergence of the same class the dense
  backends document: exemplar sets match the oracle, ulps may not.

  Both are deterministic for a fixed mesh; ``auto`` serves allgather
  until the edge list outgrows ``ALLGATHER_MAX_ELEMS``, then psum.

* the ``stop="converged"`` assignment-change counter is masked to real
  rows and ``psum``-ed (``drive_sweeps(axis_name=...)``), so every
  worker exits the while_loop in lockstep on the same sweep as the
  single-device run.

N is padded to the worker multiple with inert dummy rows
(``pad_topk`` — the compressed-layout analogue of
``core.mrhap.pad_similarity``): a dummy's neighbor slots all point back
at the dummy itself with strongly repelling values, so real columns
never receive a dummy contribution and the decode pins dummies to
themselves. Multi-process launches (one process per host) work through
``launch.mesh.maybe_init_distributed`` + a process-spanning
``workers`` mesh built from the global device list.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import hap
from repro.kernels.topk_ops import (
    alpha_from_stats, assignments_topk, c_topk, col_partial_topk,
    col_stats_topk, phi_topk, rho_topk, s_next_topk, tau_from_stats,
)
from repro.sharding.partitioning import device_put_row_sharded
from repro.solver import dense
from repro.solver.topk import TopKState

AXIS = "workers"

#: every sweep-execution mode; "auto" resolves per problem/host
SWEEP_MODES = ("auto", "single", "sharded")

#: column-exchange strategies for the sharded sweep
EXCHANGE_MODES = ("auto", "allgather", "psum")

#: N at which a multi-device host switches the *sweeps* to the sharded
#: driver. Higher than the build threshold (the build is O(N^2) work,
#: the sweep O(N*k) per iteration), so small solves keep the
#: zero-communication single-device loop.
SHARDED_SWEEP_N = 32768

#: padded edge count (N * (k+1)) above which the allgather
#: exchange's O(N*k) per-worker gather buffers would dominate the very
#: state the sharding removed; "auto" switches to the O(N) psum
#: exchange there (16M edges ~ 64 MB gathered per level).
ALLGATHER_MAX_ELEMS = 1 << 24


def resolve_sweep(name: str, *, n: int,
                  n_devices: Optional[int] = None) -> str:
    """``cfg.sweep`` -> "single" | "sharded" for this problem/host."""
    if name not in SWEEP_MODES:
        raise ValueError(
            f"unknown sweep mode {name!r}; known: {SWEEP_MODES}")
    if name != "auto":
        return name
    n_devices = len(jax.devices()) if n_devices is None else n_devices
    if n_devices > 1 and n >= SHARDED_SWEEP_N:
        return "sharded"
    return "single"


def resolve_exchange(name: str, *, n: int, kk: int) -> str:
    """``cfg.exchange`` -> a concrete exchange for this layout."""
    if name not in EXCHANGE_MODES:
        raise ValueError(
            f"unknown exchange mode {name!r}; known: {EXCHANGE_MODES}")
    if name != "auto":
        return name
    return "allgather" if n * kk <= ALLGATHER_MAX_ELEMS else "psum"


def pad_topk(s3k: jnp.ndarray, idx: jnp.ndarray, multiple: int,
             neg: float = -1.0e9
             ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad a compressed (L, N, kk) stack to a row multiple with inert
    dummies (the ``pad_similarity`` convention on the top-k layout).

    Dummy rows keep the dense dummies' values — self slot ``neg``,
    neighbors ``2*neg`` — but every neighbor slot *points back at the
    dummy row itself*, so a dummy contributes nothing to any real
    column's statistics (stronger than the dense case: the edges do not
    even reach real columns) and decodes to itself forever. Returns
    ``(padded stack, padded index map, original N)``.
    """
    levels, n, kk = s3k.shape
    pad = (-n) % multiple
    if pad == 0:
        return s3k, idx, n
    s_pad = jnp.full((levels, pad, kk), 2.0 * neg, s3k.dtype)
    s_pad = s_pad.at[:, :, 0].set(neg)
    dummy_rows = jnp.arange(n, n + pad, dtype=idx.dtype)
    idx_pad = jnp.broadcast_to(dummy_rows[:, None], (pad, kk))
    return (jnp.concatenate([s3k, s_pad], axis=1),
            jnp.concatenate([idx, idx_pad], axis=0), n)


def comm_bytes_per_sweep(n: int, k: int, levels: int, workers: int,
                         exchange: str, bytes_per_el: int = 4) -> int:
    """Analytic per-sweep cluster communication volume.

    Both exchanges pay the O(L*N) statistics gathers (base = c + phi per
    level, rdiag + the change counter); allgather additionally moves the
    (N, k+1) rho blocks for every column-statistics evaluation (twice
    per sweep: tau on levels 0..L-2, alpha on all levels), psum an (N,)
    partial each. Ring collectives move ~2*(W-1)/W * payload cluster-wide.
    """
    ring = 2 * (workers - 1) * bytes_per_el
    stats_calls = (levels - 1) + levels            # tau + alpha evaluations
    small = (levels + stats_calls) * n * ring      # base gathers + rdiag/psum
    if exchange == "psum":
        return small + stats_calls * n * ring      # the (N,) partial psums
    return small + stats_calls * n * (k + 1) * ring


# ----------------------------------------------------------------- program
@functools.lru_cache(maxsize=32)
def _sharded_program(mesh, levels: int, n_local: int, n_total: int,
                     n_real: int, kk: int, max_iterations: int,
                     damping: float, kappa: float, s_mode: str, stop: str,
                     patience: int, exchange: str,
                     segmented: bool = False, with_carry: bool = False):
    """Jitted whole-loop shard_map program, cached per mesh/config so
    repeated solves hit XLA's compile cache (the ``_mrhap_program``
    idiom).

    ``segmented`` compiles the checkpoint-segment variant
    (``repro.solver.checkpointing``): an extra replicated (1,) ``until``
    operand bounds the while_loop (dynamic, so every segment of a solve
    reuses ONE compiled program), and the raw loop carry comes back
    instead of the finished contract. ``with_carry`` additionally takes
    the previous segment's carry — sharded state/exemplars plus the
    replicated stable/it/trace — as inputs; two compilations total
    (fresh first segment, resumed segments), regardless of how many
    segment boundaries a solve crosses."""

    def body(s_loc: jnp.ndarray, idx_loc: jnp.ndarray, *rest):
        rows = idx_loc[:, 0]                       # global row ids (self slot)
        if exchange == "allgather":
            idx_full = jax.lax.all_gather(idx_loc, AXIS, axis=0, tiled=True)

        def col_stats(r_l):
            """Full-length (N_total,) availability column sum + rho self
            slots — the one cross-worker reduction in the sweep."""
            if exchange == "allgather":
                r_full = jax.lax.all_gather(r_l, AXIS, axis=0, tiled=True)
                return col_stats_topk(r_full, idx_full)   # oracle scatter
            col = jax.lax.psum(
                col_partial_topk(r_l, idx_loc, n_total), AXIS)
            rdiag = jax.lax.all_gather(r_l[:, 0], AXIS, axis=0, tiled=True)
            return col, rdiag

        def tau_red(r_lv, c_lv):                   # (L-1, B, kk), (L-1, B)
            if levels == 1:
                return jnp.zeros((0, n_local), s_loc.dtype)
            return jnp.stack([
                tau_from_stats(c_lv[l], r_lv[l][:, 0],
                               col_stats(r_lv[l])[0][rows])
                for l in range(levels - 1)])

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
            new = []
            for l in range(levels):                # L static: unrolled
                col, rdiag = col_stats(r[l])
                base = jax.lax.all_gather(c[l] + phi[l], AXIS, axis=0,
                                          tiled=True)
                new.append(alpha_from_stats(r[l], idx_loc, col, base, rdiag))
            return hap._damp(a, jnp.stack(new), damping)

        def sweep(state, it):
            return hap.jacobi_sweep(
                state, it == 0, lam=damping, kappa=kappa, s_mode=s_mode,
                update_r=update_r, update_a=update_a, reducers=reducers)

        def assign(state):
            return jax.vmap(
                lambda al, rl: assignments_topk(al, rl, idx_loc,
                                                n_total=n_total)
            )(state.a, state.r)

        init = hap.hap_init(s_loc)
        # tau/phi/c come out of hap_init as fresh constants; the loop
        # carries device-varying replacements, so mark them up front.
        vary = lambda x: jax.lax.pcast(x, (AXIS,), to="varying")
        init = init._replace(tau=vary(init.tau), phi=vary(init.phi),
                             c=vary(init.c))
        scal = lambda v: vary(jnp.reshape(v, (1,)))

        if not segmented:
            state, e, n_sweeps, conv, trace = dense.drive_sweeps(
                init, sweep, assign, levels, n_local,
                max_iterations=max_iterations, stop=stop, patience=patience,
                count_mask=rows < n_real, axis_name=AXIS)
            return state, e, scal(n_sweeps), scal(conv), vary(trace)[None]

        # segment variant: rest = (until[, carry...]). stable/it/trace
        # stay device-invariant through the loop (the change counter is
        # psum-ed), so the replicated carry inputs match without a pcast.
        until = rest[0][0]
        if with_carry:
            c_state, c_e, c_stable, c_it, c_trace = rest[1:]
            carry = (c_state, c_e, c_stable[0], c_it[0], c_trace)
        else:
            carry = None
        state, e, stable, it, trace = dense.drive_sweeps(
            init, sweep, assign, levels, n_local,
            max_iterations=max_iterations, stop=stop, patience=patience,
            count_mask=rows < n_real, axis_name=AXIS,
            segmented=True, carry=carry, until=until)
        return state, e, scal(stable), scal(it), vary(trace)[None]

    row3 = P(None, AXIS, None)
    row2 = P(None, AXIS)
    state_spec = hap.HAPState(s=row3, r=row3, a=row3,
                              tau=row2, phi=row2, c=row2)
    in_specs = [row3, P(AXIS, None)]
    if segmented:
        in_specs.append(P(None))                   # until
        if with_carry:
            in_specs += [state_spec, row2, P(None), P(None), P(None)]
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(state_spec, row2, P(AXIS), P(AXIS), P(AXIS, None))))


def run_topk_sharded(
    s3k: jnp.ndarray,
    idx: jnp.ndarray,
    mesh,
    *,
    max_iterations: int,
    damping: float = 0.5,
    kappa: float = 0.0,
    s_mode: str = "off",
    stop: str = "fixed",
    patience: int = 5,
    exchange: str = "auto",
    axis_name: str = AXIS,
):
    """Run the sparse Jacobi schedule row-sharded over ``mesh[axis_name]``.

    Same return contract as ``run_topk`` —
    ``(TopKState, exemplars, n_sweeps, converged, trace)`` — with
    exemplars/state in the padded N' (the engine strips dummies);
    assignments match the single-device oracle (under the ``allgather``
    exchange, which keeps its scatter order) and ``stop="converged"``
    exits on the same sweep with the same trace.
    """
    if tuple(mesh.axis_names) != (axis_name,):
        raise ValueError(
            f"sharded sweeps need a 1-D mesh with axis {axis_name!r} "
            f"(got axes {tuple(mesh.axis_names)}); build one with "
            "repro.launch.mesh.make_worker_mesh()")
    s3k = s3k.astype(jnp.float32)
    levels, n, kk = s3k.shape
    w = mesh.shape[axis_name]
    s3k_p, idx_p, n_real = pad_topk(s3k, idx, w)
    n_total = s3k_p.shape[1]
    exchange = resolve_exchange(exchange, n=n_total, kk=kk)
    fn = _sharded_program(
        mesh, levels, n_total // w, n_total, n_real, kk, max_iterations,
        damping, kappa, s_mode, stop, patience, exchange)
    # place row blocks on their owners up front: jit would otherwise
    # first replicate the full stack onto every device
    s3k_p = device_put_row_sharded(s3k_p, mesh, axis_name, axis=1)
    idx_p = device_put_row_sharded(idx_p, mesh, axis_name, axis=0)
    state, e, n_sweeps, conv, trace = fn(s3k_p, idx_p)
    return TopKState(state, idx_p), e, n_sweeps[0], conv[0], trace[0]
