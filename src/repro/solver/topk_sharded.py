"""Row-sharded ``dense_topk`` sweeps: the distributed message-passing loop.

The (L, N, k+1) message tensors are row-sharded over the 1-D
``workers`` mesh, which makes per-device runtime AND per-device state
linear in worker count (the paper's 80-VM experiment, realized on the
compressed layout). The sweep is ``topk.make_topk_sweep``'s and the loop
``topk.sweep_loop``'s, run whole inside ONE ``shard_map``, so a
converged run launches a single device program, not one dispatch per
sweep. A row shard supplies only what differs from one device: its
column exchange (``_exchange``), its mesh axis and its real rows.

Per-sweep dataflow on each worker (B = N/W local rows):

* rho (Eq 2.1), phi (2.5), c (2.6), tau (2.4), the Eq 2.7 refinement
  and the Eq 2.8 decode are row-wise — shard-local, the one-device ops.
* the availability column statistics (Eqs 2.2-2.3) sum max(0, rho)
  over *incoming* edges, whose sources live on other workers. That one
  primitive becomes an explicit exchange (``SolveConfig.exchange``), made
  once a level a sweep in the alpha update, which also all-gathers
  c + phi; each worker carries its rows of the exchanged sums to the
  next sweep's tau, which exchanges nothing:

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
from repro.kernels.topk_ops import col_partial_topk, col_stats_topk
from repro.sharding.partitioning import device_put_row_sharded
from repro.solver.topk import Exchange, TopKState, sweep_loop

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
    (N, k+1) rho blocks for every column-statistics evaluation (once a
    level a sweep, in alpha: tau reads the sums alpha exchanged at the
    previous sweep), psum an (N,) partial each. Ring collectives move
    ~2*(W-1)/W * payload cluster-wide.
    """
    ring = 2 * (workers - 1) * bytes_per_el
    stats_calls = levels                           # alpha's evaluations
    small = (levels + stats_calls) * n * ring      # base gathers + rdiag/psum
    if exchange == "psum":
        return small + stats_calls * n * ring      # the (N,) partial psums
    return small + stats_calls * n * (k + 1) * ring


# ----------------------------------------------------------------- program
def _exchange(kind: str, idx_loc, n_total: int) -> Exchange:
    """A row shard's column exchange (module docstring) for
    ``make_topk_sweep``: one level's statistics, full-length vectors."""
    def gather(v):
        return jax.lax.all_gather(v, AXIS, axis=0, tiled=True)

    if kind == "allgather":
        idx_full = gather(idx_loc)

        def stats(r_l):                            # the oracle's scatter
            return col_stats_topk(gather(r_l), idx_full)
    else:
        def stats(r_l):
            col = jax.lax.psum(col_partial_topk(r_l, idx_loc, n_total), AXIS)
            return col, gather(r_l[:, 0])
    return Exchange(n_total, stats, gather)


@functools.lru_cache(maxsize=32)
def _sharded_program(mesh, n_real: int, exchange: str, **loop):
    """``topk.sweep_loop`` row-sharded in one jitted ``shard_map``,
    cached per mesh/config so repeated solves hit XLA's compile cache
    (the ``_mrhap_program`` idiom); ``loop`` holds ``topk.LOOP_ARGS``.

    Called as ``(s3k, idx)`` it runs the whole loop; given a scalar
    ``until`` (and a ``carry``) one checkpoint segment, as
    ``topk.run_segment`` does (``repro.solver.checkpointing``). jit
    traces each form once, so a checkpointed solve compiles two programs
    (fresh first segment, resumed segments) however many boundaries it
    crosses. The carry's stable/it/trace stay device-invariant (the
    change counter is psum-ed): they go in and out replicated."""

    def program(s3k, idx, until=None, carry=None):
        levels, n_total, _ = s3k.shape

        def body(s_loc, idx_loc, until, carry):
            return sweep_loop(
                s_loc, idx_loc, until, carry, **loop,
                exchange=_exchange(exchange, idx_loc, n_total),
                count_mask=idx_loc[:, 0] < n_real, axis_name=AXIS)

        row3, row2 = P(None, AXIS, None), P(None, AXIS)
        state = hap.HAPState(s=row3, r=row3, a=row3, tau=row2, phi=row2,
                             c=row2, col=None if levels == 1 else row2)
        out = (state, row2, P(), P(), P())
        return jax.shard_map(
            body, mesh=mesh, out_specs=out,
            in_specs=(row3, P(AXIS, None), P(),
                      P() if carry is None else out),
        )(s3k, idx, until, carry)

    return jax.jit(program)


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
):
    """Run the sparse Jacobi schedule row-sharded over ``mesh``'s
    ``workers`` axis.

    Same return contract as ``run_topk`` —
    ``(TopKState, exemplars, n_sweeps, converged, trace)`` — with
    exemplars/state in the padded N' (the engine strips dummies);
    assignments match the single-device oracle (under the ``allgather``
    exchange, which keeps its scatter order) and ``stop="converged"``
    exits on the same sweep with the same trace.
    """
    if tuple(mesh.axis_names) != (AXIS,):
        raise ValueError(
            f"sharded sweeps need a 1-D mesh with axis {AXIS!r} "
            f"(got axes {tuple(mesh.axis_names)}); build one with "
            "repro.launch.mesh.make_worker_mesh()")
    s3k = s3k.astype(jnp.float32)
    kk = s3k.shape[2]
    s3k_p, idx_p, n_real = pad_topk(s3k, idx, mesh.shape[AXIS])
    fn = _sharded_program(
        mesh, n_real, resolve_exchange(exchange, n=s3k_p.shape[1], kk=kk),
        max_iterations=max_iterations, damping=damping, kappa=kappa,
        s_mode=s_mode, stop=stop, patience=patience)
    # place row blocks on their owners up front: jit would otherwise
    # first replicate the full stack onto every device
    s3k_p = device_put_row_sharded(s3k_p, mesh, AXIS, axis=1)
    idx_p = device_put_row_sharded(idx_p, mesh, AXIS, axis=0)
    state, e, n_sweeps, conv, trace = fn(s3k_p, idx_p)
    return TopKState(state, idx_p), e, n_sweeps, conv, trace
