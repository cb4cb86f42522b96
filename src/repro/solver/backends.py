"""The registered backends: every pre-engine entry point, adapted.

Importing this module populates the registry (``registry.get_backend``
does so lazily). Each adapter receives input the engine already prepared
(similarity stack padded to the mesh tile, or raw points) plus the full
``SolveConfig``, and returns a ``RawBackendResult`` the engine finishes
(strip padding, canonicalize, relabel).

Backend table
=============
dense_sequential   Alg. 1 as printed (Gauss-Seidel over levels), 1 device
dense_parallel     §3 Jacobi schedule, XLA-fused jnp sweeps, 1 device
dense_fused        §3 Jacobi schedule, Pallas responsibility/availability
                   kernels in the per-level hot loop (TPU-native)
dense_topk         §3 Jacobi schedule on top-k-per-row sparse
                   similarities; O(L*N*k) state, exact at k = N-1
mr1d_stats         shard_map over a 1-D mesh, O(L*N) stats communication
mr1d_transpose     paper-faithful shuffles (distributed transposes),
                   O(L*N^2/W) communication
mr2d               2-D tile decomposition (lifts the M <= L*N ceiling)
sharded_streaming  two-tier shard-local AP, O((N/S)^2) peak state
coarsen            kd-partition -> batched local dense solves -> global
                   exemplar solve; the N=1e7-on-one-host route
graph_affinity     Borůvka min-edge/contract affinity clustering over
                   an EdgeList (or the built top-k graph); O(N*k) per
                   round, ~log N rounds
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.mrhap import run_mrhap, run_mrhap_2d
from repro.core.streaming import streaming_hap
from repro.runtime.trace import (
    SPAN_BUILD, SPAN_LAYOUT, SPAN_SWEEPS, record_edge_layout, span,
)
from repro.solver import dense
from repro.solver.config import SolveConfig
from repro.solver.registry import BackendSpec, register_backend
from repro.solver.result import RawBackendResult


# ------------------------------------------------------------ dense family
def _dense_runner(order: str):
    def run(s3, cfg: SolveConfig) -> RawBackendResult:
        state, e, n_sweeps, conv, trace = dense.run_dense(
            s3, order=order, max_iterations=cfg.max_iterations,
            damping=cfg.damping, kappa=cfg.kappa, s_mode=cfg.s_mode,
            stop=cfg.stop, patience=cfg.patience, block=cfg.block)
        n_sweeps = int(n_sweeps)
        converged = bool(conv) if cfg.stop == "converged" else None
        return RawBackendResult(
            exemplars=e, n_sweeps=n_sweeps, converged=converged,
            trace=np.asarray(trace)[:n_sweeps],
            state=state if cfg.keep_state else None)
    return run


register_backend(BackendSpec(
    name="dense_sequential", run=_dense_runner("sequential"),
    supports_early_stop=True,
    doc="Alg. 1 Gauss-Seidel dense sweeps (single device)"))

register_backend(BackendSpec(
    name="dense_parallel", run=_dense_runner("parallel"),
    supports_early_stop=True,
    doc="MR Jacobi schedule, XLA-fused dense sweeps (single device)"))

register_backend(BackendSpec(
    name="dense_fused", run=_dense_runner("fused"),
    supports_early_stop=True,
    doc="MR Jacobi schedule with Pallas kernels in the hot loop"))


# ------------------------------------------------------------ sparse top-k
def _topk_run(data, cfg: SolveConfig) -> RawBackendResult:
    """Compressed-layout Jacobi sweeps; O(L*N*k) state instead of
    O(L*N^2). Accepts raw points (tiled top-k build, the N x N matrix is
    never materialized), a similarity stack (row-wise compression), or an
    ``EdgeList`` (already the compressed layout — dedup + pad, never
    densify). ``cfg.sweep`` routes the loop itself: single-device, or
    row-sharded over the workers mesh (``repro.solver.topk_sharded``).
    The single-device loop takes an edge list in row blocks of like
    degree (``EdgeList.to_blocks``); its exemplars come back in the
    caller's node ids."""
    mode, mesh = _sweep_route(data, cfg)
    blocked = mode == "single" and not _checkpointed(cfg)
    with span(SPAN_BUILD):
        s3k, idx, n, nodes = _topk_layout(data, cfg, blocked)
    with span(SPAN_SWEEPS):
        state, e, n_sweeps, conv, trace = _topk_sweeps(s3k, idx, cfg,
                                                       mode, mesh)
        n_sweeps = int(n_sweeps)
        trace = np.asarray(trace)[:n_sweeps]
    if nodes is not None:
        e_laid = np.asarray(e)
        e = np.empty_like(e_laid)
        e[:, nodes] = nodes[e_laid]
        state = state._replace(nodes=nodes)
    converged = bool(conv) if cfg.stop == "converged" else None
    return RawBackendResult(
        exemplars=e, n_sweeps=n_sweeps, converged=converged, trace=trace,
        state=state if cfg.keep_state else None)


def _checkpointed(cfg: SolveConfig) -> bool:
    return cfg.checkpoint_every > 0 or bool(cfg.resume_from)


def _topk_layout(data, cfg: SolveConfig, blocked: bool):
    """-> (value stack, column map, N, nodes): an (L, N, kk) stack and
    its (N, kk) map, or, for an edge list ``blocked`` into more than one
    block, tuples of (L, N_b, kk_b) stacks and (N_b, kk_b) maps with
    ``nodes`` the original id of each laid-out row (else None)."""
    import jax

    from repro.graph.edges import EdgeList
    from repro.solver import topk

    if isinstance(data, EdgeList):
        with span(SPAN_LAYOUT):
            return _edge_layout(data, cfg, blocked)
    arr = jnp.asarray(data)
    n = arr.shape[1] if arr.ndim == 3 else arr.shape[0]
    k = topk.resolve_k(cfg.k, n)
    if arr.ndim == 3:
        s3k, idx = topk.compress_stack(arr, k)
    else:
        s3k, idx = topk.build_from_points(
            arr, k, cfg.levels, metric=cfg.metric,
            preference=cfg.preference,
            key=jax.random.PRNGKey(cfg.seed), config=cfg)
    return s3k, idx, n, None


def _edge_layout(el, cfg: SolveConfig, blocked: bool):
    """The edge list's layout on the host, then on the device; records
    the ``edge_layout.*`` counters (``repro.runtime.trace``)."""
    from repro.graph.edges import EdgeBlocks
    from repro.solver import topk

    t0 = time.perf_counter()
    el = el.without_self_loops().deduplicated()
    n = el.n_nodes
    # an edge list brings its own sparsity: keep every stored edge
    # unless cfg.k asks for a tighter (weight desc, dst asc) cut
    if blocked:
        lay = el.to_blocks(cfg.k)
    else:
        # the sharded and checkpointed sweeps take one block, as wide as
        # the widest row
        k = (topk.resolve_k(cfg.k, n) if cfg.k is not None
             else max(1, min(el.max_degree, n - 1)))
        lay = EdgeBlocks.single(*el.to_topk(k))
    pref = el.edge_preferences(
        cfg.preference if cfg.preference is not None else "median",
        seed=cfg.seed)[lay.nodes]
    s3k, idx = [], []
    for v, i, lo, hi in zip(lay.vals, lay.idx, lay.offsets,
                            lay.offsets[1:]):
        s_rows, i_full = topk._with_self_slot(
            jnp.asarray(v), jnp.asarray(i), jnp.asarray(pref[lo:hi]),
            start=int(lo))
        s3k.append(jnp.broadcast_to(s_rows[None],
                                    (cfg.levels, *s_rows.shape)))
        idx.append(i_full)
    record_edge_layout(buckets=len(lay.vals), entries=lay.entries,
                       padded=lay.padded, max_degree=lay.max_degree,
                       host_s=time.perf_counter() - t0)
    if len(s3k) == 1:
        return s3k[0], idx[0], n, None
    return tuple(s3k), tuple(idx), n, lay.nodes


def _sweep_route(data, cfg: SolveConfig):
    """-> (``"single"`` | ``"sharded"``, the workers mesh or None): the
    sweep loop ``cfg.sweep`` picks for this input."""
    from repro.graph.edges import EdgeList
    from repro.solver import topk_sharded

    if isinstance(data, EdgeList):
        n = data.n_nodes
    else:
        shape = np.shape(data)
        n = shape[1] if len(shape) == 3 else shape[0]
    mode = topk_sharded.resolve_sweep(cfg.sweep, n=n,
                                      n_devices=cfg.device_count())
    if mode != "sharded":
        return mode, None
    from repro.solver.engine import _prepare_mesh
    mesh, _ = _prepare_mesh("1d", cfg)
    if mesh.shape["workers"] == 1:
        # a 1-worker shard_map pays collective/dispatch overhead to
        # shard nothing (the build had the same regression) — the
        # single-device loop is the same arithmetic, minus the detour
        return "single", None
    return mode, mesh


def _topk_sweeps(s3k, idx, cfg: SolveConfig, mode: str, mesh):
    """The sweep loop of ``_sweep_route`` and the checkpoint settings;
    -> ``(state, exemplars, n_sweeps, converged, trace)``."""
    from repro.solver import topk, topk_sharded

    if _checkpointed(cfg):
        from repro.solver import checkpointing
        return checkpointing.run_topk_checkpointed(s3k, idx, cfg, mesh=mesh)
    if mode == "sharded":
        return topk_sharded.run_topk_sharded(
            s3k, idx, mesh, max_iterations=cfg.max_iterations,
            damping=cfg.damping, kappa=cfg.kappa, s_mode=cfg.s_mode,
            stop=cfg.stop, patience=cfg.patience, exchange=cfg.exchange)
    return topk.run_topk(
        s3k, idx, max_iterations=cfg.max_iterations, damping=cfg.damping,
        kappa=cfg.kappa, s_mode=cfg.s_mode, stop=cfg.stop,
        patience=cfg.patience)


register_backend(BackendSpec(
    name="dense_topk", run=_topk_run, accepts_points=True,
    accepts_edges=True, supports_early_stop=True,
    doc="top-k-per-row sparse similarities; O(L*N*k) state, exact at "
        "k=N-1"))


# ------------------------------------------------------- graph affinity
def _graph_run(data, cfg: SolveConfig) -> RawBackendResult:
    """Borůvka-style affinity clustering (``repro.graph.affinity``).
    Accepts an ``EdgeList`` natively; points go through the standard
    top-k build first, a similarity stack through row compression — in
    both cases the resulting directed top-k graph is canonicalized
    (self-loops dropped, symmetrized, deduplicated) before contraction.
    ``cfg.sweep`` routes the round loop single-device or row-sharded
    over the workers mesh; the two are bit-identical."""
    import jax

    from repro.graph import affinity
    from repro.graph.edges import EdgeList
    from repro.solver import topk, topk_sharded

    if isinstance(data, EdgeList):
        el = data
    else:
        arr = jnp.asarray(data)
        if arr.ndim == 3:
            from repro.kernels.topk_similarity import topk_from_dense
            n0 = arr.shape[-1]
            vals, idx = topk_from_dense(arr[0], topk.resolve_k(cfg.k, n0))
            el = EdgeList.from_topk(np.asarray(vals), np.asarray(idx))
        else:
            el = EdgeList.from_points(
                arr, topk.resolve_k(cfg.k, arr.shape[0]),
                config=cfg.replace(metric=cfg.metric))
    el = el.canonical()
    n = el.n_nodes
    vals, idx = el.to_topk()

    mesh = None
    if topk_sharded.resolve_sweep(
            cfg.sweep, n=n, n_devices=cfg.device_count()) == "sharded":
        from repro.solver.engine import _prepare_mesh
        mesh, _ = _prepare_mesh("1d", cfg)
        if mesh.shape["workers"] == 1:
            mesh = None          # same 1-worker-detour rule as _topk_run

    hist, r, conv, trace = affinity.run_graph_affinity(
        vals, idx, levels=cfg.levels, max_rounds=cfg.graph_rounds,
        target=cfg.graph_target_clusters or 1, mesh=mesh)
    r = int(r)
    return RawBackendResult(
        exemplars=hist, n_sweeps=r, converged=bool(conv),
        trace=np.asarray(trace)[:r], state=None)


register_backend(BackendSpec(
    name="graph_affinity", run=_graph_run, accepts_points=True,
    accepts_edges=True, supports_early_stop=True,
    doc="Borůvka min-edge/contract affinity clustering over an edge "
        "list; O(N*k) per round, ~log N rounds"))


# ------------------------------------------------------------- MR family
def _mr1d_runner(comm_mode: str):
    def run(s3, cfg: SolveConfig) -> RawBackendResult:
        res = run_mrhap(s3, cfg.mesh, iterations=cfg.max_iterations,
                        damping=cfg.damping, comm_mode=comm_mode)
        return RawBackendResult(
            exemplars=res.exemplars, n_sweeps=cfg.max_iterations,
            converged=None, trace=None)
    return run


register_backend(BackendSpec(
    name="mr1d_stats", run=_mr1d_runner("stats"), mesh_kind="1d",
    doc="1-D row sharding, O(L*N) statistics communication"))

register_backend(BackendSpec(
    name="mr1d_transpose", run=_mr1d_runner("transpose"), mesh_kind="1d",
    doc="paper-faithful distributed transposes, O(L*N^2/W) communication"))


def _mr2d_run(s3, cfg: SolveConfig) -> RawBackendResult:
    res = run_mrhap_2d(s3, cfg.mesh, iterations=cfg.max_iterations,
                       damping=cfg.damping)
    return RawBackendResult(
        exemplars=res.exemplars, n_sweeps=cfg.max_iterations,
        converged=None, trace=None)


register_backend(BackendSpec(
    name="mr2d", run=_mr2d_run, mesh_kind="2d",
    doc="2-D tile decomposition over rows x cols mesh axes"))


# ----------------------------------------------------------- streaming
def _streaming_run(x, cfg: SolveConfig) -> RawBackendResult:
    res = streaming_hap(
        np.asarray(x), shard_size=cfg.shard_size,
        iterations=cfg.max_iterations, damping=cfg.damping,
        pref_scale=cfg.pref_scale, seed=cfg.seed)
    # two internal tiers collapse to one output level: each point's final
    # exemplar (its shard exemplar's top-level exemplar)
    return RawBackendResult(
        exemplars=res.exemplar_of[None, :], n_sweeps=cfg.max_iterations,
        converged=None, trace=None)


register_backend(BackendSpec(
    name="sharded_streaming", run=_streaming_run, needs_points=True,
    doc="two-tier shard-local AP; O((N/S)^2) state, single output level"))


# ------------------------------------------------------------- coarsen
def _coarsen_run(x, cfg: SolveConfig) -> RawBackendResult:
    from repro.solver.coarsen import run_coarsen
    return run_coarsen(x, cfg)


register_backend(BackendSpec(
    name="coarsen", run=_coarsen_run, needs_points=True,
    supports_early_stop=True,
    doc="two-level kd-partition -> batched local dense solves -> global "
        "exemplar solve; O(partition_size^2 * batch) peak state"))
