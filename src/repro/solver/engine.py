"""``solve()`` — the single front door for every HAP execution strategy.

    from repro.solver import solve
    res = solve(points)                        # auto backend, 3 levels
    res = solve(s3, backend="mr1d_stats")      # explicit distributed run
    res = solve(points, stop="converged")      # run until assignments stable

The engine owns what call sites used to hand-roll:

* input normalization — (N, d) points, (N, N) similarity, or (L, N, N)
  stacks all accepted; similarity construction (Pallas kernel on the fused
  path) and preference writing happen here;
* backend + mesh selection from N, L, and available devices;
* ``pad_similarity``/unpad when N doesn't divide the mesh — results come
  back in the caller's original N with dummy points stripped;
* the stopping rule — fixed sweep budgets or convergence-driven early
  stopping with a per-sweep assignment-change trace.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.assignments import canonicalize_levels, dense_labels
from repro.core.mrhap import pad_similarity
from repro.core.preferences import make_preferences
from repro.core.similarity import (
    pairwise_similarity, set_preferences, stack_levels,
)
from repro.runtime import degrade, faultinject
from repro.runtime.trace import SPAN_FINALIZE, solve_span, span
from repro.solver.config import SolveConfig
from repro.solver.registry import auto_select, get_backend
from repro.solver.result import RawBackendResult, SolveResult

#: graceful-degradation chain: a backend whose accelerated (Pallas) path
#: raises falls back to the reference backend on the same similarity
#: stack, recording a ``repro.runtime.degrade`` event instead of failing
#: the solve. The two run the identical §3 schedule; only the kernel
#: implementation differs.
DEGRADE_FALLBACKS = {"dense_fused": "dense_parallel"}


# ------------------------------------------------------------- validation
def validate_config(cfg: SolveConfig, n: int) -> None:
    """Reject invalid knob combinations at the front door, with the
    problem size in hand, instead of failing deep inside a backend."""
    if cfg.k is not None:
        if cfg.k < 1:
            raise ValueError(
                f"SolveConfig.k must be >= 1 (got k={cfg.k})")
        if cfg.k >= n:
            raise ValueError(
                f"SolveConfig.k must be < N (got k={cfg.k}, N={n}); "
                "k = N - 1 already stores every off-diagonal entry "
                "(full coverage)")
    if cfg.patience < 0:
        raise ValueError(
            f"SolveConfig.patience must be >= 0 (got {cfg.patience})")
    if cfg.max_iterations < 1:
        raise ValueError(
            "SolveConfig.max_iterations must be >= 1 "
            f"(got {cfg.max_iterations})")
    from repro.solver.topk_build import BUILD_BACKENDS
    if cfg.build not in BUILD_BACKENDS:
        raise ValueError(
            f"SolveConfig.build must be one of {BUILD_BACKENDS}; "
            f"got {cfg.build!r}")
    if cfg.build_block_rows < 1 or cfg.build_block_cols < 1 \
            or cfg.build_chunk < 1:
        raise ValueError(
            "SolveConfig.build_block_rows/build_block_cols/build_chunk "
            f"must be >= 1 (got {cfg.build_block_rows}/"
            f"{cfg.build_block_cols}/{cfg.build_chunk})")
    from repro.solver.topk_sharded import EXCHANGE_MODES, SWEEP_MODES
    if cfg.sweep not in SWEEP_MODES:
        raise ValueError(
            f"SolveConfig.sweep must be one of {SWEEP_MODES}; "
            f"got {cfg.sweep!r}")
    if cfg.exchange not in EXCHANGE_MODES:
        raise ValueError(
            f"SolveConfig.exchange must be one of {EXCHANGE_MODES}; "
            f"got {cfg.exchange!r}")
    if cfg.graph_rounds is not None and cfg.graph_rounds < 1:
        raise ValueError(
            "SolveConfig.graph_rounds must be >= 1 "
            f"(got {cfg.graph_rounds}); None lets the backend run "
            "ceil(log2 N) + 1 contraction rounds")
    if (cfg.graph_target_clusters is not None
            and cfg.graph_target_clusters < 1):
        raise ValueError(
            "SolveConfig.graph_target_clusters must be >= 1 "
            f"(got {cfg.graph_target_clusters}); None runs the "
            "contraction to connected components")
    if cfg.preseed not in ("off", "graph"):
        raise ValueError(
            "SolveConfig.preseed must be 'off' or 'graph'; "
            f"got {cfg.preseed!r}")
    if cfg.checkpoint_every < 0:
        raise ValueError(
            "SolveConfig.checkpoint_every must be >= 0 "
            f"(got {cfg.checkpoint_every}); 0 disables checkpointing")
    if cfg.checkpoint_every > 0 and not cfg.checkpoint_dir:
        raise ValueError(
            "SolveConfig.checkpoint_every > 0 needs checkpoint_dir to "
            "write the snapshots into")
    if cfg.backend == "coarsen":
        from repro.solver.coarsen import check_coarsen_config
        check_coarsen_config(cfg)


# ------------------------------------------------------------------ input
def _normalize_input(data, cfg: SolveConfig):
    """-> (points, similarity stack, edge list, original N) — exactly one
    of the first three is non-None."""
    from repro.graph.edges import EdgeList
    if isinstance(data, EdgeList):
        return None, None, data, data.n_nodes
    arr = np.asarray(data) if not isinstance(data, jnp.ndarray) else data
    if arr.ndim == 3:
        if arr.shape[1] != arr.shape[2]:
            raise ValueError(f"3-D input must be (L, N, N); got {arr.shape}")
        if cfg.input_kind == "points":
            raise ValueError("input_kind='points' requires a 2-D (N, d) array")
        return None, jnp.asarray(arr), None, arr.shape[1]
    if arr.ndim != 2:
        raise ValueError(f"expected 2-D or 3-D input; got ndim={arr.ndim}")
    kind = cfg.input_kind
    if kind == "auto":
        kind = "similarity" if arr.shape[0] == arr.shape[1] else "points"
    if kind == "similarity":
        if arr.shape[0] != arr.shape[1]:
            raise ValueError(f"similarity matrix must be square; {arr.shape}")
        return (None, stack_levels(jnp.asarray(arr), cfg.levels), None,
                arr.shape[0])
    return np.asarray(arr, np.float32), None, None, arr.shape[0]


def _densify_edges(el, cfg: SolveConfig):
    """EdgeList -> (L, N, N) stack for backends without native edge
    support: missing entries take the inert fill (strictly below every
    stored weight), the diagonal takes ``cfg.preference`` resolved over
    the stored edge weights (``None`` means "median" here — the dense
    points path's untouched-diagonal-0 convention has no meaning for a
    graph whose weights live at an arbitrary magnitude)."""
    pref = cfg.preference if cfg.preference is not None else "median"
    s = set_preferences(jnp.asarray(el.to_dense()),
                        jnp.asarray(el.edge_preferences(pref, seed=cfg.seed)))
    return stack_levels(s, cfg.levels)


def _build_similarity(x: np.ndarray, cfg: SolveConfig, backend: str):
    """Points -> (L, N, N) stack with preferences on the diagonal."""
    xj = jnp.asarray(x)
    if backend == "dense_fused" and cfg.metric == "neg_sqeuclidean":
        # the fused path builds S with the Pallas similarity kernel too;
        # a platform that rejects the kernel degrades to the jnp build
        from repro.kernels import ops
        try:
            s = ops.neg_sqeuclidean(xj, block=cfg.block)
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            degrade.record("build.neg_sqeuclidean_pallas",
                           "pairwise_similarity", exc)
            s = pairwise_similarity(xj, metric=cfg.metric)
    else:
        s = pairwise_similarity(xj, metric=cfg.metric)
    pref = cfg.preference
    if pref is None and cfg.preseed != "graph":
        return stack_levels(s, cfg.levels)
    if isinstance(pref, str):
        pref = make_preferences(s, pref, key=jax.random.PRNGKey(cfg.seed))
    if cfg.preseed == "graph":
        # seed the preference vector from a cheap Borůvka pass over the
        # matrix's top-k graph (dense path: the matrix already exists, so
        # compressing it here costs no extra build)
        from repro.graph.affinity import preseed_preferences
        from repro.kernels.topk_similarity import topk_from_dense
        from repro.solver.topk import resolve_k
        vals, idx = topk_from_dense(s, resolve_k(cfg.k, s.shape[0]))
        pref = preseed_preferences(
            vals, idx, 0.0 if pref is None else pref,
            target=cfg.graph_target_clusters, max_rounds=cfg.graph_rounds)
    s = set_preferences(s, pref)
    return stack_levels(s, cfg.levels)


# ------------------------------------------------------------------- mesh
def _factor_2d(ndev: int) -> tuple[int, int]:
    rows = max(int(math.isqrt(ndev)), 1)
    while ndev % rows:
        rows -= 1
    return rows, ndev // rows


def _prepare_mesh(kind, cfg: SolveConfig):
    """-> (mesh, pad multiple) for distributed execution.

    ``kind`` is ``"1d"`` / ``"2d"`` or a BackendSpec carrying
    ``mesh_kind`` — the sharded top-k build and sweep drivers pass the
    string directly (they shard rows over a 1-D worker mesh without
    being registered mesh backends themselves)."""
    from repro.launch.mesh import (
        make_mesh, make_worker_mesh, maybe_init_distributed,
    )

    # multi-process launches (env-var-described) must join the cluster
    # before the first mesh is built so jax.devices() spans every host;
    # single-process runs this is a strict no-op
    maybe_init_distributed()

    if not isinstance(kind, str):
        kind = kind.mesh_kind
    mesh = cfg.mesh
    if kind == "1d":
        if mesh is None:
            mesh = make_worker_mesh()
        # run_mrhap's collectives are written against these axis names
        if tuple(mesh.axis_names) != ("workers",):
            raise ValueError(
                "mr1d backends need a 1-D mesh with axis 'workers' "
                f"(got axes {tuple(mesh.axis_names)}); build one with "
                "repro.launch.mesh.make_worker_mesh()")
        multiple = mesh.shape["workers"]
    else:  # "2d"
        if mesh is None:
            rows, cols = _factor_2d(len(jax.devices()))
            mesh = make_mesh((rows, cols), ("rows", "cols"),
                             devices=jax.devices()[: rows * cols])
        if tuple(mesh.axis_names) != ("rows", "cols"):
            raise ValueError(
                "mr2d needs a 2-D mesh with axes ('rows', 'cols') "
                f"(got axes {tuple(mesh.axis_names)})")
        multiple = math.lcm(mesh.shape["rows"], mesh.shape["cols"])
    if cfg.pad_to:
        multiple = math.lcm(multiple, cfg.pad_to)
    return mesh, multiple


# ------------------------------------------------------------------ solve
def solve(data, config: Optional[SolveConfig] = None,
          **overrides: Any) -> SolveResult:
    """Cluster ``data`` hierarchically with the configured backend.

    ``data``: (N, d) points, (N, N) similarity matrix (diagonal =
    preferences, caller-owned), (L, N, N) per-level similarity stack, or
    a ``repro.graph.EdgeList`` (routed natively to edge-capable backends,
    densified with inert fill for the rest).
    Keyword overrides patch ``config`` field-by-field:
    ``solve(x, backend="mr2d", max_iterations=80)``.
    """
    cfg = config or SolveConfig()
    if overrides:
        cfg = cfg.replace(**overrides)

    x, s3, el, n = _normalize_input(data, cfg)
    validate_config(cfg, n)

    backend = cfg.backend
    if backend == "auto":
        backend = auto_select(
            n, cfg.levels, n_devices=cfg.device_count(),
            has_points=x is not None, platform=jax.default_backend(),
            cfg=cfg, has_edges=el is not None)
    spec = get_backend(backend)

    if cfg.checkpoint_every > 0 or cfg.resume_from:
        from repro.solver.checkpointing import CHECKPOINT_BACKENDS
        if backend not in CHECKPOINT_BACKENDS:
            raise ValueError(
                f"checkpoint/resume is supported by {CHECKPOINT_BACKENDS} "
                f"(the long-running paths), not backend {backend!r}; drop "
                "checkpoint_every/resume_from or pick a supported backend")

    if spec.needs_points and x is None:
        hint = (" — an EdgeList carries no point coordinates"
                if el is not None else "")
        raise ValueError(
            f"backend {backend!r} clusters raw points (it never builds the "
            f"global similarity matrix); pass an (N, d) array{hint}")
    if cfg.stop == "converged" and not spec.supports_early_stop:
        raise ValueError(
            f"backend {backend!r} runs a fixed distributed sweep schedule "
            "and does not support stop='converged'; use stop='fixed' or a "
            "dense backend")
    if cfg.preseed == "graph":
        if backend == "graph_affinity":
            raise ValueError(
                "preseed='graph' seeds a HAP backend's preferences with a "
                "graph pass; backend='graph_affinity' IS the graph pass — "
                "drop one of the two")
        if x is None:
            raise ValueError(
                "preseed='graph' re-derives preferences from the top-k "
                "graph the engine builds; it requires (N, d) point input")
        if spec.needs_points:
            raise ValueError(
                f"backend {backend!r} does not consume a per-point "
                "preference array, which is what preseed='graph' "
                "produces; use a dense or dense_topk backend")

    with solve_span(backend, n):
        raw = _dispatch(spec, backend, cfg, x, s3, el)
        with span(SPAN_FINALIZE):
            return _finalize(raw, n, backend)


def _dispatch(spec, backend: str, cfg: SolveConfig, x, s3, el
              ) -> RawBackendResult:
    """Hand the normalized input to the backend in the form it takes."""
    if el is not None and spec.accepts_edges:
        return spec.run(el, cfg)
    if spec.needs_points:
        return spec.run(x, cfg)
    if spec.accepts_points and x is not None and s3 is None:
        # points-capable backend (dense_topk, graph_affinity): hand it the
        # raw points so its own (compressed) similarity build runs and the
        # dense N x N matrix is never materialized here
        return spec.run(x, cfg)
    if s3 is None:
        s3 = (_densify_edges(el, cfg) if el is not None
              else _build_similarity(x, cfg, backend))
    if spec.mesh_kind:
        mesh, multiple = _prepare_mesh(spec, cfg)
        s3, _ = pad_similarity(s3, multiple)
        return spec.run(s3, cfg.replace(mesh=mesh))
    return _run_degradable(spec, s3, cfg, backend)


def _run_degradable(spec, s3, cfg: SolveConfig, backend: str
                    ) -> RawBackendResult:
    """Run a similarity-stack backend with the graceful-degradation
    chain: if its accelerated path raises and ``DEGRADE_FALLBACKS`` maps
    it to a reference backend, record the event and re-run there —
    same stack, same schedule, solve succeeds. The ``solver.backend``
    faultinject site makes the chain deterministically testable."""
    fallback = DEGRADE_FALLBACKS.get(backend)
    try:
        faultinject.fire("solver.backend", backend=backend)
        return spec.run(s3, cfg)
    except Exception as exc:  # noqa: BLE001 — degrade, don't fail
        if fallback is None:
            raise
        degrade.record(f"backend.{backend}", fallback, exc)
        return get_backend(fallback).run(s3, cfg)


def finalize_raw(raw: RawBackendResult, n: int, backend: str) -> SolveResult:
    """Public engine hook: turn a backend's raw output into a
    ``SolveResult`` (strip padding, canonicalize, relabel). The serve-path
    micro-batcher runs backends through its own compiled handles and
    finishes each request here, so service results and ``solve()`` results
    are the same type with the same conventions."""
    return _finalize(raw, n, backend)


def _finalize(raw: RawBackendResult, n: int, backend: str) -> SolveResult:
    """Strip padding dummies, canonicalize, relabel, count clusters."""
    e = np.asarray(raw.exemplars)[:, :n]
    levels = e.shape[0]
    # dummies repel real points, so a real point never selects one; after
    # the strip every exemplar index is < n and canonicalization is closed.
    e = canonicalize_levels(e)
    labels = np.zeros_like(e, dtype=np.int32)
    counts = np.zeros((levels,), np.int32)
    for l in range(levels):
        labels[l], counts[l] = dense_labels(e[l])
    trace = (np.asarray(raw.trace, dtype=np.int32) if raw.trace is not None
             else np.zeros((0,), np.int32))
    return SolveResult(
        exemplars=e.astype(np.int32), n_clusters=counts, labels=labels,
        levels=levels, n=n, backend=backend, n_sweeps=int(raw.n_sweeps),
        converged=raw.converged, trace=trace, state=raw.state)
