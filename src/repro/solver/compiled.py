"""Reusable AOT-compiled solve handles for the serving path.

``solve()`` traces and compiles per call shape; a service doing that on
the request path pays cold XLA compilation (seconds) against per-request
solve times (milliseconds). ``BatchedDenseSolver`` is the engine hook the
``repro.serve.cluster`` micro-batcher holds instead: one handle per
(batch, n, d) shape bucket, lowered and compiled **once** (explicitly,
via ``jax.jit(...).lower(...).compile()``), then invoked with zero
tracing or compilation on the steady-state path.

Two compiled stages per handle:

* ``prepare``: (B, n, d) padded points + (B,) real counts -> (B, L, n, n)
  similarity stacks. Rows/columns past each request's ``n_real`` are the
  same inert dummies ``pad_similarity`` uses (mutually repelling,
  self-preferring singletons), so a padded solve reproduces the unpadded
  assignment; string preferences ("median"/"range_mid") are computed over
  the *valid* off-diagonal entries only.
* ``solve``: (B, L, n, n) stacks -> per-request exemplars / sweep counts /
  convergence trace, the dense §3 Jacobi schedule under ``vmap``. The
  similarity stack argument is **donated** — it is the same size as each
  message tensor, and XLA aliases it into the solve's state buffers
  instead of holding both live.

The handle is deliberately dense-family-only: micro-batched service
requests are bucket-sized (small N), which is exactly the dense backends'
regime; big-N work belongs to ``solve()`` proper.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.similarity import pairwise_similarity, stack_levels
from repro.solver import dense
from repro.solver.config import SolveConfig

#: dummy-row similarity floor, matching ``repro.core.mrhap.pad_similarity``
PAD_NEG = -1.0e9

#: orders the batched handle can run (``dense_fused``'s Pallas kernels are
#: not vmap-batched; the service maps it to the same-schedule parallel
#: order and reports that order as each response's ``solve.backend``)
_ORDERS = {"dense_sequential": "sequential", "dense_parallel": "parallel",
           "dense_fused": "parallel", "auto": "parallel"}


def batched_order(backend: str) -> str:
    """SolveConfig.backend -> dense sweep order for the batched handle."""
    if backend not in _ORDERS:
        raise ValueError(
            f"the batched serving path runs the dense family only; got "
            f"backend={backend!r} (supported: {sorted(_ORDERS)})")
    return _ORDERS[backend]


def _masked_preference(s, valid, n_real, preference):
    """Preference vector over the valid block of a padded similarity
    matrix. Strings reproduce ``repro.core.preferences`` exactly when
    ``n_real == n`` (same sort, same two order statistics)."""
    n = s.shape[-1]
    if preference is None:
        return jnp.zeros((n,), s.dtype)
    if not isinstance(preference, str):
        return jnp.broadcast_to(jnp.asarray(preference, s.dtype), (n,))
    off = valid[:, None] & valid[None, :] & ~jnp.eye(n, dtype=bool)
    if preference == "median":
        vals = jnp.sort(jnp.where(off, s, jnp.inf).ravel())
        cnt = jnp.maximum(n_real * (n_real - 1), 1)
        lo = jnp.take(vals, (cnt - 1) // 2)
        hi = jnp.take(vals, cnt // 2)
        return jnp.full((n,), 0.5 * (lo + hi), s.dtype)
    if preference == "range_mid":
        smax = jnp.max(jnp.where(off, s, -jnp.inf))
        smin = jnp.min(jnp.where(off, s, jnp.inf))
        return jnp.full((n,), 0.5 * (smin + smax), s.dtype)
    raise ValueError(
        f"batched solves support 'median'/'range_mid'/explicit preferences; "
        f"got {preference!r} (draw 'random' preferences host-side and pass "
        "the array)")


@dataclasses.dataclass(frozen=True)
class BatchedRawResult:
    """Device output of one micro-batch, still bucket-shaped: slice row
    ``i`` and strip to the request's own ``n_real`` to finish it."""
    exemplars: np.ndarray        # (B, L, n) int32
    n_sweeps: np.ndarray         # (B,) int32
    converged: np.ndarray        # (B,) bool
    trace: np.ndarray            # (B, max_iterations) int32, -1 = not run
    preferences: np.ndarray      # (B,) f32 calibrated preference per request


class BatchedDenseSolver:
    """One compiled handle: fixed (batch, n, d), fixed config statics.

    ``compile()`` is the explicit (warmup-time) compilation point —
    nothing else in the object traces or compiles. ``run`` feeds padded
    host arrays through the two compiled executables.
    """

    def __init__(self, batch: int, n: int, d: int, cfg: SolveConfig,
                 device=None):
        if n < 2:
            raise ValueError(f"bucket n must be >= 2 (got {n})")
        self.batch, self.n, self.d = int(batch), int(n), int(d)
        self.cfg = cfg
        self.order = batched_order(cfg.backend)
        # multi-worker serving pins each worker's executables to one
        # device; None keeps jax's default (the single-device case)
        self.device = device
        self._prepare_exec = None
        self._solve_exec = None

    def _device_scope(self):
        import contextlib
        return (contextlib.nullcontext() if self.device is None
                else jax.default_device(self.device))

    # ----------------------------------------------------------- tracing
    def _prepare_fn(self, points, n_real):
        cfg, n = self.cfg, self.n

        def one(pts, nr):
            s = pairwise_similarity(pts, metric=cfg.metric)
            valid = jnp.arange(n) < nr
            s = jnp.where(valid[:, None] & valid[None, :], s, 2.0 * PAD_NEG)
            pref = _masked_preference(s, valid, nr, cfg.preference)
            diag = jnp.where(valid, pref, PAD_NEG)
            s = jnp.where(jnp.eye(n, dtype=bool), diag[:, None], s)
            return stack_levels(s, cfg.levels), pref[0]

        return jax.vmap(one)(points, n_real)

    def _solve_fn(self, s3b):
        cfg = self.cfg

        def one(s3):
            # run_dense inlines here; r/a state outputs are DCE'd. The
            # final similarity state is returned *only* so XLA can alias
            # the donated input stack into it (same shape/dtype) — the
            # caller drops it without ever copying it off device.
            state, e, n_sweeps, conv, trace = dense.run_dense(
                s3, order=self.order, max_iterations=cfg.max_iterations,
                damping=cfg.damping, kappa=cfg.kappa, s_mode=cfg.s_mode,
                stop=cfg.stop, patience=cfg.patience, block=cfg.block)
            return e, n_sweeps, conv, trace, state.s

        return jax.vmap(one)(s3b)

    # --------------------------------------------------------- lifecycle
    @property
    def backend(self) -> str:
        """The dense backend whose sweep order this handle runs."""
        return f"dense_{self.order}"

    @property
    def compiled(self) -> bool:
        return self._solve_exec is not None

    def compile(self) -> "BatchedDenseSolver":
        """Lower + XLA-compile both stages for this bucket shape. The one
        and only compilation point — the request path never traces."""
        b, n, d = self.batch, self.n, self.d
        pts = jax.ShapeDtypeStruct((b, n, d), jnp.float32)
        nr = jax.ShapeDtypeStruct((b,), jnp.int32)
        with self._device_scope():
            self._prepare_exec = jax.jit(self._prepare_fn).lower(
                pts, nr).compile()
            s3 = jax.ShapeDtypeStruct(
                (b, self.cfg.levels, n, n), jnp.float32)
            # donate the stack: XLA aliases it into the solve's state
            self._solve_exec = jax.jit(
                self._solve_fn, donate_argnums=0).lower(s3).compile()
        return self

    # ------------------------------------------------------------- run
    def run(self, points: np.ndarray, n_real: np.ndarray
            ) -> BatchedRawResult:
        """points (B, n, d) f32 (padded), n_real (B,) int32 -> results.

        Raises if ``compile()`` has not run — the service's compile cache
        is the only place allowed to pay compilation.
        """
        if not self.compiled:
            raise RuntimeError(
                "BatchedDenseSolver.run before compile(); warm the "
                "service (ClusterService.warmup) first")
        with self._device_scope():
            s3b, pref = self._prepare_exec(
                jnp.asarray(points, jnp.float32),
                jnp.asarray(n_real, jnp.int32))
            # s3b is donated: the executable owns its buffer from here on
            e, n_sweeps, conv, trace, _s = self._solve_exec(s3b)
        del _s  # device-side alias of the donated stack; never fetched
        return BatchedRawResult(
            exemplars=np.asarray(e), n_sweeps=np.asarray(n_sweeps),
            converged=np.asarray(conv), trace=np.asarray(trace),
            preferences=np.asarray(pref))


def config_static_key(cfg: SolveConfig) -> tuple:
    """The SolveConfig fields a compiled handle specializes on. Two
    configs with equal keys can share one executable; anything not listed
    here (mesh, shard knobs, ...) does not reach the batched dense path."""
    pref = cfg.preference
    if isinstance(pref, (np.ndarray, jnp.ndarray, list, tuple)):
        raise ValueError(
            "per-point preference arrays are request data, not config; "
            "pass a scalar or strategy string to the service")
    return (batched_order(cfg.backend), cfg.levels, cfg.metric, pref,
            cfg.max_iterations, float(cfg.damping), float(cfg.kappa),
            cfg.s_mode, cfg.stop, cfg.patience)


def slice_request(raw: BatchedRawResult, i: int, n_real: int,
                  stop: str) -> "tuple":
    """Row ``i`` of a micro-batch -> the engine's RawBackendResult plus
    the calibrated preference (streams keep it for drift detection)."""
    from repro.solver.result import RawBackendResult

    n_sweeps = int(raw.n_sweeps[i])
    trace: Optional[np.ndarray] = raw.trace[i][:n_sweeps]
    converged = bool(raw.converged[i]) if stop == "converged" else None
    rbr = RawBackendResult(
        exemplars=raw.exemplars[i][:, :n_real], n_sweeps=n_sweeps,
        converged=converged, trace=trace)
    return rbr, float(raw.preferences[i])
