"""Solver checkpoint/resume: segmented sweeps + per-stage artifacts.

The paper's platform (Hadoop) restarts failed tasks for free; this
module closes that gap for the two long-running backends
(``SolveConfig.checkpoint_every`` / ``checkpoint_dir`` /
``resume_from``):

* **dense_topk** (single-device and ``sweep="sharded"``) — one segment
  runner (``_run_segments``) for both placements: the Jacobi loop runs
  as *segments* of the same ``lax.while_loop`` (``topk.sweep_loop`` with
  an ``until``); between segments the host snapshots the compressed
  message state, the column sums the loop carries from alpha to the
  next sweep's tau (``col``) and the sweep index through
  ``repro.checkpoint``. A placement supplies its segment program
  (``topk.run_segment``, or the sharded program given ``until``), how a
  carry is saved and how a saved one is restored. The segment bound
  ``until`` is a *dynamic* operand, so a whole solve compiles exactly
  two programs (fresh first segment, resumed segments) no matter how
  many boundaries it crosses. Because checkpointed runs always execute
  the segmented program, an interrupted-and-resumed run and an
  uninterrupted checkpointed run are the *same op sequence with the
  same inputs* — resume is bit-exact by construction, and the tests
  additionally assert equality against the plain un-checkpointed solve.

* **sharded sweeps** save the *unpadded logical* state. On resume the
  rows are re-padded with fresh inert dummies (``_repad_carry``;
  ``pad_topk``'s dummies only self-reference, and the change counter
  masks them out), so real rows evolve bit-identically even though
  dummy rows restart — and a resume onto a different worker count would
  even be legal, though the engine currently resumes onto the same
  mesh.

* **coarsen** — per-stage artifacts instead of sweep segments: the
  deterministic kd partition is recomputed, the local-solve loop
  snapshots its exemplar/mass prefix every ``checkpoint_every`` batch
  groups, and the global stage saves its solution — so a crash during
  the broadcast-assign stage resumes *after* the global solve, not
  from zero.

Every checkpoint directory carries a ``solve_meta.json`` sidecar with
the run's config/shape key; ``resume_from`` refuses a mismatched run
rather than silently diverging. Its ``carry`` entry names the loop
state saved beyond the messages, so a ``dense_topk`` checkpoint written
before the carried ``col`` existed is refused too. Crash points are
exercised deterministically via ``repro.runtime.faultinject`` (sites
``solver.sweep`` / ``solver.coarsen``), fired *after* each save so an
injected crash always leaves a resumable directory.
"""
from __future__ import annotations

import functools
import json
import os
import shutil
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import CheckpointManager, restore_tree, save_tree
from repro.core import hap
from repro.runtime import faultinject
from repro.sharding.partitioning import device_put_row_sharded
from repro.solver import topk
from repro.solver import topk_sharded as ts
from repro.solver.config import SolveConfig
from repro.solver.topk import TopKState

META_NAME = "solve_meta.json"

#: checkpointable backends — validated at solve() entry
CHECKPOINT_BACKENDS = ("dense_topk", "coarsen")


# ------------------------------------------------------------- meta sidecar
def write_meta(directory: str, meta: dict) -> None:
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, META_NAME), "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)


def check_meta(directory: str, meta: dict) -> None:
    """Refuse to resume a directory written by a different run shape."""
    path = os.path.join(directory, META_NAME)
    if not os.path.exists(path):
        raise ValueError(
            f"resume_from={directory!r} has no {META_NAME}: not a solver "
            "checkpoint directory (or the initial save never completed)")
    with open(path) as f:
        stored = json.load(f)
    if stored != meta:
        diff = {k: (stored.get(k), meta.get(k))
                for k in sorted(set(stored) | set(meta))
                if stored.get(k) != meta.get(k)}
        raise ValueError(
            "checkpoint/config mismatch — refusing to resume "
            f"{directory!r}; differing keys (stored, requested): {diff}")


def reset_dir(directory: str) -> None:
    """Fresh checkpointed run: clear any previous run's artifacts so a
    later resume can't mix runs."""
    if not os.path.isdir(directory):
        return
    for name in os.listdir(directory):
        if name == META_NAME or name.startswith("step_") \
                or name in ("local", "global"):
            full = os.path.join(directory, name)
            if os.path.isdir(full):
                shutil.rmtree(full, ignore_errors=True)
            else:
                os.remove(full)


def _topk_meta(kind: str, n: int, kk: int, cfg: SolveConfig,
               workers: int, exchange: Optional[str]) -> dict:
    return {
        "kind": kind, "n": n, "kk": kk, "levels": cfg.levels,
        "max_iterations": cfg.max_iterations, "damping": cfg.damping,
        "kappa": cfg.kappa, "s_mode": cfg.s_mode, "stop": cfg.stop,
        "patience": cfg.patience, "workers": workers, "exchange": exchange,
        "carry": ["col"],
    }


# ------------------------------------------------------------ sweep segments
def _carry_tree(state: hap.HAPState, e, stable, it, trace) -> dict:
    return {"s": state.s, "r": state.r, "a": state.a, "tau": state.tau,
            "phi": state.phi, "c": state.c, "col": state.col, "e_prev": e,
            "stable": stable, "it": it, "trace": trace}


def _carry_like(levels: int) -> dict:
    """The saved tree's structure: ``col`` is None (no leaf) at L = 1."""
    z = np.int32(0)
    like = {k: z for k in ("s", "r", "a", "tau", "phi", "c", "col",
                           "e_prev", "stable", "it", "trace")}
    if levels == 1:
        like["col"] = None
    return like


def _restored_carry(restored: dict):
    """A saved tree -> the loop carry ``(state, e_prev, stable, it,
    trace)`` of one device."""
    state = hap.HAPState(*(
        None if restored[f] is None else jnp.asarray(restored[f])
        for f in hap.HAPState._fields))
    return (state, jnp.asarray(restored["e_prev"]),
            jnp.int32(restored["stable"]), jnp.int32(restored["it"]),
            jnp.asarray(restored["trace"]))


def _is_done(it: int, stable: int, cfg: SolveConfig) -> bool:
    return it >= cfg.max_iterations or (
        cfg.stop == "converged" and stable >= cfg.patience)


def run_topk_checkpointed(s3k, idx, cfg: SolveConfig, *, mesh=None):
    """Checkpoint-aware replacement for ``run_topk``/``run_topk_sharded``.

    Same return contract: ``(TopKState, exemplars, n_sweeps, converged,
    trace)`` (exemplars in the padded N' when sharded — the engine
    strips dummies). A placement supplies its segment program
    ``(until, carry) -> carry``, how a carry is saved and how a saved
    one is restored; ``_run_segments`` drives both."""
    s3k = s3k.astype(jnp.float32)
    _, n, kk = s3k.shape
    loop = {k: getattr(cfg, k) for k in topk.LOOP_ARGS}
    if mesh is None:
        kind, meta = "single", _topk_meta("dense_topk_single", n, kk, cfg,
                                          1, None)
        segment = functools.partial(topk.run_segment, s3k, idx, **loop)
        save, restore = _carry_tree, _restored_carry
    else:
        kind, w = "sharded", mesh.shape[ts.AXIS]
        s3k_p, idx_p, n_real = ts.pad_topk(s3k, idx, w)
        exchange = ts.resolve_exchange(cfg.exchange, n=s3k_p.shape[1],
                                       kk=kk)
        meta = _topk_meta("dense_topk_sharded", n, kk, cfg, w, exchange)
        s3k_host = np.asarray(s3k_p)
        idx = device_put_row_sharded(idx_p, mesh, ts.AXIS, axis=0)
        segment = functools.partial(
            ts._sharded_program(mesh, n_real, exchange, **loop),
            device_put_row_sharded(s3k_p, mesh, ts.AXIS, axis=1), idx)

        def logical(a):            # the unpadded rows: dummies are re-derived
            return np.asarray(a)[:, :n_real]

        def save(state, e, stable, it, trace):
            return _carry_tree(jax.tree.map(logical, state), logical(e),
                               np.int32(stable), np.int32(it),
                               np.asarray(trace))

        def restore(restored):
            return _repad_carry(restored, s3k_host, n_real, mesh)

    carry, it, stable = _run_segments(cfg, meta, kind, segment, save,
                                      restore)
    state, e, _, _, trace = carry
    return (TopKState(state, idx), e, jnp.int32(it),
            jnp.asarray(stable >= cfg.patience), jnp.asarray(trace))


def _open_run(cfg: SolveConfig, meta: dict):
    """Validate/initialize the checkpoint directories; returns
    ``(manager_or_None, restored_tree_or_None)``."""
    restored = None
    if cfg.resume_from:
        check_meta(cfg.resume_from, meta)
        mgr_in = CheckpointManager(cfg.resume_from, keep=2,
                                   async_save=False)
        hit = mgr_in.restore_latest(_carry_like(cfg.levels))
        if hit is None:
            raise ValueError(
                f"resume_from={cfg.resume_from!r} holds no step_* "
                "checkpoints to resume")
        restored = hit[1]
    mgr = None
    if cfg.checkpoint_every > 0:
        if not cfg.resume_from or \
                os.path.abspath(cfg.resume_from) != \
                os.path.abspath(cfg.checkpoint_dir):
            reset_dir(cfg.checkpoint_dir)
        write_meta(cfg.checkpoint_dir, meta)
        mgr = CheckpointManager(cfg.checkpoint_dir, keep=2,
                                async_save=False)
    return mgr, restored


def _run_segments(cfg: SolveConfig, meta: dict, kind: str, segment, save,
                  restore):
    """The one segment loop: restore the latest checkpoint if resuming,
    then run ``segment`` up to each boundary, save the carry and fire the
    ``solver.sweep`` fault site (``kind`` names the placement) until the
    stopping rule holds. -> ``(carry, it, stable)``; a resumed run that
    had already finished returns the restored carry."""
    mgr, restored = _open_run(cfg, meta)
    carry, it, stable = None, 0, 0
    if restored is not None:
        carry = restore(restored)
        it, stable = int(restored["it"]), int(restored["stable"])
    every, mi = cfg.checkpoint_every, cfg.max_iterations
    while not _is_done(it, stable, cfg):
        until = mi if every <= 0 else min(it + every, mi)
        carry = segment(jnp.int32(until), carry)
        it, stable = int(carry[3]), int(carry[2])
        if mgr is not None:
            mgr.save(it, save(*carry))
        faultinject.fire("solver.sweep", sweep=it, kind=kind)
    return carry, it, stable


def _repad_carry(restored: dict, s3k_host: np.ndarray, n_real: int, mesh):
    """Rebuild the padded sharded carry from a logical checkpoint: real
    rows from disk, dummy rows reset to their ``init_state`` values
    (inert by construction — self-referencing edges, masked change
    counter — so real-row evolution is unchanged)."""
    levels, n_total = s3k_host.shape[:2]

    def pad_field(name, init_fill):
        saved = np.asarray(restored[name])
        full_shape = (saved.shape[0], n_total) + saved.shape[2:]
        full = np.full(full_shape, init_fill, saved.dtype)
        full[:, :n_real] = saved
        return full

    s_full = s3k_host.copy()
    s_full[:, :n_real] = np.asarray(restored["s"])
    state = hap.HAPState(
        s=s_full, r=pad_field("r", 0.0), a=pad_field("a", 0.0),
        tau=pad_field("tau", np.inf), phi=pad_field("phi", 0.0),
        c=pad_field("c", 0.0),
        col=None if levels == 1 else pad_field("col", 0.0))
    e_saved = np.asarray(restored["e_prev"])
    dummies = np.broadcast_to(
        np.arange(n_real, n_total, dtype=e_saved.dtype),
        (levels, n_total - n_real))
    e_full = np.concatenate([e_saved, dummies], axis=1)
    state, e_full = jax.tree.map(
        lambda a: device_put_row_sharded(jnp.asarray(a), mesh, ts.AXIS,
                                         axis=1), (state, e_full))
    return (state, e_full, jnp.int32(restored["stable"]),
            jnp.int32(restored["it"]), jnp.asarray(restored["trace"]))


# ------------------------------------------------------------ coarsen stage
def coarsen_meta(n: int, d: int, cfg: SolveConfig) -> dict:
    pref = cfg.preference if isinstance(cfg.preference, str) \
        else float(np.asarray(cfg.preference)) \
        if np.ndim(cfg.preference) == 0 else "array"
    return {
        "kind": "coarsen", "n": n, "d": d,
        "partition_size": cfg.partition_size,
        "coarsen_batch": cfg.coarsen_batch,
        "coarsen_global_dense_n": cfg.coarsen_global_dense_n,
        "coarsen_global_k": cfg.coarsen_global_k,
        "levels": cfg.levels, "max_iterations": cfg.max_iterations,
        "damping": cfg.damping, "stop": cfg.stop,
        "patience": cfg.patience, "preference": pref,
    }


def stage_path(directory: str, stage: str) -> str:
    return os.path.join(directory, stage)


def save_stage(directory: str, stage: str, tree: dict) -> None:
    save_tree(stage_path(directory, stage), tree)


def load_stage(directory: str, stage: str, like: dict):
    """Load a stage artifact, or None when it was never written."""
    path = stage_path(directory, stage)
    if not os.path.exists(os.path.join(path, "manifest.json")):
        return None
    return restore_tree(path, like)
