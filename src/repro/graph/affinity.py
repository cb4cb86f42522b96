"""``graph_affinity`` — Borůvka-style affinity clustering on edge lists.

The MapReduce affinity-clustering loop of Ene et al. (*Fast Clustering
using MapReduce*, PAPERS.md): every round each current cluster selects
its best outgoing edge, clusters hook along the selected edges, and
pointer jumping contracts the hooking forest to its roots — O(N·k) work
per round, ~log N rounds to any target granularity. On similarity
weights (larger is better) "best" is the *maximum*-weight edge, i.e.
Borůvka's min-edge rule under negation.

Deterministic selection rule (the tie-break contract):

    best edge of cluster c = max weight, then min destination-leader id

— the same (value desc, col asc) order every top-k path in this repo
implements. On a symmetrized edge list this rule admits no hooking
cycle longer than 2 (a length->=3 cycle needs equal weights around the
cycle, and min-leader tie-breaking then orders the cycle's ids
inconsistently), and mutual 2-cycles resolve to the smaller node id, so
pointer jumping reaches a fixed point in <= ceil(log2 N) doublings.
``EdgeList.canonical()`` (applied by the backend adapter) establishes
symmetry; feed raw asymmetric edges only through ``solve()``.

Execution shapes, mirroring ``topk_sharded``:

* single device: the whole round loop is one jitted ``lax.while_loop``
  over the padded row layout (edge relabeling is a label gather; the
  between-round dedup is implicit in the segment-max reduction — equal
  relabeled edges collapse to one winner);
* sharded: rows block over the 1-D ``workers`` mesh under one
  ``shard_map``; labels replicate. The per-round min-edge exchange is
  two collectives: ``pmax`` of the per-cluster best *weight* (f32 max —
  exact and associative, so worker count cannot change the result),
  then each worker re-scores its local achievers of the global best and
  ``pmin`` reduces the candidate destination-leader (int32 min — also
  exact). The sharded path is therefore **bit-identical** to the
  single-device loop at any worker count.

The hierarchy output reuses the HAP convention: level ``l`` of the
``(levels, N)`` exemplar stack is the label snapshot ``levels-1-l``
rounds before the stop round (level 0 finest, earlier snapshots padded
with the initial all-singletons labeling when the loop stops in fewer
than ``levels`` rounds), so ``link_hierarchy`` and ``_finalize`` apply
unchanged.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.partitioning import device_put_row_sharded

AXIS = "workers"


def default_rounds(n: int) -> int:
    """Round budget when ``SolveConfig.graph_rounds`` is None: Borůvka
    at least halves the cluster count per round, so ceil(log2 N) + 1
    covers contraction to a single component with one slack round."""
    return int(math.ceil(math.log2(max(n, 2)))) + 1


def _jump_iters(n: int) -> int:
    return int(math.ceil(math.log2(max(n, 2)))) + 1


def _hook_and_jump(best_t: jnp.ndarray, n_total: int, jump_iters: int
                   ) -> jnp.ndarray:
    """Selected destination-leader per cluster -> contracted root map.

    2-cycles (mutual best edges — guaranteed to exist on the max-weight
    edge of any component, so every round makes progress) keep the
    smaller node id as root; the fori count is static so the whole
    contraction stays inside the jitted round."""
    ids = jnp.arange(n_total, dtype=jnp.int32)
    parent = jnp.where(best_t < n_total, best_t.astype(jnp.int32), ids)
    two_cycle_root = (parent[parent] == ids) & (ids < parent)
    parent = jnp.where(two_cycle_root, ids, parent)
    return jax.lax.fori_loop(0, jump_iters, lambda _, p: p[p], parent)


def _round_state(labels, levels, n_total, max_rounds):
    hist = jnp.broadcast_to(labels, (levels, n_total))
    trace = jnp.zeros((max_rounds,), jnp.int32)
    return (labels, hist, jnp.int32(0), jnp.int32(1), trace)


def _loop(select, levels: int, n_total: int, n_real: int, max_rounds: int,
          target: int, jump_iters: int):
    """The shared round loop: ``select(labels) -> best_t`` is the only
    piece that differs between the single-device and sharded programs."""
    ids = jnp.arange(n_total, dtype=jnp.int32)
    real = ids < n_real

    def n_clusters(labels):
        return jnp.sum((labels == ids) & real)

    def cond(carry):
        labels, _, r, changes, _ = carry
        return ((r < max_rounds) & (n_clusters(labels) > target)
                & ((r == 0) | (changes > 0)))

    def body(carry):
        labels, hist, r, _, trace = carry
        parent = _hook_and_jump(select(labels), n_total, jump_iters)
        new = parent[labels]
        changes = jnp.sum((new != labels) & real).astype(jnp.int32)
        hist = jnp.concatenate([hist[1:], new[None]], axis=0)
        return (new, hist, r + 1, changes,
                trace.at[r].set(changes))

    labels0 = ids
    labels, hist, r, changes, trace = jax.lax.while_loop(
        cond, body, _round_state(labels0, levels, n_total, max_rounds))
    converged = (n_clusters(labels) <= target) | ((r > 0) & (changes == 0))
    return hist, r, converged, trace


def _select_fn(vals, idx, labels, rows, n_total):
    """Per-cluster best-edge selection over one row block.

    ``rows`` are the block's global node ids; edges whose endpoints
    share a leader (including the padding's self-pointing slots) are
    inactive. Two segment reductions implement the tie-break: max
    weight, then min destination-leader among the achievers of the
    (globally combined) max.
    """
    b, d = vals.shape
    row_lbl = labels[rows]                          # (B,) leader per row
    dst_lbl = labels[idx]                           # (B, D) relabeled edges
    active = dst_lbl != row_lbl[:, None]
    seg = jnp.broadcast_to(row_lbl[:, None], (b, d)).ravel()
    w = jnp.where(active, vals, -jnp.inf).ravel()
    best_w = jax.ops.segment_max(w, seg, num_segments=n_total)
    return seg, w, best_w, dst_lbl.ravel()


def _candidates(seg, w, best_w, dst_flat, n_total):
    ach = (w == best_w[seg]) & jnp.isfinite(w)
    cand = jnp.where(ach, dst_flat, n_total).astype(jnp.int32)
    return jax.ops.segment_min(cand, seg, num_segments=n_total)


@functools.partial(
    jax.jit,
    static_argnames=("levels", "max_rounds", "target", "jump_iters"))
def _run_single(vals, idx, *, levels: int, max_rounds: int, target: int,
                jump_iters: int):
    n, _ = vals.shape
    rows = jnp.arange(n, dtype=jnp.int32)

    def select(labels):
        seg, w, best_w, dst = _select_fn(vals, idx, labels, rows, n)
        return _candidates(seg, w, best_w, dst, n)

    return _loop(select, levels, n, n, max_rounds, target, jump_iters)


# ----------------------------------------------------------------- sharded
@functools.lru_cache(maxsize=32)
def _graph_program(mesh, levels: int, n_local: int, n_total: int,
                   n_real: int, d: int, max_rounds: int, target: int,
                   jump_iters: int):
    """Jitted whole-loop shard_map program, cached per mesh/shape (the
    ``_sharded_program`` idiom). Labels replicate; each worker owns a
    row block of the edge layout and the two exact collectives combine
    the per-cluster selection."""

    def body(vals_loc, idx_loc):
        rows = (jax.lax.axis_index(AXIS) * n_local
                + jnp.arange(n_local, dtype=jnp.int32))

        def select(labels):
            seg, w, best_w_loc, dst = _select_fn(
                vals_loc, idx_loc, labels, rows, n_total)
            best_w = jax.lax.pmax(best_w_loc, AXIS)      # exact f32 max
            cand_loc = _candidates(seg, w, best_w, dst, n_total)
            return jax.lax.pmin(cand_loc, AXIS)          # exact i32 min

        hist, r, conv, trace = _loop(
            select, levels, n_total, n_real, max_rounds, target, jump_iters)
        vary = lambda x: jax.lax.pcast(x, (AXIS,), to="varying")
        scal = lambda v: vary(jnp.reshape(v, (1,)))
        # every worker holds identical (collective-derived) full-length
        # labels; emit each worker's own row slice so outputs reassemble
        # under sharded specs (no replicated-output spec needed)
        return (vary(hist)[:, rows], scal(r), scal(conv), vary(trace)[None])

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None)),
        out_specs=(P(None, AXIS), P(AXIS), P(AXIS), P(AXIS, None))))


def pad_rows(vals: jnp.ndarray, idx: jnp.ndarray, multiple: int
             ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad the (N, D) row layout to a worker multiple with inert rows:
    every padded slot points at its own (padded) row, so the padding is
    an isolated singleton forever and never enters a real selection."""
    n, d = vals.shape
    pad = (-n) % multiple
    if pad == 0:
        return vals, idx, n
    dummy = jnp.arange(n, n + pad, dtype=jnp.int32)
    return (jnp.concatenate([vals, jnp.zeros((pad, d), vals.dtype)]),
            jnp.concatenate([idx, jnp.broadcast_to(dummy[:, None],
                                                   (pad, d))]), n)


def run_graph_affinity(
    vals,
    idx,
    *,
    levels: int = 1,
    max_rounds: Optional[int] = None,
    target: int = 1,
    mesh=None,
):
    """Run Borůvka affinity clustering on a padded row layout.

    ``vals``/``idx`` are the ``EdgeList.to_topk()`` layout: (N, D)
    weights and destination ids, inert slots pointing at their own row.
    Returns ``(hist, n_rounds, converged, trace)`` — ``hist`` is the
    (levels, N) label-snapshot stack (level 0 finest), ``trace`` the
    per-round relabel count (slice by ``n_rounds``). ``mesh`` (1-D
    ``workers``) selects the sharded program; results are bit-identical
    either way.
    """
    vals = jnp.asarray(vals, jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    n, d = vals.shape
    max_rounds = default_rounds(n) if max_rounds is None else int(max_rounds)
    target = max(int(target), 1)
    jump = _jump_iters(n)
    if mesh is None or mesh.shape.get(AXIS, 1) == 1:
        hist, r, conv, trace = _run_single(
            vals, idx, levels=levels, max_rounds=max_rounds, target=target,
            jump_iters=jump)
        return hist, r, conv, trace
    if tuple(mesh.axis_names) != (AXIS,):
        raise ValueError(
            f"graph_affinity needs a 1-D mesh with axis {AXIS!r} "
            f"(got axes {tuple(mesh.axis_names)}); build one with "
            "repro.launch.mesh.make_worker_mesh()")
    w = mesh.shape[AXIS]
    vals_p, idx_p, n_real = pad_rows(vals, idx, w)
    n_total = vals_p.shape[0]
    fn = _graph_program(mesh, levels, n_total // w, n_total, n_real, d,
                        max_rounds, target, jump)
    vals_p = device_put_row_sharded(vals_p, mesh, AXIS, axis=0)
    idx_p = device_put_row_sharded(idx_p, mesh, AXIS, axis=0)
    hist, r, conv, trace = fn(vals_p, idx_p)
    return hist, r[0], conv[0], trace[0]


# ----------------------------------------------------------------- preseed
#: per-row edge cap for the preseed pass — the symmetrized graph can
#: concentrate unbounded in-degree on hub rows; the seeding only needs
#: each row's strongest edges.
PRESEED_MAX_DEGREE = 128


def preseed_preferences(vals, idx, base, *,
                        target: Optional[int] = None,
                        max_rounds: Optional[int] = None) -> jnp.ndarray:
    """ROADMAP's "cheap graph pass to seed HAP preferences": one Borůvka
    clustering over the already-built top-k edges (no second O(N^2)
    build), then bias the preference vector so graph-cluster leaders are
    the favored exemplar candidates — leaders keep ``base``, members pay
    a stored-weight-span penalty (data-scaled, so any similarity
    magnitude works). ``target`` defaults to ~sqrt(N) seed clusters.
    """
    import numpy as np

    from repro.graph.edges import EdgeList

    vals_np = np.asarray(vals)
    n, k = vals_np.shape
    el = EdgeList.from_topk(vals_np, np.asarray(idx)).canonical()
    cap = min(el.max_degree or 1, max(2 * k, 8))
    tv, ti = el.to_topk(cap)
    if target is None:
        target = max(int(math.sqrt(n)), 2)
    hist, _, _, _ = run_graph_affinity(
        tv, ti, levels=1, max_rounds=max_rounds, target=target)
    labels = jnp.asarray(hist[-1])
    leaders = labels == jnp.arange(n, dtype=labels.dtype)
    span = (float(vals_np.max()) - float(vals_np.min())
            if vals_np.size else 1.0)
    base = jnp.broadcast_to(jnp.asarray(base, jnp.float32), (n,))
    return jnp.where(leaders, base, base - jnp.float32(span))
