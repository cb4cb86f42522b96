"""Top-k-per-row similarity builds — the sparse layout's front door.

The dense builders materialize the full (N, N) matrix; past N ~ 10^4 that
is the memory wall. Everything here produces the same compressed layout
(shared by every ``repro.kernels.topk_ops`` consumer):

    vals (N, k) f32   top-k *off-diagonal* similarities per row
    idx  (N, k) i32   their column indices, sorted ascending per row

The diagonal (preference) is excluded and carried as the dedicated "self"
slot the solver prepends (``repro.solver.topk``).

Two jnp implementations live here (``repro.solver.topk_build`` owns
backend selection and the sharded driver; ``topk_build_fused`` holds the
Pallas kernel):

``topk_similarity`` — the reference scan. Streams (block_rows,
block_cols) similarity tiles and folds each into a running per-row top-k
with a full ``top_k`` re-sort per tile; O(block_rows * block_cols + N*k)
peak state, O(N^2) work. Exact at any shape, the parity oracle for every
other path.

``topk_similarity_twostage`` — the threshold-gated partial merge. Points
are kd-ordered into width-``chunk`` cells (tight centroid/radius balls);
per row block, stage 1 *gates* whole chunks on an upper similarity bound
against the running per-row k-th value (the row minimum), and stage 2
merges only the surviving chunks' candidates through an explicit
(value desc, col asc) selection — candidates that cannot beat the current
row minimum never enter a sort, and their similarities are never even
computed. A capped refinement loop plus a skippable residual sweep keep
the worst (unclusterable) case within a small factor of the reference
scan while clusterable data prunes the vast majority of all pairs.

Tie-break contract (every build path + ``topk_from_dense``): the selected
edge set is the top-k under the total order "larger value first, smaller
column index first among equal values". The reference scan and
``topk_from_dense`` satisfy it through ``lax.top_k``'s positional
stability (tiles arrive in ascending column order); the two-stage merge
and the fused kernel visit candidates out of column order and therefore
implement the tie-break explicitly (``topk_select_exact`` / the in-kernel
column-argmin). Duplicate similarity values — duplicated points are the
common source — select identical edge sets on every path at any tile
shape, which is what keeps the k = N-1 parity suites meaningful.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.similarity import _METRICS, MATMUL_PRECISION, _sq_norms

NEG_INF = float("-inf")

#: beyond this N the exact tie-break select (column ids embedded in f32
#: keys) would lose integer precision; the reference scan has no such cap.
SELECT_EXACT_MAX_N = 1 << 24

#: relative / absolute slack on the two-stage chunk bounds: the triangle
#: inequality is exact in reals but the centroid distances and radii are
#: f32, so the gate widens by a hair rather than ever pruning a true edge.
_GATE_REL = 1e-4
_GATE_ABS = 1e-6


def _block_similarity(xr, xc, metric: str, use_pallas: bool):
    if use_pallas and metric == "neg_sqeuclidean":
        from repro.kernels.similarity import similarity_pallas
        return similarity_pallas(xr, xc)
    return _METRICS[metric](xr, xc)


# --------------------------------------------------------- exact selection
def topk_select_exact(cand_v: jnp.ndarray, cand_c: jnp.ndarray, k: int):
    """Select k candidates per row under (value desc, col asc) — exact
    under duplicate values regardless of candidate order.

    Two ``lax.top_k`` passes: the first finds the k-th value ``v*``; the
    second runs on a composite key (+inf for sure winners, ``-col`` for
    the ties at ``v*``, -inf otherwise), so the tie slots fill with the
    smallest column indices. Columns must fit exactly in f32, hence the
    ``SELECT_EXACT_MAX_N`` cap enforced by callers.

    ``v*`` is a min-*reduction* over the first pass on purpose: slicing
    ``[:, -1:]`` instead composes with top_k's internal ``[:k]`` slice
    into a non-prefix slice, XLA's TopK-rewriter pattern no longer
    matches, and the pass falls back to a full O(W log W) comparator
    sort (~10x on CPU). No ``optimization_barrier`` anywhere: a barrier
    touching the TopK custom call crashes XLA's TopkDecomposer when this
    select compiles inside ``shard_map`` (the sharded build driver).
    """
    t, _ = jax.lax.top_k(cand_v, k)
    vstar = jnp.min(t, axis=1, keepdims=True)
    key = jnp.where(cand_v > vstar, jnp.inf,
                    jnp.where(cand_v == vstar, -cand_c.astype(jnp.float32),
                              NEG_INF))
    _, pos = jax.lax.top_k(key, k)
    return (jnp.take_along_axis(cand_v, pos, axis=1),
            jnp.take_along_axis(cand_c, pos, axis=1))


def _check_k(k: int, n: int) -> None:
    if not 1 <= k <= n - 1:
        raise ValueError(f"k must be in [1, N-1] = [1, {n - 1}]; got {k}")


# ----------------------------------------------------------- reference scan
def _merge_topk(carry, blk_vals, blk_cols, k):
    """Fold a (B, C) tile into the running (B, k) top-k. ``lax.top_k`` is
    positionally stable and the carry precedes the tile (tiles arrive in
    ascending column order), so ties resolve to the smaller column."""
    vals, idx = carry
    cand_v = jnp.concatenate([vals, blk_vals], axis=1)
    cand_i = jnp.concatenate([idx, blk_cols], axis=1)
    top_v, pos = jax.lax.top_k(cand_v, k)
    top_i = jnp.take_along_axis(cand_i, pos, axis=1)
    return top_v, top_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "block_rows", "block_cols",
                     "use_pallas"))
def topk_similarity(
    x: jnp.ndarray,
    k: int,
    *,
    metric: str = "neg_sqeuclidean",
    block_rows: int = 1024,
    block_cols: int = 4096,
    use_pallas: bool = False,
    cols: jnp.ndarray | None = None,
    row_offset=0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(M, d) row points -> (vals (M, k), idx (M, k)) off-diagonal top-k.

    ``cols`` (default: ``x`` itself) is the column point set; passing a
    row shard plus the full set (with ``row_offset`` = the shard's global
    starting row, so self-edges mask correctly) is how the sharded build
    driver runs this per device. ``k`` must satisfy ``1 <= k <= N - 1``
    against the *column* count N; at ``k = N - 1`` the output is the full
    off-diagonal similarity set (lossless) and downstream sparse sweeps
    reproduce the dense recurrence exactly.
    """
    y = x if cols is None else cols
    m = x.shape[0]
    n = y.shape[0]
    _check_k(k, n)
    br = min(block_rows, m)
    bc = min(block_cols, n)
    pr, pc = (-m) % br, (-n) % bc
    xr = jnp.pad(x, ((0, pr), (0, 0))) if pr else x
    n_rt, n_ct = xr.shape[0] // br, (n + pc) // bc
    col_pad = jnp.pad(y, ((0, pc), (0, 0))) if pc else y
    row_offset = jnp.asarray(row_offset, jnp.int32)

    def row_tile(args):
        tile, r0 = args                                # (br, d), scalar
        rows = row_offset + r0 + jnp.arange(br)

        def fold(carry, c0):
            s_blk = _block_similarity(
                tile, jax.lax.dynamic_slice_in_dim(col_pad, c0, bc),
                metric, use_pallas)                    # (br, bc)
            # pin the tile to the standalone formula evaluation: left
            # free, XLA fuses the similarity arithmetic separately into
            # each consumer and the copies can round apart by ulps —
            # which is exactly the value drift that made this build and
            # topk_from_dense disagree under near-tie values
            s_blk = jax.lax.optimization_barrier(s_blk)
            cols_ = c0 + jnp.arange(bc)
            # mask the diagonal (self) and any padded phantom column
            dead = (cols_[None, :] == rows[:, None]) | (cols_[None, :] >= n)
            s_blk = jnp.where(dead, NEG_INF, s_blk)
            blk_cols = jnp.broadcast_to(cols_[None, :], s_blk.shape)
            return _merge_topk(carry, s_blk, blk_cols, k), None

        init = (jnp.full((br, k), NEG_INF, jnp.float32),
                jnp.zeros((br, k), jnp.int32))
        # under shard_map the folded tiles vary over the manual axes
        # their inputs vary over; the scan carry must start that way too
        vma = tuple(sorted(jax.typeof(tile).vma | jax.typeof(rows).vma
                           | jax.typeof(col_pad).vma))
        if vma:
            init = jax.tree.map(
                lambda v: jax.lax.pcast(v, vma, to="varying"), init)
        (vals, idx), _ = jax.lax.scan(
            fold, init, jnp.arange(n_ct, dtype=jnp.int32) * bc)
        # deterministic layout: neighbors in ascending column order
        order = jnp.argsort(idx, axis=1)
        return (jnp.take_along_axis(vals, order, axis=1),
                jnp.take_along_axis(idx, order, axis=1))

    tiles = xr.reshape(n_rt, br, x.shape[1])
    starts = (jnp.arange(n_rt, dtype=jnp.int32) * br)
    vals, idx = jax.lax.map(row_tile, (tiles, starts))
    return (vals.reshape(-1, k)[:m].astype(jnp.float32),
            idx.reshape(-1, k)[:m].astype(jnp.int32))


# ------------------------------------------------------- two-stage build
def kd_order(x: np.ndarray, leaf: int) -> np.ndarray:
    """Recursive median-cut ordering: consecutive runs of ``leaf`` points
    form tight axis-aligned cells. Any permutation is correctness-neutral
    (the build's output is exact for every ordering); only the pruning
    power of the chunk bounds depends on it. The partitioner itself lives
    in ``repro.sharding.partitioning`` (the ``coarsen`` backend consumes
    the same cells as its local-solve partitions); this wrapper keeps the
    build's historical entry point."""
    from repro.sharding.partitioning import kd_median_cut
    return kd_median_cut(x, leaf)[0]


def _geometry(x, metric: str):
    """Map points into the space whose squared-Euclidean distances order
    the metric: identity for the (sq)euclidean metrics, per-point
    normalization (the same formula the dense builder applies) for
    cosine. Bounds are computed in this space; survivor *values* are
    computed with the metric's own formula."""
    if metric == "cosine":
        return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    return x


def _d2_threshold(rm, metric: str):
    """Value-space running row minimum -> inclusive squared-distance gate
    (a candidate at squared distance above it can never enter the row's
    top-k, ties included)."""
    if metric == "neg_sqeuclidean":
        thr = -rm
    elif metric == "neg_euclidean":
        thr = rm * rm
    else:  # cosine: v = x.y - 1 = -d^2/2 on normalized points
        thr = -2.0 * rm
    return thr * (1.0 + _GATE_REL) + _GATE_ABS


def _survivor_values(d2, metric: str, dot=None):
    """Exact metric values for gathered survivors, replicating the dense
    formulas element-for-element (d2 is the clamped squared distance in
    geometry space; ``dot`` is the raw inner product, used by cosine)."""
    if metric == "neg_sqeuclidean":
        return -d2
    if metric == "neg_euclidean":
        return -jnp.sqrt(jnp.maximum(d2, 1e-12))
    return dot - 1.0


def topk_similarity_twostage(
    x: jnp.ndarray,
    k: int,
    *,
    metric: str = "neg_sqeuclidean",
    block_rows: int = 1024,
    chunk: int = 128,
    round_chunks: int = 32,
    max_rounds: int = 4,
    residual_chunks: int = 32,
    cols: jnp.ndarray | None = None,
    row_offset=0,
    perm: np.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Threshold-gated two-stage top-k build; the same edge set as
    ``topk_similarity`` (enforced in tests), typically an order of
    magnitude less work on clusterable data.

    ``perm`` overrides the kd ordering (the sharded driver computes it
    once on the host and hands it to every worker).
    """
    y = x if cols is None else cols
    n = int(y.shape[0])
    _check_k(k, n)
    if n > SELECT_EXACT_MAX_N:
        raise ValueError(
            f"two-stage build supports N <= {SELECT_EXACT_MAX_N} (column "
            "ids must be exact in f32 tie-break keys); use the reference "
            f"build for N = {n}")
    chunk = max(min(chunk, n), 1)
    nch = -(-n // chunk)
    boot = min(max(2, -(-(k + 1) // chunk) + 1), nch)
    if perm is None:
        perm = kd_order(np.asarray(y), chunk)
    return _twostage_core(
        x, y, jnp.asarray(perm, jnp.int32),
        jnp.asarray(row_offset, jnp.int32), k=k, metric=metric,
        block_rows=min(block_rows, int(x.shape[0])), chunk=chunk,
        round_chunks=min(round_chunks, nch), max_rounds=max_rounds,
        residual_chunks=min(residual_chunks, nch), boot_chunks=boot)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "block_rows", "chunk", "round_chunks",
                     "max_rounds", "residual_chunks", "boot_chunks"))
def _twostage_core(x, y, perm, row_offset, *, k, metric, block_rows,
                   chunk, round_chunks, max_rounds, residual_chunks,
                   boot_chunks):
    m, d = x.shape
    n = y.shape[0]
    br, cw, S, B = block_rows, chunk, round_chunks, boot_chunks
    sq = metric != "cosine"

    # ---- chunk structures over the kd-permuted column set
    nch = -(-n // cw)
    pad = nch * cw - n
    gy = _geometry(y, metric)
    yp = jnp.pad(jnp.take(gy, perm, axis=0), ((0, pad), (0, 0)))
    gcol = jnp.pad(perm, (0, pad), constant_values=n)   # n = phantom
    valid = gcol < n
    yy = jnp.where(valid, _sq_norms(yp), jnp.inf)
    ych = yp.reshape(nch, cw, d)
    wch = valid.reshape(nch, cw).astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(wch, axis=1), 1.0)
    cen = jnp.sum(ych * wch[:, :, None], axis=1) / cnt[:, None]
    rad = jnp.sqrt(jnp.max(jnp.where(valid.reshape(nch, cw),
                                     jnp.sum((ych - cen[:, None, :]) ** 2,
                                             axis=2), 0.0), axis=1))
    rad = rad * (1.0 + _GATE_REL) + _GATE_ABS
    ccol = gcol.reshape(nch, cw)
    yych = yy.reshape(nch, cw)

    gx = _geometry(x, metric)
    pr = (-m) % br
    if pr:
        gx = jnp.pad(gx, ((0, pr), (0, 0)))
    n_rt = gx.shape[0] // br

    def row_tile(args):
        tile, r0 = args                                # (br, d) geometry
        rows = row_offset + r0 + jnp.arange(br)
        txx = _sq_norms(tile)
        d2c = jnp.maximum(txx[:, None]
                          + jnp.sum(cen * cen, axis=1)[None, :]
                          - 2.0 * jnp.matmul(tile, cen.T,
                                             precision=MATMUL_PRECISION),
                          0.0)                          # (br, nch)
        # squared lower bound on the distance to anything in the chunk
        lbd = jnp.maximum(jnp.sqrt(d2c) * (1.0 - _GATE_REL) - rad, 0.0)
        lbd2 = lbd * lbd

        def select(vals, idx, sg, cols_):
            return topk_select_exact(jnp.concatenate([vals, sg], axis=1),
                                     jnp.concatenate([idx, cols_], axis=1),
                                     k)

        def merge_chunks(vals, idx, cid, ok=None):
            """Stage 2: gather the picked chunks' points and fold their
            exact similarities into the carry."""
            sw = cid.shape[1] * cw
            pts = jnp.take(ych, cid, axis=0)            # (br, S', cw, d)
            dot = jnp.einsum("rd,rscd->rsc", tile, pts,
                             precision=MATMUL_PRECISION).reshape(br, sw)
            yyg = jnp.take(yych, cid, axis=0).reshape(br, sw)
            cols_ = jnp.take(ccol, cid, axis=0).reshape(br, sw)
            d2 = jnp.maximum(txx[:, None] + yyg - 2.0 * dot, 0.0)
            sg = _survivor_values(d2, metric, dot)
            sg = jax.lax.optimization_barrier(sg)  # see reference fold
            dead = (cols_ == rows[:, None]) | (cols_ >= n)
            if ok is not None:
                dead = dead | ~jnp.repeat(ok, cw, axis=1)
            return select(vals, idx, jnp.where(dead, NEG_INF, sg),
                          cols_)

        # bootstrap: the B nearest chunks seed the running top-k (any
        # achieved k-th value is a valid gate floor)
        _, bid = jax.lax.top_k(-d2c, B)
        vals = jnp.full((br, k), NEG_INF, jnp.float32)
        idx = jnp.zeros((br, k), jnp.int32)
        vals, idx = merge_chunks(vals, idx, bid)
        done = jnp.zeros((br, nch), bool)
        done = done.at[jnp.arange(br)[:, None], bid].set(True)

        def live_mask(vals, done):
            thr = _d2_threshold(jnp.min(vals, axis=1), metric)
            return ~done & (lbd2 <= thr[:, None])

        # stage 1 rounds: keep folding the tightest-bound live chunks;
        # every merge raises the row minimum and shrinks the live set
        def cond(st):
            vals, _, done, r = st
            return jnp.any(live_mask(vals, done)) & (r < max_rounds)

        def body(st):
            vals, idx, done, r = st
            live = live_mask(vals, done)
            lv, cid = jax.lax.top_k(jnp.where(live, -lbd2, NEG_INF), S)
            # top_k pads short rows with arbitrary (already-done) chunks;
            # ok masks those picks so no candidate is merged twice
            vals, idx = merge_chunks(vals, idx, cid, ok=lv > NEG_INF)
            done = done.at[jnp.arange(br)[:, None], cid].set(True)
            return vals, idx, done, r + 1

        vals, idx, done, _ = jax.lax.while_loop(
            cond, body, (vals, idx, done, jnp.int32(0)))

        # residual: contiguous slabs over whatever the cap left live —
        # skipped outright per slab when no row still needs it, the
        # bounded-worst-case path when the data refuses to prune
        G = residual_chunks
        ngrp = -(-nch // G)
        gpad2 = ngrp * G - nch
        done_p = jnp.pad(done, ((0, 0), (0, gpad2)), constant_values=True)
        lbd2_p = jnp.pad(lbd2, ((0, 0), (0, gpad2)),
                         constant_values=jnp.inf)
        ypr = jnp.pad(yp, ((0, gpad2 * cw), (0, 0)))
        yyr = jnp.pad(yy, (0, gpad2 * cw), constant_values=jnp.inf)
        gcolr = jnp.pad(gcol, (0, gpad2 * cw), constant_values=n)

        def res_slab(carry, g):
            vals, idx = carry
            c0 = g * G * cw
            thr = _d2_threshold(jnp.min(vals, axis=1), metric)
            live = (~jax.lax.dynamic_slice_in_dim(done_p, g * G, G, axis=1)
                    & (jax.lax.dynamic_slice_in_dim(lbd2_p, g * G, G,
                                                    axis=1)
                       <= thr[:, None]))

            def run(_):
                ypg = jax.lax.dynamic_slice_in_dim(ypr, c0, G * cw)
                dot = jnp.matmul(tile, ypg.T,
                                 precision=MATMUL_PRECISION)  # (br, G*cw)
                yyg = jax.lax.dynamic_slice_in_dim(
                    yyr, c0, G * cw)[None, :]
                cols_ = jax.lax.dynamic_slice_in_dim(
                    gcolr, c0, G * cw)[None, :]
                cols_ = jnp.broadcast_to(cols_, (br, G * cw))
                d2 = jnp.maximum(txx[:, None] + yyg - 2.0 * dot, 0.0)
                sg = _survivor_values(d2, metric, dot)
                sg = jax.lax.optimization_barrier(sg)  # see reference fold
                dead = ((cols_ == rows[:, None]) | (cols_ >= n)
                        | ~jnp.repeat(live, cw, axis=1))
                return select(vals, idx, jnp.where(dead, NEG_INF, sg),
                              cols_)

            return jax.lax.cond(jnp.any(live), run, lambda _: (vals, idx),
                                None), None

        (vals, idx), _ = jax.lax.scan(
            res_slab, (vals, idx), jnp.arange(ngrp, dtype=jnp.int32))
        order = jnp.argsort(idx, axis=1)
        return (jnp.take_along_axis(vals, order, axis=1),
                jnp.take_along_axis(idx, order, axis=1))

    tiles = gx.reshape(n_rt, br, d)
    starts = jnp.arange(n_rt, dtype=jnp.int32) * br
    vals, idx = jax.lax.map(row_tile, (tiles, starts))
    return (vals.reshape(-1, k)[:m].astype(jnp.float32),
            idx.reshape(-1, k)[:m].astype(jnp.int32))


# -------------------------------------------------------------- from dense
def topk_from_dense(s: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compress an existing dense (N, N) similarity matrix to the top-k
    layout (diagonal excluded — it is the preference slot). Used when a
    caller hands the solver a precomputed matrix; the build-from-points
    path should be preferred since it never materializes N x N.

    Tie-break: ``lax.top_k`` over a row is positionally stable, i.e.
    equal values select the smallest column indices — the same
    (value desc, col asc) order every build path implements.
    """
    n = s.shape[-1]
    _check_k(k, n)
    eye = jnp.eye(n, dtype=bool)
    off = jnp.where(eye, NEG_INF, s)
    vals, idx = jax.lax.top_k(off, k)
    order = jnp.argsort(idx, axis=1)
    return (jnp.take_along_axis(vals, order, axis=1).astype(jnp.float32),
            jnp.take_along_axis(idx, order, axis=1).astype(jnp.int32))
