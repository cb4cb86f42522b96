"""``dense_topk`` over an edge list of uneven degrees: the row-blocked
layout (``EdgeList.to_blocks``) and its sweep.

* seeded Graph500 Kronecker graphs at scales 8 and 10 (random weights):
  the program's messages and exemplars against a float64 numpy HAP over
  the flat COO list (``helpers.edge_hap_ref``);
* an edge list of equal degrees is today's one-block layout bit for bit;
* hub rows keep every edge; the padded entries stay within
  2 x (E + N) + ``MIN_BLOCK_WIDTH`` x N;
* exemplars come back in the caller's node ids;
* the ``repro.solve.layout`` span and the ``edge_layout.*`` counters.
"""
import glob

import jax
import numpy as np
import pytest

from helpers.edge_hap_ref import hap_edges, kronecker
from repro.graph.edges import MIN_BLOCK_WIDTH, EdgeBlocks, EdgeList
from repro.runtime import trace
from repro.solver import SolveConfig, solve
from repro.solver.registry import get_backend

SWEEPS = dict(backend="dense_topk", sweep="single", levels=3, damping=0.7,
              max_iterations=20, stop="fixed", preference="median")


def _graph(scale, seed=0):
    src, dst, w, n = kronecker(scale, seed=seed)
    return EdgeList(src, dst, w, n)


def _program_entries(state, n):
    """The program's stored entries in original ids: (row * N + col) keys
    in ascending order, and r, a (L, E') in that order."""
    blocks = lambda x: x if isinstance(x, tuple) else (x,)  # noqa: E731
    nodes = state.nodes if state.nodes is not None else np.arange(n)
    keys, rs, as_ = [], [], []
    lo = 0
    for r, a, idx in zip(blocks(state.hap.r), blocks(state.hap.a),
                         blocks(state.idx)):
        idx = np.asarray(idx)
        own = np.arange(lo, lo + idx.shape[0])[:, None]
        real = idx != own
        real[:, 0] = True                          # the self slot
        rows = np.broadcast_to(own, idx.shape)[real]
        keys.append(nodes[rows].astype(np.int64) * n + nodes[idx[real]])
        rs.append(np.asarray(r)[:, real])
        as_.append(np.asarray(a)[:, real])
        lo += idx.shape[0]
    keys = np.concatenate(keys)
    order = np.argsort(keys)
    return (keys[order], np.concatenate(rs, axis=1)[:, order],
            np.concatenate(as_, axis=1)[:, order])


@pytest.mark.parametrize("scale", [8, 10])
def test_matches_float64_reference(scale):
    """Messages within 4e-6 of the largest message's magnitude: float32
    (a unit roundoff of 6e-8) against float64 over 20 sweeps, each adding
    column sums of up to max-degree terms to the previous sweep's
    rounding; the readings here are 1-5e-7 of it. Exemplars exactly
    equal: with random weights no node of these graphs has its best
    a + r within that tolerance of its second."""
    el = _graph(scale, seed=scale)
    n = el.n_nodes
    res = solve(el, keep_state=True, **SWEEPS)
    assert isinstance(res.state.idx, tuple) and len(res.state.idx) > 1
    e_ref, (row, col, r_ref, a_ref) = hap_edges(
        el.src, el.dst, el.weight, n, np.median(el.weight), levels=3,
        iterations=20, damping=0.7, state=True)
    keys, r, a = _program_entries(res.state, n)
    assert np.array_equal(keys, row * n + col)
    tol = 4e-6 * np.abs(np.concatenate([r_ref, a_ref])).max()
    assert np.abs(r - r_ref).max() <= tol
    assert np.abs(a - a_ref).max() <= tol
    canon = np.stack([e[e] for e in e_ref])
    assert np.array_equal(res.exemplars, canon)


def test_equal_degrees_give_todays_layout():
    """Every node with the same degree: one block, no node relabelled,
    the ``to_topk`` layout bit for bit, and a solve identical to the
    one-block program."""
    rng = np.random.default_rng(5)
    n, deg = 64, 6
    src = np.repeat(np.arange(n), deg)
    dst = (src + np.tile(np.arange(1, deg + 1) * 7, n)) % n
    el = EdgeList(src, dst, rng.random(n * deg).astype(np.float32), n)
    lay = el.to_blocks()
    vals, idx = el.to_topk()
    assert len(lay.vals) == 1
    assert np.array_equal(lay.nodes, np.arange(n))
    assert np.array_equal(lay.vals[0], vals)
    assert np.array_equal(lay.idx[0], idx)
    res = solve(el, keep_state=True, **SWEEPS)
    assert res.state.nodes is None and not isinstance(res.state.idx, tuple)
    assert np.array_equal(np.asarray(res.state.idx[:, 1:]), idx)
    assert np.array_equal(np.asarray(res.state.hap.s[0][:, 1:]), vals)


@pytest.mark.parametrize("scale", [8, 10])
def test_hub_rows_keep_every_edge(scale):
    el = _graph(scale)
    n = el.n_nodes
    lay = el.to_blocks()
    got_src, got_dst, got_w = [], [], []
    for v, idx, lo in zip(lay.vals, lay.idx, lay.offsets):
        own = np.arange(lo, lo + v.shape[0])[:, None]
        real = idx != own
        got_src.append(lay.nodes[np.broadcast_to(own, idx.shape)[real]])
        got_dst.append(lay.nodes[idx[real]])
        got_w.append(v[real])
    key = (np.concatenate(got_src).astype(np.int64) * n
           + np.concatenate(got_dst))
    order = np.argsort(key)
    assert np.array_equal(key[order],
                          el.src.astype(np.int64) * n + el.dst)
    assert np.array_equal(np.concatenate(got_w)[order], el.weight)
    hubs = np.argsort(-el.degrees)[:4]
    widest = max(v.shape[1] for v in lay.vals)
    assert widest == el.max_degree
    assert lay.max_degree == el.max_degree
    last = lay.nodes[lay.offsets[-2]:]              # the widest block
    assert set(hubs[:1]) <= set(last.tolist())


@pytest.mark.parametrize("scale", [8, 10])
def test_padding_is_bounded(scale):
    el = _graph(scale)
    lay = el.to_blocks()
    n, e = el.n_nodes, el.n_edges
    assert lay.entries == e + n
    assert lay.padded <= 2 * (e + n) + MIN_BLOCK_WIDTH * n
    assert lay.padded < n * (el.max_degree + 1)
    rows = np.diff(lay.offsets)
    widths = [v.shape[1] + 1 for v in lay.vals]
    deg = el.degrees[lay.nodes]
    for b, (lo, hi) in enumerate(zip(lay.offsets, lay.offsets[1:])):
        assert rows[b] == hi - lo
        # every row of a block is within a factor of two of its width
        assert (np.maximum(2 * (deg[lo:hi] + 1), MIN_BLOCK_WIDTH)
                >= widths[b]).all()


def test_exemplars_in_original_ids():
    """Each node's exemplar before canonicalization is itself or one of
    its own neighbours, in the caller's ids, at every level."""
    el = _graph(9, seed=3)
    raw = get_backend("dense_topk").run(el, SolveConfig(**SWEEPS))
    e = np.asarray(raw.exemplars)
    n = el.n_nodes
    adj = set((el.src.astype(np.int64) * n + el.dst).tolist())
    for lv in range(e.shape[0]):
        for i in range(n):
            j = int(e[lv, i])
            assert j == i or i * n + j in adj, (lv, i, j)
    assert (e != np.arange(n)).any()


def test_layout_counters_and_span(tmp_path):
    from jax.profiler import ProfileData

    el = _graph(8, seed=1)
    solve(el, **SWEEPS)                               # compile outside
    with jax.profiler.trace(str(tmp_path)):
        solve(el, **SWEEPS)
    got = trace.edge_layout()
    lay = el.without_self_loops().deduplicated().to_blocks()
    assert got == {"edge_layout.buckets": len(lay.vals),
                   "edge_layout.entries": lay.entries,
                   "edge_layout.padded": lay.padded,
                   "edge_layout.max_degree": lay.max_degree,
                   "edge_layout.host_s": got["edge_layout.host_s"]}
    assert 0 < got["edge_layout.host_s"] < 60
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    layout = [e for e in events if e.name == trace.SPAN_LAYOUT]
    build = [e for e in events if e.name == trace.SPAN_BUILD]
    assert len(layout) == len(build) == 1
    assert (build[0].start_ns <= layout[0].start_ns
            and layout[0].start_ns + layout[0].duration_ns
            <= build[0].start_ns + build[0].duration_ns)


def test_checkpointed_edge_solve_keeps_one_block(tmp_path):
    """The checkpointed sweep takes the one-block layout padded to the
    widest row, as before the blocks; its exemplars agree with the
    blocked sweep's."""
    el = _graph(8, seed=2)
    blocked = solve(el, **SWEEPS)
    ckpt = solve(el, checkpoint_every=7, checkpoint_dir=str(tmp_path),
                 keep_state=True, **SWEEPS)
    assert not isinstance(ckpt.state.idx, tuple)
    assert ckpt.state.idx.shape == (el.n_nodes, el.max_degree + 1)
    assert trace.edge_layout()["edge_layout.buckets"] == 1
    assert np.array_equal(ckpt.exemplars, blocked.exemplars)


def test_single_block_wraps_to_topk():
    el = _graph(8)
    vals, idx = el.to_topk(5)
    lay = EdgeBlocks.single(vals, idx)
    assert lay.padded == el.n_nodes * 6
    assert lay.entries == np.minimum(el.degrees, 5).sum() + el.n_nodes
    assert lay.max_degree == 5
    assert np.array_equal(lay.offsets, [0, el.n_nodes])
