"""``EdgeList`` — the COO edge-list container behind graph-native input.

The paper's premise is that HAP needs "in principle only a similarity
measure between data points"; this module makes that literal for data
that already *is* a graph (social edges, web links, sparse similarity
dumps). An ``EdgeList`` holds directed weighted edges as three parallel
arrays (``src``, ``dst``, ``weight``) plus ``n_nodes``, and converts
both ways against the rest of the system:

* ``from_points`` / ``from_topk`` — the existing ``topk_build``
  pipeline's compressed ``(vals, idx)`` layout becomes an edge list, so
  every point input can feed the graph backend;
* ``to_topk`` / ``to_dense`` — an edge list becomes the compressed
  top-k layout (``dense_topk`` consumes it natively) or a dense
  ``(N, N)`` similarity matrix (every dense / distributed backend
  consumes it via the engine's densify routing);
* ``to_blocks`` — the same layout in row blocks of like degree, so a
  power-law graph's hubs do not set every row's width (``dense_topk``'s
  single-device sweep consumes it).

Conventions shared with the solver:

* weight = similarity (larger is better), matching the
  negative-squared-Euclidean build convention;
* tie-breaks everywhere are (weight desc, column asc) — the same
  (value desc, col asc) order every top-k build path implements, so
  ``from_topk(...).to_topk(k)`` round-trips bit-for-bit;
* a missing edge is "strongly repelling": padded/absent slots take
  ``inert_fill(weight)``, a value strictly below every stored weight,
  and padded top-k slots point back at their own row (the ``pad_topk``
  dummy convention) so they are inert in every sweep.

Everything here is host-side numpy on purpose — ingestion, validation
and layout conversion are one-shot data plumbing, not the iterated hot
path (that lives in ``repro.graph.affinity`` under jit).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def inert_fill(weight: np.ndarray) -> np.float32:
    """A weight strictly below every stored weight — the value a missing
    edge takes when an ``EdgeList`` is laid out densely or padded into
    the top-k layout. Data-scaled (``min - 2*span - 1``) rather than a
    fixed -1e9 so graphs whose weights live at any magnitude keep the
    "never preferred over a real edge" guarantee."""
    if weight.size == 0:
        return np.float32(-1.0)
    lo = float(weight.min())
    span = float(weight.max()) - lo
    return np.float32(lo - 2.0 * span - 1.0)


#: rows whose degree + 1 is at most this share the narrowest block of
#: ``EdgeList.to_blocks``: the layout's slack is at most this many
#: entries a row
MIN_BLOCK_WIDTH = 8


def _strictly_row_major(src: np.ndarray, dst: np.ndarray) -> bool:
    """Whether the edges are sorted (src asc, dst asc) with no duplicate."""
    if src.size < 2:
        return True
    ds = np.diff(src)                   # int32 ids: no difference overflows
    return bool(np.all((ds > 0) | ((ds == 0) & (np.diff(dst) > 0))))


def _width_class(width: np.ndarray) -> np.ndarray:
    """0 for a width up to ``MIN_BLOCK_WIDTH``, then c for
    ``MIN_BLOCK_WIDTH`` x (2^(c-1), 2^c]: the bit length of
    (width - 1) // ``MIN_BLOCK_WIDTH``."""
    q = (np.maximum(width, MIN_BLOCK_WIDTH) - 1) // MIN_BLOCK_WIDTH
    return np.frexp(q.astype(np.float64))[1].astype(np.int8)


@dataclasses.dataclass(frozen=True)
class EdgeBlocks:
    """An edge list laid out for the sparse sweep (``EdgeList.to_blocks``).

    ``vals[b]`` and ``idx[b]`` are block b's (N_b, w_b) off-diagonal
    similarities and laid-out column ids; its rows are the laid-out rows
    ``offsets[b]:offsets[b + 1]``, and ``nodes[i]`` is the original id of
    laid-out row i. ``single`` wraps a ``to_topk`` layout as one block.
    """
    nodes: np.ndarray
    vals: tuple
    idx: tuple
    n_edges: int
    max_degree: int

    @classmethod
    def single(cls, vals, idx) -> "EdgeBlocks":
        n = vals.shape[0]
        deg = np.sum(idx != np.arange(n)[:, None], axis=1)
        return cls(np.arange(n, dtype=np.int32), (vals,), (idx,),
                   int(deg.sum()), int(deg.max(initial=0)))

    @property
    def offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum([v.shape[0]
                                               for v in self.vals])])

    @property
    def entries(self) -> int:
        """Stored edges plus one self slot a row."""
        return self.n_edges + len(self.nodes)

    @property
    def padded(self) -> int:
        """Entries the sweep computes on, self slots included."""
        return sum(v.shape[0] * (v.shape[1] + 1) for v in self.vals)


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """Directed weighted COO edges over nodes ``0..n_nodes-1``.

    ``src[e] -> dst[e]`` with similarity ``weight[e]`` means "``dst`` can
    serve as an exemplar for ``src`` at that similarity". Validation at
    construction: equal-length 1-D arrays, finite weights, indices in
    range. Duplicates and self-loops are allowed in the container (they
    are real artifacts of scraped graphs) — ``deduplicated()`` /
    ``without_self_loops()`` / ``symmetrized()`` normalize explicitly,
    and ``canonical()`` is the composition the Borůvka backend requires.
    """
    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray
    n_nodes: int = 0

    def __post_init__(self):
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        w = np.asarray(self.weight)
        if not (src.ndim == dst.ndim == w.ndim == 1):
            raise ValueError(
                "EdgeList arrays must be 1-D; got shapes "
                f"src={src.shape}, dst={dst.shape}, weight={w.shape}")
        if not (src.shape == dst.shape == w.shape):
            raise ValueError(
                "EdgeList arrays must have equal length; got "
                f"src={src.shape[0]}, dst={dst.shape[0]}, "
                f"weight={w.shape[0]}")
        for name, a in (("src", src), ("dst", dst)):
            if not np.issubdtype(a.dtype, np.integer):
                raise ValueError(
                    f"EdgeList.{name} must be integer node ids; got "
                    f"dtype {a.dtype}")
        w = w.astype(np.float32)
        if w.size and not np.all(np.isfinite(w)):
            raise ValueError(
                "EdgeList.weight must be finite (no NaN/inf) — a missing "
                "edge is expressed by absence, not by an infinite weight")
        n = int(self.n_nodes)
        if n == 0:
            n = int(max(src.max(initial=-1), dst.max(initial=-1))) + 1
            n = max(n, 1)
        if n < 1:
            raise ValueError(f"EdgeList.n_nodes must be >= 1; got {n}")
        if src.size and (src.min() < 0 or dst.min() < 0
                         or src.max() >= n or dst.max() >= n):
            raise ValueError(
                f"EdgeList node ids must lie in [0, {n}); got "
                f"src in [{src.min()}, {src.max()}], "
                f"dst in [{dst.min()}, {dst.max()}]")
        object.__setattr__(self, "src", src.astype(np.int32))
        object.__setattr__(self, "dst", dst.astype(np.int32))
        object.__setattr__(self, "weight", w)
        object.__setattr__(self, "n_nodes", n)

    # ------------------------------------------------------------ queries
    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    @property
    def degrees(self) -> np.ndarray:
        """Out-degree per node (stored edges, duplicates counted)."""
        return np.bincount(self.src, minlength=self.n_nodes)

    @property
    def max_degree(self) -> int:
        return int(self.degrees.max(initial=0))

    @property
    def avg_degree(self) -> float:
        return self.n_edges / max(self.n_nodes, 1)

    # ------------------------------------------------------ normalization
    def without_self_loops(self) -> "EdgeList":
        """Drop ``src == dst`` edges — the diagonal is the preference
        slot in every solver layout, never an edge. A list without one
        comes back as it is."""
        keep = self.src != self.dst
        if keep.all():
            return self
        return EdgeList(self.src[keep], self.dst[keep], self.weight[keep],
                        self.n_nodes)

    def deduplicated(self) -> "EdgeList":
        """Collapse duplicate ``(src, dst)`` pairs, keeping the maximum
        weight (the same winner a segment-max selection would pick).
        Output is sorted (src asc, dst asc); a list that already is, with
        no duplicate, comes back as it is."""
        if self.n_edges == 0 or _strictly_row_major(self.src, self.dst):
            return self
        # primary src, secondary dst, then weight desc: the first edge of
        # each (src, dst) run is the keeper
        order = np.lexsort((-self.weight, self.dst, self.src))
        s, d, w = self.src[order], self.dst[order], self.weight[order]
        first = np.ones(len(s), bool)
        first[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
        return EdgeList(s[first], d[first], w[first], self.n_nodes)

    def symmetrized(self) -> "EdgeList":
        """Add every reverse edge, then deduplicate (max weight wins
        where both directions exist). Top-k built graphs are asymmetric
        by construction — i's best neighbors rarely reciprocate — and
        the Borůvka contraction's termination argument needs symmetry."""
        return EdgeList(
            np.concatenate([self.src, self.dst]),
            np.concatenate([self.dst, self.src]),
            np.concatenate([self.weight, self.weight]),
            self.n_nodes).deduplicated()

    def canonical(self) -> "EdgeList":
        """What ``graph_affinity`` actually clusters: no self-loops,
        symmetric, duplicate-free."""
        return self.without_self_loops().symmetrized()

    # ------------------------------------------------------- conversions
    @classmethod
    def from_topk(cls, vals, idx, n_nodes: int = 0) -> "EdgeList":
        """Compressed off-diagonal ``(N, k)`` layout -> COO edges, row
        major. The self/preference slot is *not* part of this layout
        (pass ``vals``/``idx`` from ``build_topk_similarity``, not the
        ``kk = k+1`` sweep layout)."""
        vals = np.asarray(vals, np.float32)
        idx = np.asarray(idx)
        if vals.ndim != 2 or vals.shape != idx.shape:
            raise ValueError(
                f"from_topk needs matching (N, k) arrays; got "
                f"vals={vals.shape}, idx={idx.shape}")
        n, k = vals.shape
        src = np.repeat(np.arange(n, dtype=np.int32), k)
        return cls(src, idx.astype(np.int32).ravel(), vals.ravel(),
                   n_nodes or n)

    @classmethod
    def from_points(cls, x, k: int, *, config=None) -> "EdgeList":
        """Points -> edge list through the existing ``topk_build``
        pipeline (``config.build`` picks reference / two-stage / fused /
        sharded — all bit-identical edge sets)."""
        import jax.numpy as jnp

        from repro.solver.config import SolveConfig
        from repro.solver.topk_build import build_topk_similarity

        cfg = config or SolveConfig()
        x = jnp.asarray(x, jnp.float32)
        vals, idx = build_topk_similarity(x, k, cfg)
        return cls.from_topk(np.asarray(vals), np.asarray(idx), x.shape[0])

    def _best_per_row(self, k: int):
        """Per row the k best edges by (weight desc, dst asc), as
        ``(src, dst, weight)`` sorted (src asc, dst asc)."""
        order = np.lexsort((self.dst, -self.weight, self.src))
        s = self.src[order]
        starts = np.concatenate(
            [[0], np.cumsum(np.bincount(s, minlength=self.n_nodes))[:-1]])
        keep = (np.arange(len(s)) - starts[s]) < k
        ks, kd, kw = s[keep], self.dst[order][keep], self.weight[order][keep]
        order2 = np.lexsort((kd, ks))
        return ks[order2], kd[order2], kw[order2]

    def to_topk(self, k: Optional[int] = None, fill=None
                ) -> tuple[np.ndarray, np.ndarray]:
        """Edges -> the compressed ``(N, k)`` off-diagonal layout.

        Per row keep the k best edges by (weight desc, dst asc), emitted
        in column-ascending order — the exact layout every build backend
        produces, so ``from_topk(vals, idx).to_topk(k)`` is a bit-exact
        round trip. ``k=None`` keeps every edge (k = max out-degree).
        Short rows pad with ``(fill, row)`` — an inert self-pointing slot
        per the ``pad_topk`` dummy convention. Duplicates are not merged
        here; call ``deduplicated()`` first for scraped graphs.
        """
        n = self.n_nodes
        if k is None:
            k = max(self.max_degree, 1)
        if k < 1:
            raise ValueError(f"to_topk needs k >= 1; got {k}")
        if fill is None:
            fill = inert_fill(self.weight)
        vals = np.full((n, k), fill, np.float32)
        idx = np.broadcast_to(
            np.arange(n, dtype=np.int32)[:, None], (n, k)).copy()
        if self.n_edges == 0:
            return vals, idx
        # the keepers, emitted column-ascending (the build layout)
        ks, kd, kw = self._best_per_row(k)
        starts2 = np.concatenate(
            [[0], np.cumsum(np.bincount(ks, minlength=n))[:-1]])
        pos = np.arange(len(ks)) - starts2[ks]
        vals[ks, pos] = kw
        idx[ks, pos] = kd
        return vals, idx

    def to_blocks(self, k: Optional[int] = None, fill=None) -> "EdgeBlocks":
        """Edges -> the ``to_topk`` layout in row blocks of like degree.

        A row's width class is that of its degree + 1: one class for
        every width up to ``MIN_BLOCK_WIDTH``, then one for each range
        (w/2, w] of a power of two w. Nodes are relabelled once, in order
        of (class, original id), so laid-out row i is node ``nodes[i]``,
        a block's rows are one range of laid-out ids, and every (N,)
        vector of the sweep is the concatenation of the blocks'. Each
        block is as wide as its widest row (at least one edge slot) and
        laid out as ``to_topk`` lays out rows: neighbours in ascending
        order of their original ids, named by laid-out id, then padding
        ``(fill, own row)``. A row's width with its self slot is then at
        most ``MIN_BLOCK_WIDTH`` or below 2 x (degree + 1): the blocks
        hold fewer than 2 x (E + N) + ``MIN_BLOCK_WIDTH`` x N entries.

        With a single class no node moves, and the one block is
        ``to_topk`` at the largest degree, bit for bit. ``k`` first keeps
        each row's k best edges by (weight desc, dst asc), as ``to_topk``
        does. Duplicates are not merged here.
        """
        n = self.n_nodes
        if k is not None and k < 1:
            raise ValueError(f"to_blocks needs k >= 1; got {k}")
        if fill is None:
            fill = inert_fill(self.weight)
        if k is None and _strictly_row_major(self.src, self.dst):
            src, dst, w = self.src, self.dst, self.weight
        else:
            src, dst, w = self._best_per_row(
                k if k is not None else max(self.max_degree, 1))
        first = np.searchsorted(src, np.arange(n + 1))    # rows are sorted
        deg = np.diff(first)
        cls = _width_class(deg + 1)
        nodes = np.argsort(cls, kind="stable").astype(np.int32)
        laid = np.empty(n, np.int32)
        laid[nodes] = np.arange(n, dtype=np.int32)
        lo = np.flatnonzero(np.diff(cls[nodes], prepend=-1))
        rows = np.diff(lo, append=n)
        width = np.maximum(np.maximum.reduceat(deg[nodes], lo), 1)
        # one flat buffer, block after block, row-major inside each
        base = np.concatenate([[0], np.cumsum(rows * width)])
        blk = np.repeat(np.arange(len(lo)), rows)       # laid-out row -> block
        row_w = width[blk]
        row_at = base[blk] + (np.arange(n) - lo[blk]) * row_w
        vals = np.full(int(base[-1]), fill, np.float32)
        idx = np.repeat(np.arange(n, dtype=np.int32), row_w)
        # edge e of node v (its j-th) lands at row_at[laid[v]] + j
        at = np.repeat(row_at[laid] - first[:-1], deg) + np.arange(len(src))
        vals[at] = w
        idx[at] = laid[dst]
        cut = [(int(a), int(b)) for a, b in zip(base[:-1], base[1:])]
        return EdgeBlocks(
            nodes=nodes,
            vals=tuple(vals[a:b].reshape(r, wd) for (a, b), r, wd
                       in zip(cut, rows, width)),
            idx=tuple(idx[a:b].reshape(r, wd) for (a, b), r, wd
                      in zip(cut, rows, width)),
            n_edges=len(src), max_degree=int(deg.max(initial=0)))

    def to_dense(self, fill=None) -> np.ndarray:
        """Edges -> dense ``(N, N)`` similarity, missing entries =
        ``fill`` (default ``inert_fill``), duplicates collapsed to their
        max weight, self-loops dropped. The diagonal is left at ``fill``
        — the engine writes preferences there, same contract as the
        points path."""
        if fill is None:
            fill = inert_fill(self.weight)
        s = np.full((self.n_nodes, self.n_nodes), fill, np.float32)
        d = self.without_self_loops().deduplicated()
        s[d.src, d.dst] = d.weight
        return s

    # ------------------------------------------------------- preferences
    def edge_preferences(self, strategy, *, seed: int = 0) -> np.ndarray:
        """Preference vector from the stored weights — the edge-list
        analogue of ``topk_preferences``. ``median`` / ``range_mid``
        reduce over the stored weight multiset (on a symmetrized list
        that multiset is the dense off-diagonal multiset restricted to
        present edges); floats / (N,) arrays broadcast through."""
        n = self.n_nodes
        if strategy is None:
            return np.zeros((n,), np.float32)
        if not isinstance(strategy, str):
            return np.broadcast_to(
                np.asarray(strategy, np.float32), (n,)).copy()
        if strategy == "constant":
            return np.zeros((n,), np.float32)
        if self.n_edges == 0:
            return np.zeros((n,), np.float32)
        if strategy == "median":
            return np.full((n,), np.median(self.weight), np.float32)
        if strategy == "range_mid":
            mid = 0.5 * (float(self.weight.min()) + float(self.weight.max()))
            return np.full((n,), mid, np.float32)
        if strategy == "random":
            import jax

            from repro.core.preferences import random_preference
            return np.asarray(random_preference(
                jax.random.PRNGKey(seed), n, dtype=np.float32))
        raise ValueError(f"unknown preference strategy: {strategy!r}")
