"""Graph500 Kronecker graphs, made from a seed on the host with numpy.

The generator follows the Graph500 specification's "Graph Generation"
(graph500.org): ``edgefactor * 2^scale`` edges, each placed bit by bit
in one quadrant of the adjacency matrix with initiator probabilities
A, B, C and D = 1 - A - B - C; the vertex labels are then permuted at
random, and each edge takes a weight uniform in [0, 1) (the
specification's SSSP kernel). The quadrants come from ``graph_seed``,
one fixed graph as a data set file holds it; the label permutation and
the weights from ``seed``, so every seed solves the same graph up to
its vertex numbering, with other weights. The graph is then made what an affinity
solve takes, as LDBC Graphalytics lists its ``graph500-*`` sets: self
loops dropped, every edge in both directions with the larger weight of
a pair kept, and vertices without an edge dropped and the rest numbered
0..N-1 in label order.
"""
from __future__ import annotations

import numpy as np


def generate(scale: int, edgefactor: int, initiator, graph_seed: int,
             seed: int):
    """-> (src, dst, weight, N): int32, int32, float32 arrays sorted by
    (src, dst) with no duplicate pair, and the vertex count."""
    a, b, c = (float(p) for p in initiator[:3])
    rng = np.random.default_rng([int(graph_seed), 0x6b726f6e])
    n, m = 1 << int(scale), int(edgefactor) << int(scale)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    c_norm, a_norm = c / (1.0 - a - b), a / (a + b)
    for bit in range(int(scale)):
        down = rng.random(m, dtype=np.float32) > a + b
        right = rng.random(m, dtype=np.float32) > np.where(
            down, np.float32(c_norm), np.float32(a_norm))
        src += down.astype(np.int64) << bit
        dst += right.astype(np.int64) << bit
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x6b726f6e])
    label = rng.permutation(n)
    src, dst = label[src], label[dst]
    weight = rng.random(m, dtype=np.float32)
    keep = src != dst
    src, dst, weight = src[keep], dst[keep], weight[keep]
    key = np.concatenate([src * n + dst, dst * n + src])
    weight = np.concatenate([weight, weight])
    order = np.argsort(key)
    key, weight = key[order], weight[order]
    first = np.flatnonzero(np.diff(key, prepend=-1))
    weight = np.maximum.reduceat(weight, first)
    key = key[first]
    src, dst = key // n, key % n
    used = np.flatnonzero(np.bincount(src, minlength=n))
    number = np.full(n, -1, np.int64)
    number[used] = np.arange(len(used))
    return (number[src].astype(np.int32), number[dst].astype(np.int32),
            weight.astype(np.float32), int(len(used)))
