"""Bytes and operations that one call must move, counted from shapes.

Each count is a lower bound on what the algorithm needs, so a share of
the roofline built on it cannot pass 100%: every array the step reads is
read once, every array it writes is written once, nothing is re-read.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def topk_sweep_bytes(n: int, kk: int, levels: int) -> int:
    """One Jacobi sweep of sparse HAP on the (L, N, kk) layout.

    Per level it reads s, r, a and writes r, a (5 arrays of N x kk f32);
    the (N, kk) int32 column map is shared by the levels and read once.
    The (L, N) vectors (tau, phi, c) are O(N) and left out."""
    return levels * 5 * n * kk * F32 + n * kk * I32


def topk_sweep_flops(n: int, kk: int, levels: int) -> int:
    """Elementwise work per sweep: about 20 operations per stored entry
    per level (rho: add, top-2, subtract, min, damp; alpha: clamp, sums,
    min, damp; c/phi maxes)."""
    return 20 * levels * n * kk


def dense_sweep_bytes(n: int, levels: int) -> int:
    """One Jacobi sweep of dense HAP on an (L, n, n) stack for one
    request: reads s, r, a and writes r, a."""
    return levels * 5 * n * n * F32


def dense_sweep_flops(n: int, levels: int) -> int:
    """Elementwise work of the same sweep, about 20 operations per entry
    per level, as ``topk_sweep_flops``."""
    return 20 * levels * n * n


def bound(bytes_moved: float, flops: float, peaks: dict) -> tuple:
    """(least seconds, "memory" | "compute") for work of this size."""
    t_mem = bytes_moved / peaks["hbm_bytes_per_s"]
    t_cmp = flops / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
