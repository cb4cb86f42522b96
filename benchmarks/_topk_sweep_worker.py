"""Subprocess body for bench_scaling's ``topk_sweep`` suite: time the
dense_topk Jacobi loop — single-device or row-sharded — on the forced
device count and print one JSON line.

The compressed (L, N, k+1) layout is *synthesized* (descending random
neighbor values, random neighbor columns, constant preference) instead
of built from points: the sweep's cost depends only on the layout shape,
the build is O(N^2) and benched separately (``BENCH_topk_build.json``),
and decoupling lets the sweep rows reach N = 10^6 on this container.
Synthesis and compile happen outside the timed region.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def synth_topk(n: int, k: int, levels: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    vals = np.sort(rng.standard_normal((n, k)).astype(np.float32) - 2.0,
                   axis=1)[:, ::-1]               # descending, like a build
    idx = np.concatenate(
        [np.arange(n, dtype=np.int32)[:, None],
         rng.integers(0, n, (n, k)).astype(np.int32)], axis=1)
    s_rows = np.concatenate(
        [np.full((n, 1), -4.0, np.float32), vals], axis=1)
    s3k = np.broadcast_to(s_rows[None], (levels, n, k + 1))
    return jnp.asarray(s3k), jnp.asarray(idx)


def main(n: int, k: int, levels: int, iterations: int, sweep: str,
         exchange: str) -> None:
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    from repro.solver.topk import run_topk
    from repro.solver.topk_sharded import (
        comm_bytes_per_sweep, resolve_exchange, run_topk_sharded)

    s3k, idx = synth_topk(n, k, levels)
    jax.block_until_ready(s3k)
    workers = len(jax.devices())

    if sweep == "sharded":
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh()
        n_pad = n + (-n) % workers
        exchange = resolve_exchange(exchange, n=n_pad, kk=k + 1)
        run = lambda: run_topk_sharded(
            s3k, idx, mesh, max_iterations=iterations, damping=0.7)[1]
        comm = comm_bytes_per_sweep(n_pad, k, levels, workers, exchange)
    else:
        exchange = "none"
        run = lambda: run_topk(
            s3k, idx, max_iterations=iterations, damping=0.7)[1]
        comm = 0

    jax.block_until_ready(run())    # compile once, then time
    t0 = time.time()
    jax.block_until_ready(run())
    wall = time.time() - t0

    # s/r/a are the O(L*N*kk) tensors; each worker persists only its rows
    state_dev = 3 * levels * ((n + workers - 1) // workers) * (k + 1) * 4
    print(json.dumps({
        "workers": workers, "sweep": sweep, "exchange": exchange,
        "n": n, "k": k, "levels": levels, "iterations": iterations,
        "wall_s": wall, "us_per_sweep": wall * 1e6 / iterations,
        "state_bytes_per_device": state_dev, "comm_bytes_sweep": comm,
    }))


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
         int(sys.argv[4]), sys.argv[5], sys.argv[6])
