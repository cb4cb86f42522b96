"""Subprocess body for bench_scaling: runs distributed HAP through the
solver engine on the forced device count and prints one JSON line.

The similarity build + preferences + padding are worker-count-independent
setup, so they happen (and compile) outside the timed region — the timed
call receives a pre-padded (L, N', N') stack and measures the distributed
sweeps (plus the engine's O(L*N) host finalize)."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    pad_similarity, pairwise_similarity, set_preferences, stack_levels,
)
from repro.core.preferences import median_preference
from repro.data import gaussian_blobs
from repro.solver import solve


def main(n: int, levels: int, iterations: int, mode: str) -> None:
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    x, _ = gaussian_blobs(n=n, k=7, seed=0)
    workers = len(jax.devices())
    backend = f"mr1d_{mode}"
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    s3p, _ = pad_similarity(stack_levels(s, levels), workers)
    jax.block_until_ready(s3p)

    run = lambda: solve(s3p, backend=backend, max_iterations=iterations,
                        damping=0.6)
    run()                       # compile once, then time
    t0 = time.time()
    res = run()
    wall = time.time() - t0
    # the engine saw the pre-padded stack, so count clusters over the
    # first n REAL points (each padding dummy is its own singleton)
    k0 = len(np.unique(res.exemplars[0][:n]))
    print(json.dumps({
        "workers": workers, "mode": mode, "n": n, "levels": levels,
        "iterations": iterations, "wall_s": wall, "k_level0": k0,
    }))


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
