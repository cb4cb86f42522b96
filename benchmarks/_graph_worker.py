"""Subprocess body for bench_graph: run the Borůvka contraction loop —
single-device or row-sharded on the forced device count — and print one
JSON line.

The edge list is synthesized at fixed average degree (random endpoints,
weights from a small value set so selections are tie-heavy like real
similarity dumps) and canonicalized outside the timed region; compile is
excluded by a warmup call. Wall clock covers the full jitted while_loop
to convergence, so ``rounds`` rides along for the us/round derivation.
"""
import json
import sys
import time

import jax
import numpy as np


def synth_graph(n: int, deg: int, seed: int = 0):
    from repro.graph import EdgeList
    rng = np.random.default_rng(seed)
    m = deg * n
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.choice(np.asarray([1.0, 2.0, 3.0, 4.0], np.float32), m)
    return EdgeList(src, dst, w, n_nodes=n).canonical()


def main(n: int, deg: int, sweep: str) -> None:
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    from repro.graph.affinity import run_graph_affinity

    el = synth_graph(n, deg)
    vals, idx = el.to_topk()
    workers = len(jax.devices())
    mesh = None
    if sweep == "sharded":
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh()

    run = lambda: run_graph_affinity(vals, idx, levels=1, mesh=mesh)
    jax.block_until_ready(run()[0])     # compile once, then time
    t0 = time.time()
    hist, rounds, conv, trace = run()
    jax.block_until_ready(hist)
    wall = time.time() - t0

    labels = np.asarray(hist)[0]
    print(json.dumps({
        "workers": workers, "sweep": sweep, "n": n, "deg": deg,
        "edges": int(el.n_edges), "rounds": int(rounds),
        "converged": bool(conv), "clusters": int(len(np.unique(labels))),
        "wall_s": wall,
        "us_per_round": wall * 1e6 / max(int(rounds), 1),
    }))


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), sys.argv[3])
