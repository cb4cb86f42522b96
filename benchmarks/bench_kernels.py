"""Kernel microbenchmarks: Pallas (interpret on CPU) vs jnp oracle.

On this container the oracle path is the performance-relevant one (Pallas
interpret mode is a correctness harness, orders slower than compiled jnp);
the derived column records the kernel's analytic FLOPs/bytes so the TPU
roofline expectation is on record next to the measured oracle time.

Also times one full solver sweep of the ``dense_fused`` backend (the
Pallas responsibility/availability kernels wired into the per-level HAP
hot loop) against the jnp ``dense_parallel`` sweep — on CPU the fused
column measures interpret-mode overhead; on TPU it is the headline number.

    PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke] [--json P]

An end-to-end ``coarsen`` solve row rides both tiers so the two-level
partition -> local solves -> global stage pipeline's steady-state wall
clock is gated on PRs like any kernel.

``--smoke`` shrinks sizes/reps so CI can run the whole file in seconds
and still catch compile regressions in every kernel. Every run also
writes a machine-readable ``BENCH_kernels.json`` (``--json`` overrides
the path) that ``check_regression.py`` gates against the committed
``benchmarks/baseline_smoke.json``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

try:
    from benchmarks._emit import emit
except ImportError:  # executed as a script: benchmarks/ is sys.path[0]
    from _emit import emit


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / reps


def run(n: int = 1024, reps: int = 5, sweep_n: int = 256,
        sweep_iters: int = 3) -> list:
    rng = np.random.default_rng(0)
    s = jnp.asarray(-rng.random((n, n)).astype(np.float32) * 10)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    r = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    tau = jnp.full((n,), jnp.inf)
    c = jnp.zeros((n,))
    phi = jnp.zeros((n,))
    x = jnp.asarray(rng.standard_normal((n, 64)).astype(np.float32))

    # arrays passed as ARGUMENTS (closure constants get constant-folded
    # away by XLA, timing nothing)
    resp_j = jax.jit(lambda s_, a_: ref.responsibility(
        s_, a_, tau, r, 0.5))
    avail_j = jax.jit(lambda r_, a_: ref.availability(r_, c, phi, a_, 0.5))
    sim_j = jax.jit(lambda x_: ref.neg_sqeuclidean(x_, x_))
    resp = lambda: resp_j(s, a)
    avail = lambda: avail_j(r, a)
    sim = lambda: sim_j(x)

    bh, sq, dh = 4, max(n // 2, 64), 64
    qkv = jnp.asarray(rng.standard_normal((bh, sq, dh)).astype(np.float32))
    flash_j = jax.jit(lambda q_: ref.flash_attention(q_, q_, q_, True))
    flash = lambda: flash_j(qkv)

    rows = [
        {"name": "responsibility", "us": _time(resp, reps=reps) * 1e6,
         "flops": 4 * n * n, "bytes": 4 * n * n * 4},
        {"name": "availability", "us": _time(avail, reps=reps) * 1e6,
         "flops": 4 * n * n, "bytes": 4 * n * n * 4},
        {"name": "similarity", "us": _time(sim, reps=reps) * 1e6,
         "flops": 2 * n * n * 64, "bytes": (2 * n * 64 + n * n) * 4},
        {"name": "flash_attention", "us": _time(flash, reps=reps) * 1e6,
         "flops": 4 * bh * sq * sq * dh,
         "bytes": 4 * bh * sq * dh * 4},  # flash: O(S*D), not O(S^2)
    ]
    rows += run_solver_sweeps(sweep_n, sweep_iters, reps)
    return rows


def run_solver_sweeps(n: int, iters: int, reps: int) -> list:
    """dense_fused (Pallas kernels in the hot loop) vs dense_parallel
    (jnp sweeps) vs dense_topk (compressed layout) through the one
    stopping-rule driver all three share."""
    from repro.data import gaussian_blobs
    from repro.solver.dense import run_dense
    from repro.solver.topk import build_from_points, run_topk

    x, _ = gaussian_blobs(n=n, k=5, seed=0)
    from repro.core.preferences import median_preference
    from repro.core.similarity import (
        pairwise_similarity, set_preferences, stack_levels,
    )
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    s3 = stack_levels(s, 3)
    # per-sweep analytic cost of the two kernel updates, all levels
    flops = 2 * 4 * 3 * n * n
    bytes_ = 2 * 4 * 3 * n * n * 4
    rows = []
    for order in ("parallel", "fused"):
        fn = lambda s3_: run_dense(s3_, order=order, max_iterations=iters,
                                   damping=0.6)[1]
        t = _time(fn, s3, reps=reps) / iters
        rows.append({"name": f"hap_sweep_{order}_n{n}", "us": t * 1e6,
                     "flops": flops, "bytes": bytes_})

    # sparse top-k: same schedule on the (N, k+1) compressed layout
    k = min(32, n - 1)
    xj = jnp.asarray(x)
    build = lambda x_: build_from_points(x_, k, 3)[0]
    t = _time(build, xj, reps=reps)
    rows.append({"name": f"topk_build_n{n}_k{k}", "us": t * 1e6,
                 "flops": 2 * n * n * x.shape[1],
                 "bytes": (n * x.shape[1] + n * k) * 4})
    s3k, idx = build_from_points(xj, k, 3)
    fn = lambda s3k_: run_topk(s3k_, idx, max_iterations=iters,
                               damping=0.6)[1]
    t = _time(fn, s3k, reps=reps) / iters
    rows.append({"name": f"hap_sweep_topk_n{n}_k{k}", "us": t * 1e6,
                 "flops": 2 * 4 * 3 * n * (k + 1),
                 "bytes": 2 * 4 * 3 * n * (k + 1) * 4})

    # the row-sharded sweep program (repro.solver.topk_sharded) on the
    # host mesh: with one CI device this times the full shard_map/
    # collective machinery at W=1 — a compile + dispatch-overhead canary
    # for the distributed path (real 8-worker runs: nightly slow tier)
    from repro.launch.mesh import make_worker_mesh
    from repro.solver.topk_sharded import run_topk_sharded
    mesh = make_worker_mesh()
    fn = lambda s3k_: run_topk_sharded(s3k_, idx, mesh,
                                       max_iterations=iters, damping=0.6)[1]
    t = _time(fn, s3k, reps=reps) / iters
    rows.append({"name": f"hap_sweep_topk_sharded_n{n}_k{k}", "us": t * 1e6,
                 "flops": 2 * 4 * 3 * n * (k + 1),
                 "bytes": 2 * 4 * 3 * n * (k + 1) * 4,
                 "mesh": [mesh.shape["workers"]]})

    # graph_affinity: the full Borůvka contraction while_loop over the
    # same compressed layout (per-round cost, not per-HAP-sweep — rounds
    # to convergence is O(log N), so this times the whole solve)
    from repro.graph import EdgeList
    from repro.graph.affinity import run_graph_affinity
    el = EdgeList.from_topk(np.asarray(s3k[0][:, 1:]),
                            np.asarray(idx[:, 1:])).canonical()
    gvals, gidx = el.to_topk()
    fn = lambda v_: run_graph_affinity(v_, gidx, levels=1)[0]
    t = _time(fn, gvals, reps=reps)
    rows.append({"name": f"graph_affinity_n{n}_k{k}", "us": t * 1e6,
                 # ~log2(N) rounds x 2 segment reductions over N*deg slots
                 "flops": 2 * int(np.log2(n)) * el.n_edges,
                 "bytes": 2 * int(np.log2(n)) * el.n_edges * 4})
    return rows


def run_coarsen_solve(n: int, reps: int) -> list:
    """End-to-end two-level ``coarsen`` solve row: kd partition ->
    batched local dense solves -> global exemplar stage -> broadcast
    assignment. Timed after a warmup call so the AOT local-solver
    compile (cached across calls) is excluded — the row gates the
    steady-state pipeline, not the compiler."""
    from repro.data import gaussian_blobs
    from repro.solver import solve

    part, iters = 128, 10
    x, _ = gaussian_blobs(n=n, k=8, seed=0, spread=0.4)
    kw = dict(backend="coarsen", partition_size=part, levels=2,
              max_iterations=iters, damping=0.7, preference="median")
    solve(x, **kw)                              # warmup + compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        solve(x, **kw)
        best = min(best, time.time() - t0)
    cells = max(n // part, 1)
    return [{"name": f"coarsen_solve_n{n}_p{part}", "us": best * 1e6,
             # local stage dominates: 2 kernels x 4 flops/entry per sweep
             # over every cell's part^2 block (global stage is O(E^2))
             "flops": 2 * 4 * iters * cells * part * part,
             "bytes": 3 * part * part * 8 * 4}]


def run_checkpointed_solve(n: int, reps: int) -> list:
    """Checkpointed dense_topk solve row: the segmented while-loop
    program plus a host state snapshot per segment boundary. Timed after
    a warmup call, so the row gates the steady-state checkpointing
    overhead (segment re-dispatch, device->host state pull, atomic tmp+
    rename save) — the price of crash-resumable solves staying small."""
    import tempfile

    from repro.data import gaussian_blobs
    from repro.solver import solve

    k, iters, every = 16, 12, 4
    x, _ = gaussian_blobs(n=n, k=8, seed=0, spread=0.4)
    best = float("inf")
    with tempfile.TemporaryDirectory() as d:
        kw = dict(backend="dense_topk", k=k, stop="fixed",
                  max_iterations=iters, damping=0.7, preference="median",
                  checkpoint_every=every, checkpoint_dir=d)
        solve(x, **kw)                          # warmup + compile
        for _ in range(reps):
            t0 = time.time()
            solve(x, **kw)
            best = min(best, time.time() - t0)
    segments = (iters + every - 1) // every
    return [{"name": f"checkpointed_solve_n{n}", "us": best * 1e6,
             # sweep arithmetic as in the plain solve; traffic adds one
             # full compressed-state round trip per segment boundary
             "flops": 2 * 4 * iters * n * (k + 1),
             "bytes": segments * 6 * n * (k + 1) * 4}]


def run_topk_build(tier: str) -> list:
    """Top-k similarity build tier: the perf target of the fused/sharded
    build PR. Times each build backend on the same blob suite so the
    reference-vs-two-stage speedup is on record (``BENCH_topk_build.json``;
    the smoke rows also ride the kernels gate).

    Every row carries the mesh the build ran on (``[workers]``; the
    sharded row runs the real shard_map driver) so records from
    differently-sized runners stay comparable.
    """
    import jax.numpy as jnp

    from repro.data import gaussian_blobs
    from repro.kernels.topk_similarity import (
        topk_similarity, topk_similarity_twostage)
    from repro.kernels.topk_build_fused import topk_similarity_fused
    from repro.launch.mesh import make_worker_mesh
    from repro.solver.config import SolveConfig
    from repro.solver.topk_build import sharded_topk_similarity

    k = 32
    n = 2048 if tier == "smoke" else 100_000
    x, _ = gaussian_blobs(n=n, k=7, seed=0)
    xj = jnp.asarray(x)
    d = x.shape[1]
    flops = 2 * n * n * d
    bytes_ = (n * d + n * k) * 4
    mesh = make_worker_mesh()
    w = mesh.shape["workers"]

    def row(name, fn, mesh_shape, reps):
        # best-of-reps: shared-runner wall clocks flap tens of percent
        # run-to-run, and the floor is the comparable number
        jax.block_until_ready(fn(xj))
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn(xj))
            best = min(best, time.time() - t0)
        return {"name": f"topk_build_{name}_n{n}_k{k}", "us": best * 1e6,
                "flops": flops, "bytes": bytes_, "mesh": mesh_shape}

    fast_reps = 3
    rows = [
        row("ref", lambda x_: topk_similarity(x_, k), [1],
            reps=3 if tier == "smoke" else 1),
        row("twostage", lambda x_: topk_similarity_twostage(x_, k), [1],
            fast_reps),
        row("sharded",
            lambda x_: sharded_topk_similarity(x_, k, SolveConfig(),
                                               mesh=mesh), [w],
            fast_reps),
    ]
    # fused runs interpret-mode here (CPU container): a compile +
    # correctness canary, only worth timing at a tiny size
    nf = 256
    xf = jnp.asarray(gaussian_blobs(n=nf, k=4, seed=1)[0])
    t = _time(lambda x_: topk_similarity_fused(x_, 16), xf, reps=1)
    rows.append({"name": f"topk_build_fused_interp_n{nf}_k16",
                 "us": t * 1e6, "flops": 2 * nf * nf * 2,
                 "bytes": (nf * 2 + nf * 16) * 4, "mesh": [1]})
    ref_us = rows[0]["us"]
    two_us = rows[1]["us"]
    print(f"topk_build n={n} k={k}: reference {ref_us / 1e6:.2f}s, "
          f"twostage {two_us / 1e6:.2f}s "
          f"({ref_us / max(two_us, 1e-9):.1f}x)")
    return rows


def main(argv=None):
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes / 1 rep: CI compile-regression check")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="override the BENCH_kernels.json output path")
    ap.add_argument("--topk-build-tier", default=None,
                    choices=["smoke", "full", "skip"],
                    help="top-k build bench tier (default: smoke, full "
                         "sizes in the nightly trajectory)")
    args = ap.parse_args(argv)
    if args.smoke:
        # reps=3 and non-tiny sizes: single-rep sub-millisecond timings
        # flap 2-3x run-to-run on shared runners, which would flake the
        # regression gate (it only arms on rows above its --min-us floor)
        rows = run(n=256, reps=3, sweep_n=192, sweep_iters=2)
        rows += run_coarsen_solve(n=1024, reps=3)
        rows += run_checkpointed_solve(n=256, reps=3)
    else:
        rows = run()
        rows += run_coarsen_solve(n=4096, reps=3)
        rows += run_checkpointed_solve(n=2048, reps=3)
    build_tier = args.topk_build_tier or "smoke"
    build_rows = [] if build_tier == "skip" else run_topk_build(build_tier)
    if build_tier == "smoke":
        # smoke build rows ride the kernels record so the committed
        # baseline_smoke.json gates build-path regressions on PRs
        rows = rows + build_rows
    for r in rows:
        ai = r["flops"] / r["bytes"]
        print(f"kernel_{r['name']},{r['us']:.0f},"
              f"flops={r['flops']:.2e} ai={ai:.2f}")
    path = emit("kernels", rows, meta={"smoke": args.smoke})
    if build_rows:
        emit("topk_build", build_rows, meta={"tier": build_tier})
    if args.json and args.json != path:
        import shutil
        shutil.copy(path, args.json)
    return rows


if __name__ == "__main__":
    main()
