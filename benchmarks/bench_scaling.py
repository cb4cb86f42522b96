"""Fig 4.3 analogue + the beyond-paper single-device N sweep.

Two suites, both recorded into ``BENCH_scaling.json``:

``mrhap`` — the paper's figure: MR-HAP runtime & communication vs worker
count. The paper scales EC2 VMs 1..80 and shows MR-HAP hitting
linear-in-data runtime. This container has ONE physical core, so
wall-clock over forced host devices measures overhead, not speedup; the
bench therefore reports BOTH measured wall time and the two analytic
scaling columns the paper's figure is about:

  work_per_worker = k * L * N^2 / W      (O(kN) as W -> LN, paper §3.1)
  comm_bytes      = per-iteration cluster traffic for the paper-faithful
                    transpose mode vs the beyond-paper stats mode

Workers run in subprocesses (benchmarks/_scaling_worker.py) so each sees
its own forced device count; they run on virtual CPU devices
(``JAX_PLATFORMS=cpu``, rows stamped ``platform: cpu``), never on a chip
the parent may hold.

``topk`` — dense vs sparse single-device scaling out to N = 2*10^5: the
dense backends stop at the quadratic-state budget (rows past the cap are
recorded as ``skipped``: 3 * L * N^2 f32 message tensors at N = 2e5
would be ~1 TB); ``dense_topk`` keeps O(L*N*k) state and runs the full
range — the paper's linear-complexity headline realized on one device.

``topk_sweep`` — the sharded *sweep* column (ISSUE 6): the dense_topk
Jacobi loop timed single-device vs row-sharded over 8 forced host
devices (subprocess workers, ``_topk_sweep_worker.py``), N swept to
10^6 on a synthesized compressed layout. As with the ``mrhap`` suite,
wall clock over forced devices on this 1-core container measures
dispatch/collective overhead, not speedup; the scaling claim lives in
the recorded analytic columns — ``state_bytes_per_device`` drops by the
worker count (the psum exchange keeps every per-device buffer O(N/W*k) +
O(N)), which is what raises the memory-bound max-N by ~W at fixed
per-device budget — plus the measured fact that the sharded program
*runs* the same N bit-exactly (nightly parity check).

``coarsen`` — the two-level backend vs the flat ``dense_topk`` path,
end-to-end wall clock on the same blob suite (emitted separately into
``BENCH_coarsen.json``). dense_topk rows past ``topk_cap`` are recorded
as skipped — the O(N)-column build and O(L*N*k) message state are
exactly the walls the coarsen decomposition sidesteps. Each ok row
carries L0 purity against the generating labels so the
decomposition-quality trajectory is recorded next to the speed
trajectory (``benchmarks/records/coarsen_full.json`` holds the
paper-scale N = 1e6 / 1e7 run).

    PYTHONPATH=src python benchmarks/bench_scaling.py [--tier smoke|full]
"""
from __future__ import annotations

import os
import time

from repro.core.mrhap import comm_bytes_per_iteration

try:
    from benchmarks._emit import emit, peak_rss_mb, run_cpu_child
except ImportError:  # executed as a script: benchmarks/ is sys.path[0]
    from _emit import emit, peak_rss_mb, run_cpu_child

WORKER = os.path.join(os.path.dirname(__file__), "_scaling_worker.py")
SWEEP_WORKER = os.path.join(os.path.dirname(__file__),
                            "_topk_sweep_worker.py")

#: N above which the dense O(L*N^2) backends are skipped (not attempted):
#: at 8192 the three (2, N, N) f32 message tensors already take ~1.6 GB;
#: the topk rows keep going.
DENSE_STATE_CAP = 4096


def run(n: int = 512, levels: int = 3, iterations: int = 20,
        worker_counts=(1, 2, 4, 8), modes=("stats", "transpose")) -> list:
    rows = []
    for mode in modes:
        for w in worker_counts:
            rec = run_cpu_child(WORKER, (n, levels, iterations, mode), w,
                                timeout=900)
            rec["work_per_worker"] = iterations * levels * n * n // w
            rec["comm_bytes_iter"] = comm_bytes_per_iteration(
                n, levels, w, mode)
            rows.append(rec)
    return rows


def run_topk_scaling(sizes=(1024, 4096, 16384, 65536, 200_000), k: int = 32,
                     levels: int = 2, iterations: int = 15,
                     dense_cap: int = DENSE_STATE_CAP) -> list:
    """Dense vs sparse single-device N sweep (the ``topk`` suite)."""
    from repro.data import gaussian_blobs
    from repro.solver import solve

    rows = []
    for n in sizes:
        x, _ = gaussian_blobs(n=n, k=16, seed=0, spread=0.5)
        for backend in ("dense_parallel", "dense_topk"):
            base = {"suite": "topk", "backend": backend, "n": n,
                    "levels": levels, "iterations": iterations}
            if backend == "dense_parallel":
                base["state_bytes"] = 3 * levels * n * n * 4
                if n > dense_cap:
                    rows.append({**base, "status": "skipped",
                                 "reason": "O(L*N^2) message state past "
                                           "the single-device budget"})
                    continue
                kw = {}
            else:
                base["k"] = k
                base["state_bytes"] = 3 * levels * n * (k + 1) * 4
                kw = {"k": k}
            t0 = time.time()
            res = solve(x, backend=backend, levels=levels,
                        max_iterations=iterations, damping=0.7,
                        preference="median", **kw)
            rows.append({**base, "status": "ok",
                         "wall_s": time.time() - t0,
                         "n_clusters_l0": int(res.n_clusters[0])})
    return rows


def run_sweep_scaling(sizes=(65536, 262144, 1_000_000), k: int = 16,
                      levels: int = 2, iterations: int = 3,
                      sharded_workers: int = 8,
                      exchange: str = "auto") -> list:
    """1-vs-8-device sharded-sweep N sweep (the ``topk_sweep`` suite).

    Each configuration runs in a subprocess with its own forced device
    count; rows carry the resolved exchange and the analytic per-device
    state / per-sweep communication columns next to the measured wall
    time.
    """
    rows = []
    for n in sizes:
        for sweep, w in (("single", 1), ("sharded", sharded_workers)):
            rec = run_cpu_child(SWEEP_WORKER,
                                (n, k, levels, iterations, sweep, exchange),
                                w, timeout=3000)
            rec["suite"] = "topk_sweep"
            rows.append(rec)
    return rows


def run_coarsen_scaling(sizes=(200_000, 1_000_000), topk_cap=1_000_000,
                        k: int = 32, levels: int = 2,
                        iterations: int = 30,
                        partition_size: int = 256) -> list:
    """coarsen vs dense_topk end-to-end N sweep (the ``coarsen`` suite).

    Both backends solve the same blobs with the same sweep budget; rows
    record wall clock, L0 cluster count, L0 purity against the
    generating labels, the analytic message-state column, and the
    process peak RSS after the solve. ``ru_maxrss`` is monotone over
    the process lifetime, so the memory-wall evidence is each
    backend's FIRST row at a given N (coarsen runs before dense_topk
    at each size for exactly this reason).
    """
    from repro.core.metrics import purity
    from repro.data import gaussian_blobs
    from repro.solver import solve
    from repro.solver.config import SolveConfig

    batch = SolveConfig().coarsen_batch
    rows = []
    for n in sizes:
        x, y = gaussian_blobs(n=n, k=16, seed=0, spread=0.5)
        for backend in ("coarsen", "dense_topk"):
            base = {"suite": "coarsen", "backend": backend, "n": n,
                    "levels": levels, "iterations": iterations}
            if backend == "coarsen":
                base["partition_size"] = partition_size
                # local stage state; the global stage adds O(E * k)
                base["state_bytes"] = (3 * levels * partition_size
                                       * partition_size * batch * 4)
                kw = {"partition_size": partition_size}
            else:
                base["k"] = k
                base["state_bytes"] = 3 * levels * n * (k + 1) * 4
                if n > topk_cap:
                    rows.append({**base, "status": "skipped",
                                 "reason": "O(N)-column build + O(L*N*k) "
                                           "state past the flat-backend "
                                           "budget"})
                    continue
                kw = {"k": k}
            t0 = time.time()
            res = solve(x, backend=backend, levels=levels,
                        max_iterations=iterations, damping=0.7,
                        preference="median", **kw)
            rows.append({**base, "status": "ok",
                         "wall_s": time.time() - t0,
                         "n_clusters_l0": int(res.n_clusters[0]),
                         "purity_l0": float(purity(res.labels[0], y)),
                         "peak_rss_mb": peak_rss_mb()})
    return rows


def main(argv=None):
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=("smoke", "full"), default="full",
                    help="smoke: CI/nightly-sized rows; full: the paper-"
                         "scale sweep incl. the N=2e5 topk row")
    args = ap.parse_args(argv)
    if args.tier == "smoke":
        mr_rows = run(n=256, iterations=10, worker_counts=(1, 2))
        topk_rows = run_topk_scaling(sizes=(512, 2048, 4096), k=16,
                                     iterations=10, dense_cap=2048)
        sweep_rows = run_sweep_scaling(sizes=(4096, 16384), k=16,
                                       iterations=5, sharded_workers=2)
        coarsen_rows = run_coarsen_scaling(sizes=(20_000,), topk_cap=20_000,
                                           iterations=15)
    else:
        mr_rows = run()
        topk_rows = run_topk_scaling()
        sweep_rows = run_sweep_scaling()
        coarsen_rows = run_coarsen_scaling()
    for r in mr_rows:
        r["suite"] = "mrhap"
        print(f"mrhap_scaling_{r['mode']}_w{r['workers']},"
              f"{r['wall_s'] * 1e6 / r['iterations']:.0f},"
              f"work/W={r['work_per_worker']} "
              f"comm={r['comm_bytes_iter']}B k={r['k_level0']}")
    for r in topk_rows:
        if r["status"] == "ok":
            print(f"scaling_{r['backend']}_n{r['n']},"
                  f"{r['wall_s'] * 1e6 / r['iterations']:.0f},"
                  f"state={r['state_bytes']}B k_l0={r['n_clusters_l0']}")
        else:
            print(f"scaling_{r['backend']}_n{r['n']},skipped,"
                  f"state={r['state_bytes']}B ({r['reason']})")
    for r in sweep_rows:
        print(f"sweep_{r['sweep']}_n{r['n']}_w{r['workers']},"
              f"{r['us_per_sweep']:.0f},"
              f"state/dev={r['state_bytes_per_device']}B "
              f"comm={r['comm_bytes_sweep']}B exch={r['exchange']}")
    for r in coarsen_rows:
        if r["status"] == "ok":
            print(f"coarsen_{r['backend']}_n{r['n']},"
                  f"{r['wall_s'] * 1e6:.0f},"
                  f"purity_l0={r['purity_l0']:.3f} "
                  f"rss={r['peak_rss_mb']:.0f}MB")
        else:
            print(f"coarsen_{r['backend']}_n{r['n']},skipped,"
                  f"state={r['state_bytes']}B ({r['reason']})")
    rows = mr_rows + topk_rows + sweep_rows
    emit("scaling", rows, meta={"tier": args.tier})
    emit("coarsen", coarsen_rows, meta={"tier": args.tier})
    return rows


if __name__ == "__main__":
    main()
