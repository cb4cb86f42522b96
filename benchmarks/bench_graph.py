"""Graph backend benchmark: Borůvka contraction rounds and wall clock
vs N at fixed average degree, single device vs 8 forced host workers.

Two suites, both emitted to ``BENCH_graph.json``:

* ``scaling`` — N swept at fixed average degree (the O(N * deg) per-round
  regime the backend targets), single device; records rounds to
  convergence (the ~log2 N claim on record), wall, and us/round;
* ``workers`` — one size run at 1 and 8 forced host devices
  (subprocesses on virtual CPU devices, same pattern as bench_scaling)
  so the shard_map exchange overhead vs the row-block win is on record.
  The 8 "workers" share one host's CPU — the row gauges dispatch and
  collective overhead, not real scaling.

    PYTHONPATH=src python benchmarks/bench_graph.py [--smoke]

``--smoke`` shrinks sizes so CI finishes in seconds.
"""
from __future__ import annotations

import argparse
import os

try:
    from benchmarks._emit import emit, run_cpu_child
except ImportError:  # executed as a script: benchmarks/ is sys.path[0]
    from _emit import emit, run_cpu_child

WORKER = os.path.join(os.path.dirname(__file__), "_graph_worker.py")


def _run_worker(n: int, deg: int, sweep: str, workers: int) -> dict:
    return run_cpu_child(WORKER, (n, deg, sweep), workers, timeout=900)


def run(sizes, deg: int, worker_n: int) -> list:
    rows = []
    for n in sizes:
        rec = _run_worker(n, deg, "single", 1)
        print(f"graph_n{n}_deg{deg},rounds={rec['rounds']},"
              f"wall={rec['wall_s']:.3f}s,clusters={rec['clusters']}")
        rows.append(rec)
    for w in (1, 8):
        rec = _run_worker(worker_n, deg, "sharded" if w > 1 else "single", w)
        print(f"graph_workers{w}_n{worker_n},rounds={rec['rounds']},"
              f"wall={rec['wall_s']:.3f}s")
        rows.append(rec)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: CI compile-regression check")
    args = ap.parse_args(argv)
    if args.smoke:
        rows = run(sizes=(2048,), deg=8, worker_n=2048)
    else:
        rows = run(sizes=(10_000, 100_000, 1_000_000), deg=8,
                   worker_n=100_000)
    emit("graph", rows, meta={"smoke": args.smoke})
    return rows


if __name__ == "__main__":
    main()
