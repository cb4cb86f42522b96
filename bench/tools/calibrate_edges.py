#!/usr/bin/env python3
"""Readings that the limits of the edge-list cell's ``correct`` are set
from, by ``calibrate.py``'s method: for each seed, the numbers the
benchmark compares for the program, and the same numbers for the control
(the plain reference with its message state in bfloat16 instead of
float32, put in the program's place; the edge list has no matmul).

    python3 bench/tools/calibrate_edges.py --workload graph500_s18.solve20 --seeds 11,12,13

One process: one solve of the program and two of the reference per
seed. One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

CONTROL = {"dtype": "bfloat16"}


def edge_seed(system, ctx, seed: int) -> dict:
    import jax

    from repro.graph.edges import EdgeList
    from repro.solver import solve
    ref, config = ctx.reference, ctx.config
    graph = system.make_graph(config["data"], seed)
    src, dst, weight, n = graph
    res = solve(EdgeList(src, dst, weight, n_nodes=n),
                system.solve_config(config, seed))
    jax.block_until_ready(res.state)
    rows = system.sample_rows(np.bincount(src, minlength=n),
                              config["check"]["sample_rows"], seed)
    miss = system.edge_miss(graph, system.program_rows(res.state, n, rows))
    got_e = res.exemplars
    del res
    ref_e = system.reference_solve(ref, graph, config)
    ctl_e = system.reference_solve(ref, graph, config, **CONTROL)
    return {"n": n, "edges": len(src),
            "program": {"edge_miss": float(miss),
                        "exemplar_diff": system.exemplar_diff(got_e, ref_e)},
            "control": {"edge_miss": 0.0,
                        "exemplar_diff": system.exemplar_diff(ctl_e,
                                                              ref_e)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="run on whatever device JAX has (a CPU rehearsal "
                         "at a small scale; not a chip reading)")
    ap.add_argument("--scale", type=int,
                    help="Kronecker scale in place of the cell's")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        _, system, ctx, _ = run.make_context(run.ROOT, args.workload,
                                             seeds[0], 1, False,
                                             require_tpu=not args.cpu)
    except run.NoDevice as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 3
    if args.scale:
        ctx.config["data"] = dict(ctx.config["data"], scale=args.scale)
    for seed in seeds:
        out = edge_seed(system, ctx, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
