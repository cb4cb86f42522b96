#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed,
the numbers the benchmark compares for the program, and the same numbers
for the control (the plain reference at the next lower precision, put in
the program's place: matmuls at ``Precision.HIGH`` instead of
``HIGHEST``, message state in bfloat16 instead of float32).

    python3 bench/tools/calibrate.py --workload sift128.solve20 --seeds 11,12,13
    python3 bench/tools/calibrate.py --workload seg_rgb.serve --seeds 11,12 --seconds 10 --rate 5

One process, one set-up: the batch cell runs one solve per seed, the
served cell a short window per seed at the cell's own load. One JSON
line per seed on standard output.
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

CONTROL = {"precision": "high", "dtype": "bfloat16"}


def batch_seed(system, ctx, seed: int) -> dict:
    import jax

    from repro.solver import solve
    ref = ctx.reference
    x, _ = system.make_points(ctx.config["data"], seed)
    res = solve(x, system.solve_config(ctx.config, seed))
    jax.block_until_ready(res.state)
    rows = system.sample_rows(x.shape[0], ctx.config["check"]["sample_rows"],
                              seed)
    got_vals, got_idx = system.program_edges(res, rows)
    got_e = res.exemplars
    del res
    x_host = np.asarray(x)
    ref_e, _, _ = system.reference_solve(ref, x, ctx.config, seed)
    program = system.numbers(ref, ctx.config, x_host, rows, got_vals,
                             got_idx, got_e, ref_e)
    ctl_e, ctl_vals, ctl_idx = system.reference_solve(ref, x, ctx.config,
                                                      seed, **CONTROL)
    control = system.numbers(ref, ctx.config, x_host, rows, ctl_vals[rows],
                             ctl_idx[rows], ctl_e, ref_e)
    return {"program": program, "control": control}


def serve_seed(system, ctx, svc, seed: int, seconds: int) -> dict:
    reqs = system.plan(ctx, seed, seconds)
    sv = ctx.config["service"]["solve"]
    records, _ = system.serve_window(ctx, svc, reqs)
    rows = system.responses(records)
    picked = system.sample(rows, ctx.config["check"]["sample"], seed)
    program = system.compare(ctx, reqs, rows, picked)
    diffs, per_request = [], []
    for i in picked:
        ref_e = system.reference_exemplars(ctx.reference, ctx.config,
                                           reqs[i]["points"])
        ctl_e = system.reference_exemplars(ctx.reference, ctx.config,
                                           reqs[i]["points"], **CONTROL)
        diffs.append(float(np.mean(ctl_e != ref_e)))
        per_request.append({
            "n": rows[i]["n"], "sweeps": rows[i].get("sweeps"),
            "at_max_iterations": rows[i].get("sweeps") ==
            sv["max_iterations"],
            "program": float(np.mean(ref_e != rows[i]["exemplars"]))
            if rows[i]["ok"] else None, "control": diffs[-1]})
    return {"program": program, "control": system.numbers(diffs),
            "requests": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "per_request": per_request}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--rate", type=float,
                    help="offered rate of the served cell (default: its mix's)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        _, system, ctx, _ = run.make_context(run.ROOT, args.workload,
                                             seeds[0], args.seconds, False)
    except run.NoDevice as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 3
    if args.rate:
        ctx.traffic = dict(ctx.traffic, rate_rps=args.rate)
    svc = None
    if hasattr(system, "serve_window"):
        svc, _ = system.setup(ctx)
    for seed in seeds:
        out = (serve_seed(system, ctx, svc, seed, args.seconds)
               if svc is not None else batch_seed(system, ctx, seed))
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
