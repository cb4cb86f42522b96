#!/usr/bin/env python3
"""Find the served cell's knee: offer its traffic mix at a ladder of
rates, on several seeds each, through one warmed service and report, per
window, the latency percentiles, the service's launch-busy share and
whether the backlog grew across the window.

    python3 bench/tools/knee.py --workload seg_rgb.serve --rates 4,6,8 --seeds 1,2 --seconds 30

``--tiles`` and ``--buckets`` replace the configuration's tiling and
buckets, to try another request size. A window holds when the service
kept up: the last request finished within ``--lag`` seconds (default 4)
of the last due time, and the requests due in the window's last quarter
waited no longer, at the median, than those of its first quarter times
``--growth`` (default 1.5). One JSON line per window on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def ints(text: str) -> list:
    return [int(v) for v in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="seg_rgb.serve")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=int, default=30)
    ap.add_argument("--tiles", help="gy,gx")
    ap.add_argument("--buckets", help="n,d,batch[;n,d,batch...]")
    ap.add_argument("--growth", type=float, default=1.5)
    ap.add_argument("--lag", type=float, default=4.0)
    args = ap.parse_args(argv)
    seeds = ints(args.seeds)
    try:
        _, system, ctx, _ = run.make_context(run.ROOT, args.workload,
                                             seeds[0], args.seconds, False)
    except run.NoDevice as exc:
        print(f"knee: {exc}", file=sys.stderr)
        return 3
    from lib import stats
    if args.tiles:
        ctx.config["requests"] = dict(ctx.config["requests"],
                                      tiles=ints(args.tiles))
    if args.buckets:
        ctx.config["service"] = dict(ctx.config["service"], buckets=[
            ints(b) for b in args.buckets.split(";")])
    svc, notes = system.setup(ctx)
    print(json.dumps({"setup": notes, "requests": ctx.config["requests"],
                      "buckets": ctx.config["service"]["buckets"]}),
          flush=True)
    max_it = ctx.config["service"]["solve"]["max_iterations"]
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            traffic = dict(ctx.traffic, rate_rps=rate)
            reqs = system.plan(ctx, seed, args.seconds, traffic)
            records, window_s = system.serve_window(ctx, svc, reqs)
            rows = system.responses(records)
            lat = stats.latencies(rows)
            q = max(len(rows) // 4, 1)
            first, last = stats.median(lat[:q]), stats.median(lat[-q:])
            launches = system.launches(rows)
            busy = sum(lch["solve_ms"] for lch in launches) / 1e3 / window_s
            lag = window_s - reqs[-1]["due"]
            print(json.dumps({
                "rate_rps": rate, "seed": seed,
                "served_rps": len(rows) / window_s, "lag_s": lag,
                "failed": sum(not r["ok"] for r in rows),
                **system.summary(rows, launches, max_it),
                "p90_ms": stats.percentile(lat, 90.0),
                "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
                "holds": last <= args.growth * first and lag <= args.lag,
                "launch_busy_share": busy, "window_s": window_s,
                "serve_solve_ms": stats.served_median(rows, "solve_ms"),
                "serve_queue_ms": stats.served_median(rows, "queue_ms")}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
