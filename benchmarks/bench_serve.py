"""Clustering-service load bench: offered-load sweep -> latency/throughput.

For each offered load (requests/second, Poisson arrivals) push a mixed
shape population through a warmed ``ClusterService`` and record p50/p99
end-to-end latency, achieved throughput, and the incremental fast-path
share. The knee where p99 departs from p50 is the service's capacity at
the configured bucket/batch settings.

    PYTHONPATH=src python benchmarks/bench_serve.py [--smoke] [--json P]
    PYTHONPATH=src python benchmarks/bench_serve.py --multiworker
    PYTHONPATH=src python benchmarks/bench_serve.py --chaos

``--chaos`` runs the worker-failure recovery bench instead: the same
Poisson load through a multi-worker service while a seeded
``FaultInjector`` kills one worker's launches mid-traffic, then a clean
follow-up load. The emitted ``serve_chaos`` row records baseline /
under-chaos / recovered p99 plus the recovery counters (worker_deaths,
retried_batches, requeued_requests, resurrections) — the trajectory
plot shows recovery cost, not just steady-state latency.

``--multiworker`` runs the scale-out comparison instead: the same load
ladder through (a) the legacy single-worker configuration — one worker,
fixed-shape full-batch launches, fixed 2 ms gather window — and (b) the
scaled configuration — multi-worker dispatch, batch-ladder right-sized
launches, deadline-driven batch closing, multi-source offered load. It
reports each configuration's *sustained* throughput (the best achieved
rate whose p99 stays inside the same latency budget), their ratio, and
an overload burst at 2x the bounded queues' hold capacity showing
explicit sheds with bounded p99 instead of unbounded latency growth.

Emits ``BENCH_serve.json`` (the nightly workflow uploads it; rows are
named ``serve_load_<rps>`` plus a ``serve_warmup`` compile row, or
``serve_{sw,mw}_load_<rps>`` + ``serve_scaleout_summary`` +
``serve_mw_overload`` under ``--multiworker``). ``--chaos`` writes its
``serve_chaos`` row to ``BENCH_serve_chaos.json`` instead, so the
nightly can run the load sweep and the chaos bench back to back without
one record clobbering the other.
"""
from __future__ import annotations

import argparse
import math

try:
    from benchmarks._emit import emit
except ImportError:  # executed as a script: benchmarks/ is sys.path[0]
    from _emit import emit

from repro.serve.cluster import (
    ClusterService, DeadlineExceededError, ServiceOverloadedError,
)
from repro.serve.cluster.loadgen import run_load, synthetic_requests
from repro.solver.config import SolveConfig

FULL = {"buckets": [(128, 2), (256, 2), (512, 2)], "batch": 8,
        "loads": [5.0, 20.0, 50.0, 100.0], "requests": 120,
        "max_iterations": 100}
SMOKE = {"buckets": [(64, 2), (128, 2)], "batch": 4,
         "loads": [5.0, 15.0], "requests": 30, "max_iterations": 60}

#: scale-out comparison tiers: same buckets + load ladder for both
#: configurations; ``requests`` scales with load (fixed offering window)
MW_FULL = {"buckets": [(64, 2), (128, 2)], "batch": 8,
           "loads": [1.0, 2.0, 4.0, 8.0, 16.0, 24.0], "window_s": 12.0,
           "min_requests": 16, "max_iterations": 100,
           "workers": 2, "sources": 4, "max_wait_ms": 40.0,
           "overload_queue": 8, "slo_floor_ms": 600.0}
MW_SMOKE = {"buckets": [(64, 2)], "batch": 4,
            "loads": [2.0, 8.0], "window_s": 4.0,
            "min_requests": 8, "max_iterations": 60,
            "workers": 2, "sources": 2, "max_wait_ms": 20.0,
            "overload_queue": 4, "slo_floor_ms": 600.0}

#: chaos-recovery tiers: one load level, offered three times (baseline,
#: under injected worker kills, recovered)
CHAOS_FULL = {"buckets": [(64, 2)], "batch": 4, "rps": 40.0,
              "requests": 80, "max_iterations": 60, "workers": 4,
              "kills": 3, "cooldown_s": 0.2, "deadline_ms": 2000.0}
CHAOS_SMOKE = {"buckets": [(64, 2)], "batch": 4, "rps": 30.0,
               "requests": 30, "max_iterations": 60, "workers": 2,
               "kills": 1, "cooldown_s": 0.1, "deadline_ms": 2000.0}


def run_sweep(argv_tier, args) -> int:
    """The classic single-configuration offered-load sweep."""
    tier = argv_tier
    cfg = SolveConfig(stop="converged",
                      max_iterations=tier["max_iterations"],
                      damping=0.6, levels=2, preference="median",
                      seed=args.seed)
    svc = ClusterService(
        config=cfg,
        buckets=[(n, d, tier["batch"]) for n, d in tier["buckets"]])
    delta = svc.warmup()
    print(f"[serve] warmup: {delta['misses']} compiles "
          f"{delta['compile_seconds']:.2f}s "
          f"({len(svc.router.buckets)} buckets x batch {tier['batch']})")
    rows = [{"name": "serve_warmup", "compiles": delta["misses"],
             "compile_seconds": delta["compile_seconds"]}]

    print(f"{'rps_offered':>12} {'rps_achieved':>13} {'p50_ms':>8} "
          f"{'p99_ms':>8} {'fast%':>6} {'err':>4}")
    for load in tier["loads"]:
        reqs = synthetic_requests(tier["requests"], tier["buckets"],
                                  seed=args.seed + int(load))
        res = run_load(svc, reqs, rps=load, stream="bench",
                       stream_frac=args.stream_frac, seed=args.seed)
        print(f"{res.offered_rps:>12.1f} {res.achieved_rps:>13.1f} "
              f"{res.p50_ms:>8.2f} {res.p99_ms:>8.2f} "
              f"{100 * res.fast_frac:>5.1f}% {res.n_errors:>4}")
        rows.append(res.row(f"serve_load_{load:g}"))

    snap = svc.snapshot()
    post_warm = snap["cache"]["misses"] - delta["misses"]
    print(f"[serve] cache hits={snap['cache']['hits']} "
          f"misses={snap['cache']['misses']} "
          f"(request-path compiles: {post_warm})")
    emit("serve", rows,
         meta={"smoke": args.smoke, "stream_frac": args.stream_frac,
               "request_path_compiles": post_warm, **snap["cache"]},
         out_dir=".")
    return 0


def _n_requests(tier, load: float) -> int:
    return max(tier["min_requests"], int(load * tier["window_s"]))


def _sweep_config(tier, args, *, name: str, rows: list,
                  **service_kw) -> tuple:
    """Load-ladder one service configuration; returns (svc_snapshot,
    warm_delta, results)."""
    cfg = SolveConfig(stop="converged",
                      max_iterations=tier["max_iterations"],
                      damping=0.6, levels=2, preference="median",
                      seed=args.seed)
    svc = ClusterService(
        config=cfg,
        buckets=[(n, d, tier["batch"]) for n, d in tier["buckets"]],
        auto_bucket=False, **service_kw)
    delta = svc.warmup()
    workers = len(svc.workers)
    print(f"[serve:{name}] warmup: {delta['misses']} compiles "
          f"{delta['compile_seconds']:.2f}s ({workers} workers)")
    results = []
    for load in tier["loads"]:
        reqs = synthetic_requests(_n_requests(tier, load),
                                  tier["buckets"],
                                  seed=args.seed + int(load))
        res = run_load(svc, reqs, rps=load, seed=args.seed,
                       sources=tier["sources"] if name == "mw" else 1)
        print(f"[serve:{name}] {res.offered_rps:>6.1f} rps offered -> "
              f"{res.achieved_rps:>6.1f} achieved | "
              f"p50 {res.p50_ms:>7.1f}  p99 {res.p99_ms:>7.1f} ms | "
              f"{res.n_errors} err")
        rows.append(res.row(f"serve_{name}_load_{load:g}"))
        results.append(res)
    snap = svc.snapshot()
    return snap, delta, results


def _sustained(results, slo_ms: float) -> float:
    """Best achieved throughput whose p99 stayed inside the budget."""
    ok = [r.achieved_rps for r in results
          if r.n_errors == 0 and not math.isnan(r.p99_ms)
          and r.p99_ms <= slo_ms]
    return max(ok) if ok else 0.0


def run_multiworker(args) -> int:
    """Scale-out comparison: legacy single-worker vs multi-worker SLO
    dispatch, equal-p99 sustained throughput, plus a 2x-overload run."""
    tier = MW_SMOKE if args.smoke else MW_FULL
    rows: list = []

    sw_snap, sw_delta, sw_res = _sweep_config(
        tier, args, name="sw", rows=rows,
        workers=1, batch_ladder=False, max_wait_ms=2.0)
    mw_snap, mw_delta, mw_res = _sweep_config(
        tier, args, name="mw", rows=rows,
        workers=tier["workers"], batch_ladder=True,
        max_wait_ms=tier["max_wait_ms"])

    # equal-p99 budget: generous enough that the legacy config sustains
    # *something* (its floor is one full-batch solve), tight enough to be
    # a real latency SLO
    sw_floor = min((r.p99_ms for r in sw_res
                    if not math.isnan(r.p99_ms)), default=0.0)
    slo_ms = max(tier["slo_floor_ms"], 1.2 * sw_floor)
    sus_sw = _sustained(sw_res, slo_ms)
    sus_mw = _sustained(mw_res, slo_ms)
    ratio = sus_mw / sus_sw if sus_sw > 0 else float("inf")
    print(f"[serve:scaleout] p99 budget {slo_ms:.0f} ms: "
          f"single-worker sustains {sus_sw:.1f} rps, "
          f"multi-worker sustains {sus_mw:.1f} rps "
          f"({ratio:.1f}x)")
    rows.append({"name": "serve_scaleout_summary", "slo_ms": slo_ms,
                 "sustained_sw_rps": sus_sw, "sustained_mw_rps": sus_mw,
                 "ratio": ratio})

    # overload: burst 2x the system's total hold capacity (bounded
    # queues plus one in-flight batch per worker) at the door faster
    # than the workers can drain -> admission control sheds the excess
    # explicitly; whatever is admitted keeps a bounded p99. A paced
    # Poisson offering can't force this reliably — the scaled config's
    # raw capacity sits well above its SLO-limited sustained rate.
    cfg = SolveConfig(stop="converged",
                      max_iterations=tier["max_iterations"],
                      damping=0.6, levels=2, preference="median",
                      seed=args.seed)
    svc = ClusterService(
        config=cfg,
        buckets=[(n, d, tier["batch"]) for n, d in tier["buckets"]],
        auto_bucket=False, workers=tier["workers"], batch_ladder=True,
        max_wait_ms=tier["max_wait_ms"],
        max_queue=tier["overload_queue"])
    svc.warmup()
    svc.start()
    capacity = tier["workers"] * (tier["overload_queue"] + tier["batch"])
    burst = synthetic_requests(2 * capacity, tier["buckets"],
                               seed=args.seed + 999)
    futs = [svc.submit(pts, deadline_ms=slo_ms) for pts in burst]
    lat, shed, missed = [], 0, 0
    for fut in futs:
        exc = fut.exception(timeout=120)
        if exc is None:
            resp = fut.result()
            lat.append(resp.queue_ms + resp.solve_ms)
        elif isinstance(exc, ServiceOverloadedError):
            shed += 1
        elif isinstance(exc, DeadlineExceededError):
            missed += 1
    svc.stop()
    lat.sort()
    over_p99 = (lat[min(len(lat) - 1, int(0.99 * len(lat)))]
                if lat else float("nan"))
    over_snap = svc.snapshot()
    print(f"[serve:overload] burst {len(burst)} "
          f"(2x hold capacity, max_queue={tier['overload_queue']}): "
          f"{len(lat)} served p99 {over_p99:.1f} ms | "
          f"{shed} shed, {missed} deadline-missed")
    rows.append({"name": "serve_mw_overload", "burst": len(burst),
                 "hold_capacity": capacity, "served": len(lat),
                 "shed": shed, "deadline_missed": missed,
                 "p99_ms": over_p99,
                 "max_queue": tier["overload_queue"],
                 "deadline_ms": slo_ms,
                 "sheds": over_snap["sheds"],
                 "deadline_rejects": over_snap["deadline_rejects"],
                 "deadline_drops": over_snap["deadline_drops"]})

    def per_worker_compiles(snap, delta):
        # warm misses split evenly across workers; report actual
        return [{"worker": w["worker"], "misses": w["cache"]["misses"],
                 "post_warmup_compiles":
                     w["cache"]["misses"]
                     - delta["misses"] // max(len(snap["workers"]), 1)}
                for w in snap["workers"]]

    post_warm_mw = mw_snap["cache"]["misses"] - mw_delta["misses"]
    post_warm_sw = sw_snap["cache"]["misses"] - sw_delta["misses"]
    print(f"[serve:scaleout] post-warmup compiles: "
          f"single-worker {post_warm_sw}, multi-worker {post_warm_mw} "
          f"(per worker: "
          f"{[w['post_warmup_compiles'] for w in per_worker_compiles(mw_snap, mw_delta)]})")
    emit("serve", rows,
         meta={"smoke": args.smoke, "multiworker": True,
               "workers": tier["workers"], "sources": tier["sources"],
               "slo_ms": slo_ms, "scaleout_ratio": ratio,
               "post_warmup_compiles_sw": post_warm_sw,
               "post_warmup_compiles_mw": post_warm_mw,
               "per_worker_mw": per_worker_compiles(mw_snap, mw_delta),
               "overload_sheds": over_snap["sheds"],
               "overload_deadline_drops": over_snap["deadline_drops"]},
         out_dir=".")
    return 0


def run_chaos(args) -> int:
    """Worker-failure recovery bench: baseline load, load under seeded
    worker kills (every future must still resolve successfully), clean
    recovered load — one ``serve_chaos`` row with all three p99s and the
    recovery counters."""
    from repro.runtime import faultinject
    from repro.runtime.faultinject import FaultInjector, Rule

    tier = CHAOS_SMOKE if args.smoke else CHAOS_FULL
    cfg = SolveConfig(stop="converged",
                      max_iterations=tier["max_iterations"],
                      damping=0.6, levels=2, preference="median",
                      seed=args.seed)
    svc = ClusterService(
        config=cfg,
        buckets=[(n, d, tier["batch"]) for n, d in tier["buckets"]],
        auto_bucket=False, workers=tier["workers"],
        max_wait_ms=1.0, max_retries=3,
        worker_cooldown_s=tier["cooldown_s"], retry_backoff_ms=2.0)
    delta = svc.warmup()
    print(f"[serve:chaos] warmup: {delta['misses']} compiles "
          f"{delta['compile_seconds']:.2f}s ({tier['workers']} workers)")

    def load(seed):
        return run_load(
            svc, synthetic_requests(tier["requests"], tier["buckets"],
                                    seed=seed),
            rps=tier["rps"], seed=seed, deadline_ms=tier["deadline_ms"])

    baseline = load(args.seed + 1)
    inj = FaultInjector(seed=7).add(
        Rule("serve.launch", nth=0, times=tier["kills"],
             match={"worker": 1}))
    with faultinject.active(inj):
        chaos = load(args.seed + 2)
    recovered = load(args.seed + 3)
    s = svc.stats
    print(f"[serve:chaos] p99 baseline {baseline.p99_ms:.1f} ms -> "
          f"under-chaos {chaos.p99_ms:.1f} ms -> "
          f"recovered {recovered.p99_ms:.1f} ms | "
          f"errors {baseline.n_errors}/{chaos.n_errors}/"
          f"{recovered.n_errors} | deaths={s.worker_deaths} "
          f"retried={s.retried_batches} requeued={s.requeued_requests} "
          f"resurrections={s.resurrections}")
    if chaos.n_errors or recovered.n_errors:
        print("[serve:chaos] FAIL: futures failed — recovery is supposed "
              "to absorb worker kills")
        return 1
    rows = [{"name": "serve_chaos",
             "baseline_p99_ms": baseline.p99_ms,
             "chaos_p99_ms": chaos.p99_ms,
             "recovered_p99_ms": recovered.p99_ms,
             "n_requests": 3 * tier["requests"],
             "n_errors": (baseline.n_errors + chaos.n_errors
                          + recovered.n_errors),
             "injected_faults": len(inj.events),
             "worker_deaths": s.worker_deaths,
             "retried_batches": s.retried_batches,
             "requeued_requests": s.requeued_requests,
             "resurrections": s.resurrections,
             "workers": tier["workers"], "rps": tier["rps"]}]
    # own record name: the nightly runs the load sweep and the chaos
    # bench back to back, and this emit must not clobber BENCH_serve.json
    emit("serve_chaos", rows,
         meta={"smoke": args.smoke, "chaos": True,
               "workers": tier["workers"], "seed": 7}, out_dir=".")
    return 0


def main(argv=None) -> int:
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes/loads for CI")
    ap.add_argument("--multiworker", action="store_true",
                    help="scale-out comparison: single-worker legacy vs "
                         "multi-worker SLO dispatch + 2x-overload run")
    ap.add_argument("--chaos", action="store_true",
                    help="worker-failure recovery bench: load under "
                         "seeded worker kills + recovered p99")
    ap.add_argument("--stream-frac", type=float, default=0.5,
                    help="fraction of requests riding one stream's "
                         "incremental fast path (classic sweep only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, help="override output path")
    args = ap.parse_args(argv)

    if args.chaos:
        ret = run_chaos(args)
    elif args.multiworker:
        ret = run_multiworker(args)
    else:
        ret = run_sweep(SMOKE if args.smoke else FULL, args)
    if args.json:
        import shutil
        src = "BENCH_serve_chaos.json" if args.chaos else "BENCH_serve.json"
        shutil.move(src, args.json)
        print(f"[serve] moved record to {args.json}")
    return ret


if __name__ == "__main__":
    raise SystemExit(main())
