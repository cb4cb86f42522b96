"""System driver: image-tile requests served by ``ClusterService``.

Set-up builds a one-worker service from the configuration, compiles
every bucket and batch-ladder variant (``warmup``), then runs one launch
of each variant on a fixed tile that does not depend on the seed, so
that set-up does the same work on every seed. The window starts the
service's scheduler and offers the traffic mix's requests open loop (see
``bench/generators/open_loop.py``); each request's latency runs from its
due time to its result, and requests that finish after the window
closes are waited for, up to the mix's ``late_wait_s``.

Afterwards ``check.sample`` requests drawn from the seed are solved
again by the plain reference (``bench/reference/<reference>.py``), each
alone on its own points, and compared:

* ``exemplar_diff``: the mean over sampled requests of the share of
  (level, point) exemplars, after each point follows its exemplar's
  exemplar, that differ from the reference's. A request that never
  came back counts 1.
"""
from __future__ import annotations

import time

import numpy as np

from lib import stats
from lib.spans import span, window

CHECK_STREAM = 3


def build_service(config: dict):
    from repro.serve.cluster import ClusterService
    from repro.solver import SolveConfig
    svc_cfg = config["service"]
    cfg = SolveConfig(**svc_cfg["solve"])
    return ClusterService(config=cfg,
                          buckets=[tuple(b) for b in svc_cfg["buckets"]],
                          auto_bucket=False, workers=svc_cfg["workers"])


def ladder(batch: int) -> list:
    """Power-of-two launch sizes up to ``batch``: the service's ladder."""
    out, v = [], 1
    while v < batch:
        out.append(v)
        v <<= 1
    return out + [int(batch)]


def warm_launches(svc, config: dict) -> int:
    """One launch of every ladder variant of every bucket, on the
    largest catalog tile that fits the bucket (seed-independent).
    Returns the launches run."""
    from generators.open_loop import catalog
    cat = catalog(config["requests"])
    launches = 0
    for n, _d, batch in config["service"]["buckets"]:
        pts = max((c["points"] for c in cat if c["points"].shape[0] <= n),
                  key=len)
        for b in ladder(batch):
            futs = [svc.submit(pts) for _ in range(b)]
            svc.drain()
            for f in futs:
                f.result()
            launches += 1
    return launches


def setup(ctx):
    svc = build_service(ctx.config)
    warm = svc.warmup()
    launches = warm_launches(svc, ctx.config)
    return svc, {"warmup_compiles": warm["misses"],
                 "warmup_launches": launches}


def serve_window(ctx, svc, requests: list, trace_dir=None) -> tuple:
    """Offer ``requests`` open loop; -> (records, window seconds)."""
    gen = ctx.generator
    svc.start()
    try:
        with window(trace_dir):
            t0 = time.perf_counter()
            records = gen.drive(requests, svc.submit, t0, span)
            close = t0 + (requests[-1]["due"] if requests else 0.0)
            with span("bench.drain"):
                gen.wait_all(records,
                             max(close, time.perf_counter())
                             + float(ctx.traffic["late_wait_s"]))
            t1 = max([r.get("done", t0) for r in records] + [t0])
        t_end = time.perf_counter()
    finally:
        svc.stop()
    for r in records:
        r.setdefault("gave_up", t_end)
    return records, t1 - t0


def responses(records: list) -> list:
    """Per request: latency from due time, generator lateness and the
    service's own counters. One that never came counts the time until
    the wait for it ended, and is not ``ok``."""
    out = []
    for r in records:
        f = r["future"]
        row = {"n": r["n"],
               "late_ms": (r["sent"] - r["due"]) * 1e3,
               "latency_ms": (r["gave_up"] - r["due"]) * 1e3, "ok": False}
        if f.done() and f.exception() is None and "done" in r:
            resp = f.result()
            row.update(latency_ms=(r["done"] - r["due"]) * 1e3, ok=True,
                       queue_ms=resp.queue_ms, solve_ms=resp.solve_ms,
                       bucket=tuple(resp.bucket), worker=resp.worker,
                       sweeps=int(resp.solve.n_sweeps),
                       exemplars=resp.solve.exemplars)
        out.append(row)
    return out


def launches(rows: list) -> list:
    """Group served requests by launch: riders of one launch share its
    bucket, worker and wall (``solve_ms``, one float per launch)."""
    groups: dict = {}
    for i, r in enumerate(rows):
        if r["ok"]:
            groups.setdefault((r["bucket"], r["worker"], r["solve_ms"]),
                              []).append(i)
    out = []
    for (bucket, _w, solve_ms), idx in groups.items():
        sweeps = [rows[i]["sweeps"] for i in idx]
        out.append({"bucket": bucket, "riders": len(idx),
                    "batch": next(v for v in ladder(bucket[2])
                                  if v >= len(idx)),
                    "sweeps": max(sweeps), "solve_ms": solve_ms})
    return out


def sample(rows: list, count: int, seed: int) -> list:
    """Indices of ``count`` requests to compare, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % (1 << 63), CHECK_STREAM])
    return sorted(rng.choice(len(rows), size=min(count, len(rows)),
                             replace=False).tolist())


def reference_exemplars(ref, config: dict, points, *,
                        precision: str = "highest",
                        dtype: str = "float32") -> np.ndarray:
    sv = config["service"]["solve"]
    e, _ = ref.solve_one(np.asarray(points, np.float32),
                         levels=sv["levels"],
                         max_iterations=sv["max_iterations"],
                         patience=sv.get("patience", 5),
                         damping=float(sv.get("damping", 0.7)),
                         precision=precision, dtype=dtype)
    return ref.canonical(np.asarray(e))


def numbers(diffs: list) -> dict:
    return {"exemplar_diff": float(np.mean(diffs)) if diffs else 1.0}


def compare(ctx, requests: list, rows: list, picked: list, **prec) -> dict:
    diffs = []
    for i in picked:
        if not rows[i]["ok"]:
            diffs.append(1.0)
            continue
        ref_e = reference_exemplars(ctx.reference, ctx.config,
                                    requests[i]["points"], **prec)
        diffs.append(float(np.mean(ref_e != rows[i]["exemplars"])))
    return numbers(diffs)


def plan(ctx, seed: int, seconds: float, traffic: dict = None) -> list:
    return ctx.generator.plan(traffic or ctx.traffic, seed, seconds,
                              ctx.config["requests"])


def summary(rows: list, launch: list, max_iterations: int) -> dict:
    """The window's readings by request and by launch."""
    lat = stats.latencies(rows)
    return {
        "requests": len(rows),
        "beyond_p95": stats.beyond(lat, 95.0),
        "p50_ms": stats.median(lat),
        "p95_ms": stats.percentile(lat, 95.0),
        "sweeps_per_launch": stats.sweeps_per_launch(launch)
        if launch else None,
        "gen_late_p95_ms": stats.gen_late_p95(rows),
        "launches": len(launch),
        "launches_at_max_iterations": sum(
            lch["sweeps"] >= max_iterations for lch in launch)}


def run(ctx):
    import jax

    from run import Run

    svc, notes = setup(ctx)
    requests = plan(ctx, ctx.seed, ctx.seconds)
    compiles0 = ctx.compiles.count
    snap0 = svc.snapshot()
    setup_s = time.perf_counter() - ctx.t_process
    records, window_s = serve_window(ctx, svc, requests, ctx.trace_dir)
    window_compiles = ctx.compiles.count - compiles0
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    snap = svc.snapshot()
    del svc
    rows = responses(records)
    del records
    launch = launches(rows)
    max_it = ctx.config["service"]["solve"]["max_iterations"]
    picked = sample(rows, ctx.config["check"]["sample"], ctx.seed)
    nums = compare(ctx, requests, rows, picked)
    checks = [(name, nums[name], float(limit))
              for name, limit in ctx.config["limits"].items()]
    notes.update(summary(rows, launch, max_it))
    notes.update({
        "window_s": window_s,
        "window_compiles": window_compiles,
        "cache_misses_in_window": snap["cache"]["misses"]
        - snap0["cache"]["misses"],
        "micro_batches": snap["micro_batches"] - snap0["micro_batches"],
        "compared": len(picked)})
    return Run(
        e2e={"setup_s": setup_s, "p50_ms": notes["p50_ms"],
             "p95_ms": notes["p95_ms"]},
        attempted=len(rows), failed=sum(not r["ok"] for r in rows),
        checks=checks,
        data={"rows": rows, "launches": launch, "window_s": window_s,
              "levels": ctx.config["service"]["solve"]["levels"]},
        memory_peak_bytes=peak, notes=notes)
