#!/usr/bin/env python3
"""Bring-up smoke: the clustering system's main paths on a TPU chip.

    python3 chip_smoke.py [--seed 0]     # one chip: phases A, B, C
    python3 chip_smoke.py --chips 4      # four chips: phase D only

Phases (data made from ``--seed``, everything through ``repro.solver.solve``
and ``repro.serve.cluster.ClusterService`` as a user calls them):

A  dense HAP from an (N, N) similarity matrix, ``backend="auto"`` (must be
   ``dense_fused``, the Pallas responsibility/availability kernels),
   N=9216, L=3: exemplars equal ``dense_parallel`` on the same stack; at
   N=512 both equal a float64 numpy oracle of Alg. 1 (at L=1, where Alg.
   1 and the paper's Jacobi schedule are the same recurrence).
B  sparse HAP from points, ``backend="auto"`` (must be ``dense_topk``
   with the fused Pallas build and the single-device sweep),
   N=2^18, d=128, k=32, L=3: 256 sampled rows of the edge list against a
   float64 numpy top-k; level-0 purity against the generating labels.
C  served traffic: pixel-like requests (crops of the segmentation
   images, colour + position, d=5) through a warmed one-worker
   ``ClusterService`` with buckets (512, 5, 8) and (2048, 5, 4): zero
   request-path compiles, zero errors, each response's exemplars equal
   to ``solve()`` with the backend the service reports (requests whose
   sweep counts differ are listed).
D  (``--chips 4``) ``dense_topk`` at N=2^18, d=128, k=32, L=3, 20
   sweeps, with the build and sweep (allgather exchange) sharded over a
   four-chip ``workers`` mesh, against the same points on one chip:
   exemplars, labels, sweep count, edge lists and the whole message
   state compared bit for bit. (Cut from N=2^20: one sparse sweep there
   takes 4.4 s on one chip, and the phase would hold four chips for
   about ten minutes.)

Each phase prints one JSON line: the backend, build and sweep that ran,
sizes, compile and wall seconds, the device's peak bytes so far, every
check, and the number of degradation events (any event fails: a path
that degraded did not run on the chip). The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every check passed. Without a TPU, or without the ``repro`` package
next to this file, the script exits non-zero and prints no result.

Times are bring-up numbers from one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

#: phase B level-0 purity floor: the same solve at N=4096 on the CPU
#: (JAX_PLATFORMS=cpu, seed 0) reaches this; the chip must stay within
#: PURITY_SLACK of it at full size
CPU_PURITY_N4096 = 1.0
PURITY_SLACK = 0.02

#: phase B edge values: |chip - float64| <= VALUE_RTOL * (|x_i|^2 + max
#: |x_j|^2), the size of the terms ||x||^2 + ||y||^2 - 2 x.y cancels —
#: about 80 f32 ulps of them, far inside one bf16 pass (2^-8)
VALUE_RTOL = 1e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# ----------------------------------------------------------------- helpers
class Compiles:
    """Backend compile seconds, counted from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """(result, seconds) of ``fn()`` with its device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def cache_probe() -> bool:
    """Compile one program twice with JAX's in-memory caches cleared in
    between: True when the second compile came from the persistent
    cache."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.xla_cache import HIT_EVENT
    hits = []

    def on(event, **_):
        if event == HIT_EVENT:
            hits.append(event)

    jax.monitoring.register_event_listener(on)
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    probe = lambda a: jnp.tanh(a @ a.T).sum(axis=0)  # noqa: E731
    jax.jit(probe).lower(x).compile()
    jax.clear_caches()
    jax.jit(probe).lower(x).compile()
    return bool(hits)


def hap_sequential_f64(s3, iterations: int, damping: float):
    """Alg. 1 as printed (bottom-up Gauss-Seidel over levels) in float64
    numpy: the plain reference for phase A, independent of the code
    under test. Returns the (L, N) raw exemplar decode argmax(a + r)."""
    import numpy as np
    s3 = np.asarray(s3, np.float64)
    levels, n, _ = s3.shape
    rows = np.arange(n)
    eye = np.eye(n, dtype=bool)
    r = np.zeros_like(s3)
    a = np.zeros_like(s3)
    tau = np.full((levels, n), np.inf)
    phi = np.zeros((levels, n))
    c = np.zeros((levels, n))
    lam = damping
    for _ in range(iterations):
        for l in range(levels):
            v = a[l] + s3[l]
            i1 = np.argmax(v, axis=1)
            m1 = v[rows, i1]
            v[rows, i1] = -np.inf
            m2 = v.max(axis=1)
            excl = np.where(rows[None, :] == i1[:, None], m2[:, None],
                            m1[:, None])
            rho = s3[l] + np.minimum(tau[l][:, None], -excl)
            r[l] = lam * r[l] + (1 - lam) * rho
            rp = np.where(eye, 0.0, np.maximum(r[l], 0.0))
            col = rp.sum(axis=0)
            rdiag = np.diagonal(r[l])
            base = c[l] + phi[l]
            a_off = np.minimum(0.0, base + rdiag + col - rp)
            alpha = np.where(eye, (base + col)[None, :], a_off)
            a[l] = lam * a[l] + (1 - lam) * alpha
            c[l] = (a[l] + r[l]).max(axis=1)
            if l + 1 < levels:
                tau[l + 1] = c[l] + rdiag + col
            if l > 0:
                phi[l - 1] = (a[l] + s3[l]).max(axis=1)
    return np.argmax(a + r, axis=2)


def similarity_f64(x):
    """float64 negative squared distances, median preference on the
    diagonal (the repo's default points->matrix convention)."""
    import numpy as np
    x = np.asarray(x, np.float64)
    sq = (x * x).sum(axis=1)
    s = -np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)
    off = s[~np.eye(len(x), dtype=bool)]
    np.fill_diagonal(s, np.median(off))
    return s


# ------------------------------------------------------------------ phases
def phase_a(seed: int, pin, n: int = 9216, n_oracle: int = 512) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pairwise_similarity, set_preferences
    from repro.core.assignments import canonicalize_levels
    from repro.core.preferences import median_preference
    from repro.data import gaussian_blobs
    from repro.solver import SolveConfig, solve

    levels = 3
    cfg = SolveConfig(levels=levels, max_iterations=50, damping=0.7,
                      stop="fixed", mesh=pin)
    x, _ = gaussian_blobs(n=n, k=16, dim=2, seed=seed, spread=0.5)
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    solve(s, cfg)                                  # compile + first run
    res, wall = timed(lambda: solve(s, cfg))
    ref = solve(s, cfg.replace(backend="dense_parallel"))
    checks = {"ran_dense_fused": res.backend == "dense_fused",
              "exemplars_equal_dense_parallel":
              bool(np.array_equal(res.exemplars, ref.exemplars))}

    xo, _ = gaussian_blobs(n=n_oracle, k=8, dim=2, seed=seed, spread=0.5)
    so = similarity_f64(xo)
    so32 = so.astype(np.float32)
    ocfg = SolveConfig(levels=1, max_iterations=200, damping=0.7,
                       stop="fixed", mesh=pin)
    oracle = canonicalize_levels(hap_sequential_f64(
        so32[None].astype(np.float64), ocfg.max_iterations, ocfg.damping))
    small = solve(so32, ocfg)
    small_ref = solve(so32, ocfg.replace(backend="dense_parallel"))
    checks["oracle_n512_auto"] = bool(np.array_equal(small.exemplars,
                                                     oracle))
    checks["oracle_n512_dense_parallel"] = bool(np.array_equal(
        small_ref.exemplars, oracle))
    return {"phase": "A", "backend": res.backend, "n": n, "levels": levels,
            "sweeps": res.n_sweeps, "clusters": res.n_clusters.tolist(),
            "wall_s": wall, "checks": checks}


def phase_b(seed: int, pin, n: int = 1 << 18, d: int = 128, k: int = 32,
            paths: dict | None = None) -> dict:
    import numpy as np

    from repro.core.metrics import purity
    from repro.data import gaussian_blobs
    from repro.solver import SolveConfig, solve
    from repro.solver.topk_build import resolve_build_backend
    from repro.solver.topk_sharded import resolve_sweep

    levels, sample = 3, 256
    cfg = SolveConfig(backend="auto", levels=levels, k=k,
                      max_iterations=100, damping=0.7, stop="converged",
                      mesh=pin, seed=seed, **(paths or {}))
    x, y = gaussian_blobs(n=n, k=64, dim=d, seed=seed, spread=1.0)
    # one call (its wall includes its compiles): the sweeps take minutes
    res, wall = timed(lambda: solve(x, cfg.replace(keep_state=True)))
    build = resolve_build_backend(cfg.build, n=n, k=k, metric=cfg.metric,
                                  n_devices=cfg.device_count())
    sweep = resolve_sweep(cfg.sweep, n=n, n_devices=cfg.device_count())

    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice(n, size=sample, replace=False))
    vals = np.asarray(res.state.hap.s[0])[rows, 1:].astype(np.float64)
    idx = np.asarray(res.state.idx)[rows, 1:]
    x64 = x.astype(np.float64)
    sq = (x64 * x64).sum(axis=1)
    ref = -np.maximum(sq[rows, None] + sq[None, :]
                      - 2.0 * x64[rows] @ x64.T, 0.0)
    ref[np.arange(sample), rows] = -np.inf
    tol = VALUE_RTOL * (sq[rows] + sq.max())
    values_ok = sets_ok = True
    for i in range(sample):
        top = np.argsort(-ref[i], kind="stable")[:k]
        kth = ref[i, top[-1]]
        values_ok &= bool(np.all(np.abs(vals[i] - ref[i, idx[i]]) <= tol[i]))
        diff = np.setxor1d(idx[i], top)
        sets_ok &= bool(np.all(np.abs(ref[i, diff] - kth) <= tol[i]))
    p0 = purity(res.labels[0], y)
    checks = {"ran_dense_topk_fused_single":
              (res.backend, build, sweep) == ("dense_topk", "fused",
                                              "single"),
              "edge_values_within_tolerance": values_ok,
              "edge_sets_equal_outside_ties": sets_ok,
              "purity_l0_ok": bool(p0 >= CPU_PURITY_N4096 - PURITY_SLACK)}
    return {"phase": "B", "backend": res.backend, "build": build,
            "sweep": sweep, "n": n, "d": d, "k": k, "levels": levels,
            "sweeps": res.n_sweeps, "clusters": res.n_clusters.tolist(),
            "purity_l0": p0, "first_call_s": wall, "checks": checks}


def pixel_requests(seed: int, sizes) -> list:
    """Pixel-like requests, d=5 (colour, position): rectangular crops of
    the procedural stand-ins for the paper's segmentation images (16 or
    32 rows, as many columns as the size needs), each pixel's RGB and
    its (row, column) in [0, 1] as in the paper's image experiments."""
    import numpy as np

    from repro.data.images import buttons_image, mandrill_like_image
    rng = np.random.default_rng(seed)
    out = []
    for i, n in enumerate(sizes):
        img = buttons_image() if i % 2 else mandrill_like_image(seed=i)
        h = 16 if n <= 512 else 32
        w = n // h
        y0 = int(rng.integers(0, img.shape[0] - h + 1))
        x0 = int(rng.integers(0, img.shape[1] - w + 1))
        rgb = img[y0:y0 + h, x0:x0 + w].astype(np.float32) / 255.0
        yy, xx = np.mgrid[y0:y0 + h, x0:x0 + w].astype(np.float32)
        pos = np.stack([yy / img.shape[0], xx / img.shape[1]], axis=-1)
        out.append(np.concatenate([rgb, pos], axis=-1).reshape(n, 5))
    return out


def phase_c(seed: int, pin, compiles: Compiles,
            buckets=((512, 5, 8), (2048, 5, 4)), requests: int = 32
            ) -> dict:
    import numpy as np

    from repro.serve.cluster import ClusterService
    from repro.solver import SolveConfig, solve

    # the service's defaults (damping 0.7, median preference). The served
    # program is batched and bucket-padded, solve() is neither; with the
    # column sums and norms summed in a fixed order (core.hap.column_sum,
    # core.similarity._sq_norms) a request that fills its bucket runs
    # the same trajectory in both. A padded one can still part on the
    # way (on a v5e one of 32 ran 96 sweeps against 77 and settled on
    # the same exemplars): its sweep counts are reported, not checked
    cfg = SolveConfig(levels=2, stop="converged", max_iterations=200,
                      mesh=pin)
    svc = ClusterService(config=cfg.replace(mesh=None), buckets=buckets,
                         auto_bucket=False, workers=1)
    warm = svc.warmup()
    # two request sizes per bucket, one padded and one exact: every
    # distinct size costs the unbatched reference solve() its compiles
    rng = np.random.default_rng(seed)
    small, large = buckets[0][0], buckets[1][0]
    sizes = [int(rng.choice([3 * small // 4, small])) if i % 4
             else int(rng.choice([3 * large // 4, large]))
             for i in range(requests)]
    pts = pixel_requests(seed, sizes)
    before = compiles.count
    t0 = time.perf_counter()
    futs = [svc.submit(p) for p in pts]
    svc.drain()
    wall = time.perf_counter() - t0
    request_path_compiles = compiles.count - before
    errors, backends, mismatched, sweeps_apart = 0, set(), [], []
    for p, f in zip(pts, futs):
        if f.exception() is not None:
            errors += 1
            continue
        got = f.result().solve
        backends.add(got.backend)
        ref = solve(p, cfg.replace(backend=got.backend))
        record = {"n": len(p),
                  "sweeps": [int(got.n_sweeps), int(ref.n_sweeps)],
                  "converged": [bool(got.converged), bool(ref.converged)]}
        if not np.array_equal(got.exemplars, ref.exemplars):
            mismatched.append(record)
        elif got.n_sweeps != ref.n_sweeps:
            sweeps_apart.append(record)
    snap = svc.snapshot()
    checks = {"zero_request_path_compiles":
              snap["cache"]["misses"] == warm["misses"]
              and request_path_compiles == 0,
              "zero_errors": errors == 0,
              "exemplars_equal_solve": not mismatched}
    return {"phase": "C", "backend": sorted(backends), "d": 5,
            "buckets": [list(b) for b in buckets], "requests": requests,
            "sizes": sorted(set(sizes)),
            "warmup_compiles": warm["misses"],
            "warmup_compile_s": warm["compile_seconds"],
            "micro_batches": snap["micro_batches"], "wall_s": wall,
            "mismatched": mismatched[:4], "sweeps_apart": sweeps_apart,
            "checks": checks}


def phase_d(seed: int, chips: int, n: int = 1 << 18, d: int = 128,
            k: int = 32, sweeps: int = 20) -> dict:
    import numpy as np

    from repro.data import gaussian_blobs
    from repro.launch.mesh import make_worker_mesh
    from repro.solver import SolveConfig, solve

    levels = 3
    fields = ("s", "r", "a", "tau", "phi", "c")
    x, _ = gaussian_blobs(n=n, k=64, dim=d, seed=seed, spread=1.0)
    # the allgather exchange scatters the gathered edge list in the
    # one-chip order and every other op is row-local, so the two sides
    # run the same arithmetic: the whole message state must match bit
    # for bit, not only the exemplars (L=3 HAP on this data does not
    # settle in these sweeps, so one ulp anywhere would also move them)
    base = SolveConfig(backend="dense_topk", levels=levels, k=k,
                       max_iterations=sweeps, damping=0.7, stop="fixed",
                       seed=seed, exchange="allgather", keep_state=True)

    def run(cfg):
        """One solve (its wall includes its compiles: each path runs
        once); only host copies of what is compared leave."""
        res, wall = timed(lambda: solve(x, cfg))
        state = {f: np.asarray(getattr(res.state.hap, f))[:, :n]
                 for f in fields}
        state["idx"] = np.asarray(res.state.idx)[:n]
        return res.exemplars, res.labels, res.n_sweeps, state, wall

    e4, l4, sw4, st4, wall4 = run(base.replace(
        mesh=make_worker_mesh(chips), build="sharded", sweep="sharded"))
    e1, l1, sw1, st1, wall1 = run(base.replace(mesh=make_worker_mesh(1)))
    differing = [f for f in st1 if not np.array_equal(st4[f], st1[f])]
    checks = {"exemplars_equal_one_chip": bool(np.array_equal(e4, e1)),
              "labels_equal_one_chip": bool(np.array_equal(l4, l1)),
              "same_sweeps": sw4 == sw1,
              "edges_equal_one_chip": not {"idx", "s"} & set(differing),
              "state_equal_one_chip": not differing}
    return {"phase": "D", "backend": "dense_topk", "build": "sharded",
            "sweep": "sharded", "exchange": base.exchange, "chips": chips,
            "n": n, "d": d, "k": k, "levels": levels,
            "sweeps": [sw4, sw1],
            "clusters": [int(len(np.unique(e))) for e in e4],
            "exemplars_differing": (e4 != e1).sum(axis=1).tolist(),
            "state_fields_differing": differing,
            "first_call_s_sharded": wall4, "first_call_s_one_chip": wall1,
            "checks": checks}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded dense_topk phase (D) "
                         "across four chips against one chip")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}; run from a checkout")
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX's first device is {devices[0].platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    from repro.launch.mesh import make_worker_mesh
    from repro.runtime import degrade
    from repro.runtime.xla_cache import use_persistent_cache

    cache_dir = use_persistent_cache()
    compiles = Compiles()
    print(json.dumps({"phase": "setup", "jax": jax.__version__,
                      "devices": len(devices),
                      "kind": devices[0].device_kind,
                      "compile_cache": cache_dir,
                      "second_compile_hit_cache": cache_probe()}),
          flush=True)

    # the one-chip phases run on a one-device mesh, and on a multi-chip
    # host name the build and sweep too: the auto rules cannot then move
    # them to the sharded paths
    pin = make_worker_mesh(1)
    paths = ({"build": "fused", "sweep": "single"} if len(devices) > 1
             else None)
    phases = ([lambda: phase_d(args.seed, args.chips)] if args.chips > 1
              else [lambda: phase_a(args.seed, pin),
                    lambda: phase_b(args.seed, pin, paths=paths),
                    lambda: phase_c(args.seed, pin, compiles)])
    ok = True
    for run in phases:
        events0 = len(degrade.events())
        c0, n0 = compiles.seconds, compiles.count
        try:
            rec = run()
        except Exception:  # noqa: BLE001 — report the phase, fail the run
            traceback.print_exc()
            ok = False
            continue
        rec["compile_s"] = compiles.seconds - c0
        rec["compiles"] = compiles.count - n0
        rec["peak_bytes_in_use"] = peak_bytes(devices[0])
        rec["degrade_events"] = len(degrade.events()) - events0
        rec["ok"] = all(rec["checks"].values()) and not rec["degrade_events"]
        ok &= rec["ok"]
        print(json.dumps(rec), flush=True)
    if not ok:
        fail("a phase failed (see the lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
