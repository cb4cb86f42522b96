"""System driver: a batch ``repro.solver.solve()`` of points, repeated.

Set-up makes the points on the device from the seed in one jitted call,
then runs one whole solve (``solve()`` compiles as it runs; the
persistent cache holds every program after a checkout's first run). The
window runs whole solves of the same points back to back. Afterwards the
last window solve is compared with the plain reference
(``bench/reference/<reference>.py``):

* ``edge_gap``: the widest gap between a stored similarity and float64,
  over ||x_i||^2 + max ||x_j||^2, on rows sampled from the seed;
* ``edge_miss``: stored edges on those rows that are not among the row's
  k nearest in float64 (beyond a tie band of ``edge_gap``'s limit), plus
  duplicates;
* ``exemplar_diff``: the share of (level, point) exemplars, after each
  point follows its exemplar's exemplar, that differ from the reference's
  solve of the same points (its own build, preference and sweeps).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from lib.spans import span, window

CHECK_STREAM = 3


def seed31(seed: int) -> int:
    """The solver's ``SolveConfig.seed`` (a 31-bit PRNG seed)."""
    return int(seed) % (1 << 31)


@functools.lru_cache(maxsize=None)
def _blobs_fn(n: int, d: int, centers: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def blobs(key, box, spread):
        kc, kw, kl, kn = jax.random.split(key, 4)
        ctr = jax.random.uniform(kc, (centers, d), jnp.float32, 0.0, box)
        w = jax.random.dirichlet(kw, jnp.full((centers,), 3.0))
        lab = jax.random.categorical(kl, jnp.log(w), shape=(n,))
        x = ctr[lab] + spread * jax.random.normal(kn, (n, d), jnp.float32)
        return x, lab

    return blobs


def make_points(data: dict, seed: int):
    """Gaussian blobs on the device: ``centers`` centres uniform in
    [0, box]^d, uneven cluster sizes (Dirichlet(3) weights), isotropic
    spread. One jitted call; the same seed gives the same points."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed31(seed)),
                             int(seed) >> 31)
    x, lab = _blobs_fn(int(data["n_points"]), int(data["dim"]),
                       int(data["centers"]))(key, float(data["box"]),
                                             float(data["spread"]))
    return jax.block_until_ready(x), lab


def solve_config(config: dict, seed: int):
    from repro.launch.mesh import make_worker_mesh
    from repro.solver import SolveConfig
    return SolveConfig(**config["solve"], seed=seed31(seed),
                       keep_state=True, mesh=make_worker_mesh(1))


def sample_rows(n: int, count: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed) % (1 << 63), CHECK_STREAM])
    return np.sort(rng.choice(n, size=min(count, n), replace=False))


def reference_solve(ref, x, config: dict, seed: int, *,
                    precision: str = "highest", dtype: str = "float32"):
    """-> (canonical exemplars (L, N), vals (N, k), idx (N, k)) of the
    plain reference at the given precision and message dtype."""
    sv, chk = config["solve"], config["check"]
    vals, idx = ref.build(x, k=sv["k"], block=chk["ref_block"],
                          precision=precision)
    pref = ref.preference(x, vals, seed31(seed), sample=chk["pref_sample"],
                          precision=precision)
    e = ref.sweeps(vals, idx, pref, levels=sv["levels"],
                   iterations=sv["max_iterations"],
                   damping=float(sv["damping"]), dtype=dtype)
    return ref.canonical(np.asarray(e)), np.asarray(vals), np.asarray(idx)


def numbers(ref, config: dict, x_host, rows, got_vals, got_idx, got_e,
            ref_e) -> dict:
    """The compared numbers for one solve (see module doc)."""
    ref64, scale = ref.edges_f64(x_host, rows)
    gap, miss = ref.edge_numbers(ref64, scale, got_vals, got_idx,
                                 band=config["limits"]["edge_gap"])
    return {"edge_gap": gap, "edge_miss": float(miss),
            "exemplar_diff": float(np.mean(np.asarray(got_e)
                                           != np.asarray(ref_e)))}


def program_edges(res, rows):
    """The solve's stored (values, columns) on ``rows``, self slot
    dropped."""
    vals = np.asarray(res.state.hap.s[0][rows, 1:])
    idx = np.asarray(res.state.idx[rows, 1:])
    return vals, idx


def run(ctx):
    import jax

    from repro.solver import solve
    from run import Run

    x, _ = make_points(ctx.config["data"], ctx.seed)
    cfg = solve_config(ctx.config, ctx.seed)

    def call():
        res = solve(x, cfg)
        jax.block_until_ready(res.state)
        return res

    warm = call()
    warm_e = warm.exemplars
    del warm
    compiles0 = ctx.compiles.count
    setup_s = time.perf_counter() - ctx.t_process
    with window(ctx.trace_dir):
        calls = ctx.generator.drive(call, ctx.seconds, span)
    window_s = calls[-1][1] - calls[0][0]
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    window_compiles = ctx.compiles.count - compiles0

    last = calls[-1][2]
    rows = sample_rows(x.shape[0], ctx.config["check"]["sample_rows"],
                       ctx.seed)
    got_vals, got_idx = program_edges(last, rows)
    got_e = last.exemplars
    same = sum(bool(np.array_equal(c[2].exemplars, warm_e)) for c in calls)
    walls = [c[1] - c[0] for c in calls]
    del calls, last

    ref_e, _, _ = reference_solve(ctx.reference, x, ctx.config, ctx.seed)
    nums = numbers(ctx.reference, ctx.config, np.asarray(x), rows,
                   got_vals, got_idx, got_e, ref_e)
    sv = ctx.config["solve"]
    checks = [(name, nums[name], float(limit))
              for name, limit in ctx.config["limits"].items()]
    return Run(
        e2e={"setup_s": setup_s, "solve_s": window_s / len(walls)},
        attempted=len(walls), failed=0, checks=checks,
        data={"solves": len(walls), "sweeps": int(sv["max_iterations"]),
              "n": int(x.shape[0]), "kk": int(sv["k"]) + 1,
              "levels": int(sv["levels"]), "walls": walls},
        memory_peak_bytes=peak,
        notes={"solves": len(walls), "window_s": window_s,
               "solve_wall_s": walls,
               "window_compiles": window_compiles,
               "solves_equal_warmup": same})
