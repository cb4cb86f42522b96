#!/usr/bin/env python3
"""Where do two programs that should agree bit for bit part on a chip?

    python3 tools/chip_parity_probe.py [--small] [--damping 0.7]

Prints one JSON line per comparison, each a bitwise verdict plus the
largest absolute difference:

sparse  ``dense_topk`` sweeps at N=2^16, d=128, k=32, L=3: the one-device
        loop (``run_topk``) run twice, and against the row-sharded
        program (``run_topk_sharded``, allgather exchange) on a
        one-device mesh, after 1, 2, 4, 8 and 20 sweeps, field by field;
        the column-sum scatter alone against numpy's in-order
        ``add.at`` in float32.
dense   served requests (``chip_smoke.pixel_requests``: image crops,
        d=5) against ``solve()``: the similarity the served ``prepare``
        stage builds against the engine's, its cross term and squared
        norms batched against alone, and the dense sweeps batched
        (``vmap``, bucket-padded) against one request alone, padded and
        unpadded (from the served similarity and from the engine's);
        the dense column sum batched against alone.

``--damping`` sets both parts' damping; ``--only`` runs one part;
``--small`` shrinks every size for a CPU dry run. Diagnostic only: it
checks nothing and always exits 0 after its last line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def emit(**rec):
    print(json.dumps(rec), flush=True)


def compare(a, b) -> dict:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return {"equal": False, "shapes": [list(a.shape), list(b.shape)]}
    eq = bool(np.array_equal(a, b))
    out = {"equal": eq}
    if not eq and a.dtype.kind == "f":
        fin = np.isfinite(a) & np.isfinite(b)
        out["max_abs"] = float(np.max(np.abs(a[fin] - b[fin]))) if fin.any() \
            else None
        out["n_diff"] = int(np.sum(a != b))
    elif not eq:
        out["n_diff"] = int(np.sum(a != b))
    return out


def state_fields(state, n=None) -> dict:
    """HAPState fields as host arrays, rows cut to ``n``."""
    import numpy as np
    cut3 = (lambda x: x[:, :n]) if n is not None else (lambda x: x)
    return {f: np.asarray(cut3(getattr(state, f)))
            for f in ("r", "a", "tau", "phi", "c")}


# ------------------------------------------------------------------ sparse
def sparse(n: int, d: int, k: int, levels: int, sweeps_list,
           damping: float) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import gaussian_blobs
    from repro.launch.mesh import make_worker_mesh
    from repro.solver.config import SolveConfig
    from repro.solver.topk import build_from_points, run_topk
    from repro.solver.topk_sharded import run_topk_sharded

    x, _ = gaussian_blobs(n=n, k=64, dim=d, seed=0, spread=1.0)
    s3k, idx = build_from_points(jnp.asarray(x), k, levels,
                                 config=SolveConfig(build="auto"))
    s3k, idx = jax.block_until_ready((s3k, idx))
    mesh = make_worker_mesh(1)
    for sw in sweeps_list:
        kw = dict(max_iterations=sw, damping=damping, stop="fixed")
        st1, e1, *_ = run_topk(s3k, idx, **kw)
        f1 = state_fields(st1.hap)
        if sw == sweeps_list[-1]:
            st1b, e1b, *_ = run_topk(s3k, idx, **kw)
            f1b = state_fields(st1b.hap)
            emit(probe="sparse_single_repeat", sweeps=sw,
                 exemplars=compare(e1, e1b),
                 **{f: compare(f1[f], f1b[f]) for f in f1})
        st2, e2, *_ = run_topk_sharded(s3k, idx, mesh, exchange="allgather",
                                       **kw)
        f2 = state_fields(st2.hap, n)
        emit(probe="sparse_single_vs_sharded1", sweeps=sw,
             exemplars=compare(e1, np.asarray(e2)[:, :n]),
             **{f: compare(f1[f], f2[f]) for f in f1})

    # the column-sum scatter alone: TPU result against numpy's in-order
    # float32 accumulation, and against itself in a second program
    from repro.kernels.topk_ops import col_partial_topk
    rng = np.random.default_rng(1)
    r = rng.standard_normal(idx.shape).astype(np.float32)
    col = jax.jit(col_partial_topk, static_argnums=2)(r, idx, n)
    col2 = jax.jit(lambda r, i: col_partial_topk(r * 1.0 + 0.0, i, n))(
        jnp.asarray(r), idx)
    rp = np.maximum(r, 0.0)
    rp[:, 0] = 0.0
    seq = np.zeros(n, np.float32)
    np.add.at(seq, np.asarray(idx).ravel(), rp.ravel())
    emit(probe="scatter_vs_numpy_in_order", n=n, **compare(col, seq))
    emit(probe="scatter_two_programs", n=n, **compare(col, col2))


# ------------------------------------------------------------------- dense
def dense(buckets, sizes_per_bucket, cfg_kw) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chip_smoke import pixel_requests
    from repro.solver import SolveConfig
    from repro.solver import dense as dense_mod
    from repro.solver.compiled import BatchedDenseSolver
    from repro.solver.engine import _build_similarity

    cfg = SolveConfig(backend="dense_parallel", **cfg_kw)
    run_kw = dict(order="parallel", max_iterations=cfg.max_iterations,
                  damping=cfg.damping, stop=cfg.stop,
                  patience=cfg.patience)
    batched = jax.jit(jax.vmap(lambda s3: dense_mod.run_dense(s3, **run_kw)))
    one = jax.jit(lambda s3: dense_mod.run_dense(s3, **run_kw))
    for (bn, bd, bb), sizes in zip(buckets, sizes_per_bucket):
        reqs = pixel_requests(bn, sizes)
        h = BatchedDenseSolver(bb, bn, bd, cfg).compile()
        pts = np.zeros((bb, bn, bd), np.float32)
        n_real = np.full((bb,), 2, np.int32)
        for i, p in enumerate(reqs):
            pts[i, :len(p)] = p
            n_real[i] = len(p)
        s_srv, _ = h._prepare_exec(jnp.asarray(pts), jnp.asarray(n_real))
        s_srv = np.asarray(s_srv)
        st_b, e_b, sw_b, *_ = batched(jnp.asarray(s_srv))
        for i, p in enumerate(reqs):
            n = len(p)
            s_eng = np.asarray(_build_similarity(p, cfg, "dense_parallel"))
            st_u, e_u, sw_u, *_ = one(jnp.asarray(s_eng))
            st_p, e_p, sw_p, *_ = one(jnp.asarray(s_srv[i]))
            # the served similarity cut to the request: padding alone
            st_c, e_c, *_ = one(jnp.asarray(s_srv[i][:, :n, :n]))
            fu = state_fields(st_u)
            fc = state_fields(st_c)
            cut = lambda st: {f: (np.asarray(getattr(st, f))[:, :n, :n]
                                  if f in ("r", "a") else
                                  np.asarray(getattr(st, f))[:, :n])
                              for f in fu}
            fb = cut(jax.tree.map(lambda v: v[i], st_b))
            fp = cut(st_p)
            emit(probe="dense_request", bucket=bn, n=n,
                 similarity_served_vs_engine=compare(
                     s_srv[i][:, :n, :n], s_eng),
                 sweeps=[int(sw_u), int(sw_p), int(np.asarray(sw_b)[i])],
                 exemplars_padded_vs_alone=compare(
                     np.asarray(e_p)[:, :n], e_u),
                 exemplars_batched_vs_alone=compare(
                     np.asarray(e_b)[i][:, :n], e_u),
                 state_padded_vs_alone={f: compare(fp[f], fu[f])
                                        for f in fu},
                 exemplars_padded_vs_cut=compare(
                     np.asarray(e_p)[:, :n], e_c),
                 state_padded_vs_cut={f: compare(fp[f], fc[f]) for f in fc},
                 state_batched_vs_padded={f: compare(fb[f], fp[f])
                                          for f in fu})

        # the similarity's two parts, batched and padded against alone
        from repro.core.similarity import MATMUL_PRECISION
        dot = lambda a: jnp.matmul(a, a.T, precision=MATMUL_PRECISION)
        from repro.core.similarity import _sq_norms as nrm
        p, n = reqs[0], len(reqs[0])
        emit(probe="similarity_parts", bucket=bn, n=n,
             dot_batched_vs_alone=compare(
                 jax.jit(jax.vmap(dot))(pts)[0][:n, :n], jax.jit(dot)(p)),
             norm_batched_vs_alone=compare(
                 jax.jit(jax.vmap(nrm))(pts)[0][:n], jax.jit(nrm)(p)))

    # the dense column sum alone: vmapped over a batch against one matrix
    rng = np.random.default_rng(2)
    for bn, _, bb in buckets:
        xs = np.maximum(rng.standard_normal((bb, bn, bn)), 0).astype(
            np.float32)
        cb = jax.jit(jax.vmap(lambda m: jnp.sum(m, axis=0)))(xs)
        c1 = jax.jit(lambda m: jnp.sum(m, axis=0))(xs[0])
        emit(probe="colsum_batched_vs_alone", n=bn,
             **compare(np.asarray(cb)[0], c1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true",
                    help="tiny sizes, for a CPU dry run")
    ap.add_argument("--only", choices=("sparse", "dense"))
    ap.add_argument("--damping", type=float, default=0.7)
    args = ap.parse_args(argv)
    import jax
    emit(probe="setup", platform=jax.devices()[0].platform,
         kind=jax.devices()[0].device_kind, damping=args.damping)
    if args.small:
        sp = dict(n=1024, d=16, k=8, levels=3, sweeps_list=(1, 2))
        buckets = ((64, 5, 4), (128, 5, 2))
        sizes = ([48, 64], [96, 128])
    else:
        sp = dict(n=1 << 16, d=128, k=32, levels=3, sweeps_list=(1, 2, 4, 8, 20))
        buckets = ((512, 5, 8), (2048, 5, 4))
        sizes = ([384, 512, 512, 384], [1536, 2048])
    cfg_kw = dict(levels=2, stop="converged", max_iterations=200,
                  damping=args.damping)
    if args.only != "dense":
        sparse(**sp, damping=args.damping)
    if args.only != "sparse":
        dense(buckets, sizes, cfg_kw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
