"""MR-HAP clustering driver — the paper's application, end to end:

  python -m repro.launch.cluster --dataset aggregation --levels 3 \
      --iterations 30 --damping 0.5 --comm-mode stats

Builds the similarity tensor (paper §2: negative squared Euclidean,
preferences on the diagonal), runs distributed MR-HAP over all local
devices, reports per-level cluster counts + purity, and optionally
checkpoints/restores the closed message state (fault tolerance per
runtime/fault.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_tree, save_tree
from repro.core import (
    link_hierarchy, make_preferences, pad_similarity, pairwise_similarity,
    purity, run_mrhap, set_preferences, stack_levels,
)
from repro.data import aggregation_like, gaussian_blobs, two_moons
from repro.data.images import buttons_image, image_to_points, mandrill_like_image
from repro.core.mrhap import run_mrhap_2d
from repro.launch.mesh import make_worker_mesh

DATASETS = {
    "aggregation": lambda seed: aggregation_like(seed),
    "blobs": lambda seed: gaussian_blobs(seed=seed),
    "moons": lambda seed: two_moons(seed=seed),
    "mandrill": lambda seed: (
        image_to_points(mandrill_like_image(seed=seed), subsample=12), None),
    "buttons": lambda seed: (
        image_to_points(buttons_image(seed=seed), subsample=12), None),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=sorted(DATASETS), default="aggregation")
    ap.add_argument("--levels", type=int, default=3)
    ap.add_argument("--iterations", type=int, default=30)
    ap.add_argument("--damping", type=float, default=0.5)
    ap.add_argument("--comm-mode", choices=["stats", "transpose"],
                    default="stats")
    ap.add_argument("--parallel-mode", choices=["1d", "2d"], default="1d",
                    help="2d: tile decomposition over a rows x cols mesh "
                         "(lifts the paper's M <= L*N worker ceiling)")
    ap.add_argument("--preference", choices=["median", "random", "range_mid"],
                    default="random")
    ap.add_argument("--pref-low", type=float, default=-1e6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)

    x, labels = DATASETS[args.dataset](args.seed)
    n = len(x)
    print(f"[cluster] {args.dataset}: {n} points, L={args.levels}")

    s = pairwise_similarity(jnp.asarray(x))
    pref = make_preferences(
        s, args.preference, key=jax.random.PRNGKey(args.seed),
        low=args.pref_low)
    s = set_preferences(s, pref)
    s3 = stack_levels(s, args.levels)

    if args.parallel_mode == "2d":
        ndev = len(jax.devices())
        rows = max(int(ndev ** 0.5), 1)
        cols = max(ndev // rows, 1)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((rows, cols), ("rows", "cols"),
                         devices=jax.devices()[: rows * cols])
        workers = rows * cols
        s3p, n_real = pad_similarity(s3, rows * cols)
        t0 = time.time()
        res = run_mrhap_2d(s3p, mesh, iterations=args.iterations,
                           damping=args.damping)
    else:
        mesh = make_worker_mesh()
        workers = mesh.shape["workers"]
        s3p, n_real = pad_similarity(s3, workers)
        t0 = time.time()
        res = run_mrhap(s3p, mesh, iterations=args.iterations,
                        damping=args.damping, comm_mode=args.comm_mode)
    exemplars = np.asarray(res.exemplars)[:, :n_real]
    dt = time.time() - t0
    hier = link_hierarchy(jnp.asarray(exemplars))
    for l in range(args.levels):
        line = f"[cluster] L{l}: k={hier.n_clusters[l]}"
        if labels is not None:
            line += f" purity={purity(hier.labels[l], labels):.3f}"
        print(line)
    print(f"[cluster] workers={workers} mode={args.comm_mode}/"
          f"{args.parallel_mode} time={dt:.2f}s")
    if args.ckpt:
        save_tree(args.ckpt, {"r": res.r, "a": res.a,
                              "exemplars": res.exemplars})
        print(f"[cluster] state checkpointed to {args.ckpt}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
