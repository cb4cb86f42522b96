"""Fig 5.1 analogue: purity of MR-HAP vs HK-Means across datasets,
plus the sparse ``dense_topk`` (k=32) column tracking the quality cost
of top-k similarity truncation (contract: within 2 purity points of
dense on these suites)."""
from __future__ import annotations

import time

from repro.baselines import hierarchical_kmeans
from repro.core import link_hierarchy, purity
from repro.data import aggregation_like, gaussian_blobs, two_moons
from repro.solver import solve

try:
    from benchmarks._emit import emit
except ImportError:  # executed as a script: benchmarks/ is sys.path[0]
    from _emit import emit

DATASETS = {
    "aggregation": aggregation_like,
    "blobs": lambda: gaussian_blobs(n=600, k=6, seed=2, spread=0.5),
    "moons": lambda: two_moons(n=400, seed=3),
}


def run(levels: int = 3, iterations: int = 40, topk_k: int = 32) -> list:
    rows = []
    for name, fn in DATASETS.items():
        x, y = fn()
        t0 = time.time()
        res = solve(x, backend="dense_parallel", levels=levels,
                    max_iterations=iterations, damping=0.7,
                    preference="median")
        hap_t = time.time() - t0
        hier = link_hierarchy(res.exemplars)
        t0 = time.time()
        sres = solve(x, backend="dense_topk", k=topk_k, levels=levels,
                     max_iterations=iterations, damping=0.7,
                     preference="median")
        topk_t = time.time() - t0
        shier = link_hierarchy(sres.exemplars)
        t0 = time.time()
        hk = hierarchical_kmeans(x, levels=levels, branch=3)
        hk_t = time.time() - t0
        for l in range(levels):
            rows.append({
                "dataset": name, "level": l,
                "hap_purity": purity(hier.labels[l], y),
                "hap_k": int(hier.n_clusters[l]),
                "topk_purity": purity(shier.labels[l], y),
                "topk_k": int(shier.n_clusters[l]),
                "hk_purity": purity(hk.labels[l], y),
                "hk_k": int(hk.n_clusters[l]),
                "hap_s": hap_t, "topk_s": topk_t, "hk_s": hk_t,
            })
    return rows


def main():
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    rows = run()
    for r in rows:
        print(f"purity_{r['dataset']}_L{r['level']},"
              f"{r['hap_s'] * 1e6:.0f},"
              f"hap={r['hap_purity']:.3f}(k={r['hap_k']}) "
              f"topk={r['topk_purity']:.3f}(k={r['topk_k']}) "
              f"hk={r['hk_purity']:.3f}(k={r['hk_k']})")
    emit("purity", rows)
    return rows


if __name__ == "__main__":
    main()
