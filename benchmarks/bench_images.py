"""Fig 4.1 / 4.2 analogue: hierarchical image segmentation.

Paper params: mandrill 103x103 (=10,609 px) and buttons 120x100 (=12,000
px), RGB vectors, negative Euclidean similarity, random preferences in
[-1e6, 0], 30 iterations, lambda = 0.5, L = 3. Full-resolution N makes an
N^2 f32 similarity ~450 MB x 6 tensors — beyond this container's RAM, so
the bench runs the same pipeline at a documented subsample (the full run is
a single flag on a real host).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    link_hierarchy, pairwise_similarity, run_hap, set_preferences,
    stack_levels,
)
from repro.core.assignments import recolor_by_exemplar
from repro.core.preferences import random_preference
from repro.data.images import (
    buttons_image, image_to_points, mandrill_like_image,
)

IMAGES = {
    "mandrill": lambda: mandrill_like_image(103, 103),
    "buttons": lambda: buttons_image(100, 120),
}


def run(subsample: int = 8, levels: int = 3, iterations: int = 30,
        damping: float = 0.5) -> list:
    rows = []
    for name, fn in IMAGES.items():
        img = fn()
        x = image_to_points(img, subsample=subsample)
        n = len(x)
        s = pairwise_similarity(jnp.asarray(x))
        pref = random_preference(jax.random.PRNGKey(0), n, low=-1e6)
        s = set_preferences(s, pref)
        t0 = time.time()
        res = run_hap(stack_levels(s, levels), iterations=iterations,
                      damping=damping, order="parallel")
        dt = time.time() - t0
        hier = link_hierarchy(res.exemplars)
        recon = recolor_by_exemplar(x, hier.exemplars[0])
        mse = float(np.mean((recon - x) ** 2))
        rows.append({
            "image": name, "pixels": n,
            "k_per_level": [int(k) for k in hier.n_clusters],
            "recolor_mse": mse, "wall_s": dt,
        })
    return rows


def main():
    from repro.runtime.xla_cache import use_persistent_cache
    use_persistent_cache()
    rows = run()
    for r in rows:
        print(f"image_{r['image']},{r['wall_s'] * 1e6:.0f},"
              f"k={r['k_per_level']} px={r['pixels']} "
              f"recolor_mse={r['recolor_mse']:.1f}")
    return rows


if __name__ == "__main__":
    main()
