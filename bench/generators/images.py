"""Procedural stand-ins for the paper's segmentation images (Mandrill,
103 x 103, and Buttons, 120 x 100; arXiv:1403.7394 section 4.1), as RGB
uint8 arrays. The USC-SIPI files cannot be fetched here; these have the
same sizes and comparable colour statistics (a multi-hue organic texture,
a grid of coloured discs on grey)."""
from __future__ import annotations

import numpy as np


def mandrill(h: int = 103, w: int = 103, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
    yn, xn = yy / h, xx / w
    f1 = np.sin(3.1 * xn + 1.7) * np.cos(2.3 * yn)
    f2 = np.cos(4.2 * xn * yn + 0.5) + np.sin(2.9 * yn)
    r = 0.55 + 0.4 * f1
    g = 0.45 + 0.35 * np.sin(5.0 * (xn - 0.5) ** 2 + 3.0 * yn)
    b = 0.5 + 0.45 * f2 * 0.5
    img = np.stack([r, g, b], axis=-1)
    img += 0.06 * rng.standard_normal(img.shape)
    return (np.clip(img, 0, 1) * 255).astype(np.uint8)


def buttons(h: int = 100, w: int = 120, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    img = np.full((h, w, 3), 0.82)
    palette = np.array([
        [0.85, 0.1, 0.1], [0.1, 0.5, 0.9], [0.95, 0.8, 0.1],
        [0.2, 0.7, 0.3], [0.6, 0.2, 0.7], [0.9, 0.5, 0.1],
    ])
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
    k = 0
    for cy in range(12, h, 25):
        for cx in range(14, w, 28):
            rad = 9 + rng.integers(0, 3)
            mask = (yy - cy) ** 2 + (xx - cx) ** 2 <= rad ** 2
            color = palette[k % len(palette)] * (0.85 + 0.3 * rng.random())
            img[mask] = np.clip(color, 0, 1)
            k += 1
    img += 0.02 * rng.standard_normal(img.shape)
    return (np.clip(img, 0, 1) * 255).astype(np.uint8)


IMAGES = {"mandrill": mandrill, "buttons": buttons}


def edges(size: int, parts: int) -> list:
    """Boundaries of ``parts`` near-equal spans of ``size`` pixels."""
    return [round(i * size / parts) for i in range(parts + 1)]


def tiles(img: np.ndarray, gy: int, gx: int) -> list:
    """The image cut by a fixed gy x gx grid: ((ty, tx), points) per
    tile, row-major; see ``rgb_points``."""
    ys, xs = edges(img.shape[0], gy), edges(img.shape[1], gx)
    return [((ty, tx), rgb_points(img[ys[ty]:ys[ty + 1],
                                      xs[tx]:xs[tx + 1]]))
            for ty in range(gy) for tx in range(gx)]


def rgb_points(block: np.ndarray) -> np.ndarray:
    """An (h, w, 3) uint8 block as (h * w, 3) float32 points: the
    pixels' RGB intensities in [0, 1], the feature vectors of the
    paper's image experiments."""
    return (block.astype(np.float32) / 255.0).reshape(-1, 3)
