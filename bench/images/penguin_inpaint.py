"""penguin_inpaint: a stand-in for the Middlebury MRF benchmark's Penguin
denoising and inpainting task (Szeliski et al., IEEE TPAMI 2008), whose
image and mask files are not in the repository.  At the published size
(height x width, 256 gray levels), it draws:

  * a piecewise-smooth 8-bit image: a shallow gradient behind a few
    ellipses, each filled with a gradient of its own;
  * Gaussian noise of standard deviation `noise_sigma` (the mix's key),
    rounded and clipped to the gray levels;
  * a hole of strokes and blocks covering `mask_fraction` of the pixels
    (the mix's key), each marked -1: unobserved, the part to inpaint.
"""

import numpy as np


def _smooth_image(rng, height: int, width: int, top: int) -> np.ndarray:
    r = np.arange(height, dtype=np.float64)[:, None] / height
    c = np.arange(width, dtype=np.float64)[None, :] / width
    img = top * (0.2 + 0.3 * r + 0.2 * c) * np.ones((height, width))
    for _ in range(6):
        r0, c0 = rng.uniform(0.1, 0.9), rng.uniform(0.1, 0.9)
        a, b = rng.uniform(0.08, 0.35), rng.uniform(0.08, 0.35)
        inside = ((r - r0) / a) ** 2 + ((c - c0) / b) ** 2 <= 1.0
        level = rng.uniform(0.0, 1.0) * top
        slope_r, slope_c = rng.uniform(-0.5, 0.5, size=2) * top
        img = np.where(inside, level + slope_r * (r - r0)
                       + slope_c * (c - c0), img)
    return np.clip(img, 0, top)


def _hole(rng, height: int, width: int, fraction: float) -> np.ndarray:
    """Strokes (thin, long) and blocks until `fraction` of the pixels are
    covered."""
    hole = np.zeros((height, width), bool)
    target = fraction * height * width
    while hole.sum() < target:
        if rng.random() < 0.5:  # a horizontal or vertical stroke
            thick, length = rng.integers(2, 5), rng.integers(10, 40)
            if rng.random() < 0.5:
                thick, length = length, thick
        else:
            thick, length = rng.integers(4, 12), rng.integers(4, 12)
        r0 = rng.integers(0, max(1, height - thick))
        c0 = rng.integers(0, max(1, width - length))
        hole[r0:r0 + thick, c0:c0 + length] = True
    return hole


def draw(height: int, width: int, labels: int, traffic: dict,
         seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    top = labels - 1
    clean = _smooth_image(rng, height, width, top)
    noisy = clean + rng.normal(0.0, traffic["noise_sigma"], clean.shape)
    noisy = np.clip(np.rint(noisy), 0, top).astype(np.int32)
    hole = _hole(rng, height, width, traffic["mask_fraction"])
    return np.where(hole, -1, noisy).astype(np.int32)
