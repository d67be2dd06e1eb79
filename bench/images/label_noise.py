"""label_noise: a piecewise-constant image of rectangles with each pixel
replaced by a uniform label with probability `noise` (the mix's key): the
noisy observation of a denoising query.  A fixed copy of the program's
`core/mrf.make_denoising_problem`, so a change there cannot move the
benchmark."""

import numpy as np


def draw(height: int, width: int, labels: int, traffic: dict,
         seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    clean = np.zeros((height, width), np.int32)
    for _ in range(max(3, labels)):
        r0, c0 = rng.integers(0, height), rng.integers(0, width)
        rh, cw = rng.integers(height // 4, height), rng.integers(width // 4, width)
        clean[r0:r0 + rh, c0:c0 + cw] = rng.integers(0, labels)
    flip = rng.random((height, width)) < traffic["noise"]
    noisy = np.where(flip, rng.integers(0, labels, (height, width)), clean)
    return noisy.astype(np.int32)
