"""Reference checkerboard Gibbs on a Potts grid MRF (free boundary, Potts
data term): what each served denoising query must equal, bit for bit.

Semantics of one query (seed s, noisy image e):
  * key = PRNG key of s; (k0, key) = split(key); labels start at
    randint(k0, (chains, H, W), 0, V);
  * each sweep: keys = split(key, 3); key = keys[0]; the two colors of the
    grid's DSATUR coloring in color order, color i with keys[1 + i]: every
    site of that parity draws from
        log P(l = v | neighbors, e) = theta * #(4-neighbors at v)
                                      + h * [e == v]   (float32)
    while the other parity keeps its labels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import coloring
from bench.reference.sampler import DrawSpec, draw


class MRFReference:
    def __init__(self, height: int, width: int, n_labels: int, theta: float,
                 h: float, spec: DrawSpec):
        self.height, self.width, self.v = height, width, n_labels
        self.theta, self.h, self.spec = float(theta), float(h), spec
        colors = coloring.dsatur(coloring.grid_adjacency(height, width))
        parity = (np.arange(height)[:, None] + np.arange(width)[None]) % 2
        self.masks = [
            jnp.asarray(colors.reshape(height, width) == c)
            for c in range(int(colors.max()) + 1)
        ]
        for m in self.masks:  # each color is one whole checkerboard parity
            if len(np.unique(parity[np.asarray(m)])) != 1:
                raise AssertionError("grid coloring is not a checkerboard")
        self._run = jax.jit(self._sample,
                            static_argnames=("n_chains", "n_iters"))

    def _log_potentials(self, labels, image):
        values = jnp.arange(self.v, dtype=labels.dtype)
        onehot = (labels[..., None] == values).astype(jnp.float32)
        z_row = jnp.zeros_like(onehot[:, :1])
        z_col = jnp.zeros_like(onehot[:, :, :1])
        counts = (
            jnp.concatenate([z_row, onehot[:, :-1]], axis=1)
            + jnp.concatenate([onehot[:, 1:], z_row], axis=1)
            + jnp.concatenate([z_col, onehot[:, :, :-1]], axis=2)
            + jnp.concatenate([onehot[:, :, 1:], z_col], axis=2)
        )
        data = self.h * (image[..., None] == values).astype(jnp.float32)
        return self.theta * counts + data

    def _sample(self, image, seed, *, n_chains, n_iters):
        k0, key = jax.random.split(jax.random.key(seed))
        labels = jax.random.randint(
            k0, (n_chains, self.height, self.width), 0, self.v, jnp.int32)

        def sweep(_, state):
            labels, key = state
            keys = jax.random.split(key, 1 + len(self.masks))
            for mask, k in zip(self.masks, keys[1:]):
                new = draw(self._log_potentials(labels, image), k, self.spec)
                labels = jnp.where(mask[None], new, labels)
            return labels, keys[0]

        labels, _ = jax.lax.fori_loop(0, n_iters, sweep, (labels, key))
        return labels

    def run(self, image, seed: int, *, n_chains: int,
            n_iters: int) -> np.ndarray:
        """Final labels (chains, H, W) of one query."""
        return np.asarray(self._run(
            jnp.asarray(image, jnp.int32), jnp.asarray(seed, jnp.uint32),
            n_chains=n_chains, n_iters=n_iters))
