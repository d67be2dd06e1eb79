"""Reference checkerboard Gibbs on a grid MRF with truncated-quadratic
smoothness, a free boundary and masked observations (the Middlebury
denoising and inpainting energy): what each served query must equal, bit
for bit.

The configuration's model entry is the energy (`bench/spec.py`): `height`,
`width`, `labels`, `theta`, `h`, `pairwise` (`truncated_quadratic` only),
`truncation` and `data_cost` (`quadratic` or `potts`); `image` names the
observation module and is not part of the energy.  A key or value this
module does not implement is refused, so no part of an energy is ignored.

Semantics of one query (seed s, observation e, e < 0 where unobserved):
  * key = PRNG key of s; (k0, key) = split(key); labels start at
    randint(k0, (chains, H, W), 0, V);
  * each sweep: keys = split(key, 3); key = keys[0]; the two colors of the
    grid's DSATUR coloring in color order, color i with keys[1 + i]: the
    random words are bits(keys[1 + i], (chains * H * W, words)) in
    row-major site order, and every site of that parity draws, with its
    own words, from (float32, neighbors up, down, left, right)
        n_q = min(d * d, T) with d = float32(v - l_q), or 0 past the edge
        S = -theta * (((n_up + n_down) + n_left) + n_right)
        D = (-h * d) * d with d = float32(e - v)   (`quadratic`)
          | h * float32(e == v)                   (`potts`)
        log P(l = v) = S + (D where e >= 0, else 0)
    while the other parity keeps its labels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import coloring
from bench.reference.sampler import DrawSpec


IMPLEMENTED = frozenset({"height", "width", "labels", "image", "theta", "h",
                         "data_cost", "pairwise", "truncation"})
PAIRWISE = ("truncated_quadratic",)
DATA_COSTS = ("quadratic", "potts")


def knuth_yao(weights: jax.Array, words: jax.Array, tree_bits: int,
              max_retries: int) -> jax.Array:
    """`sampler.knuth_yao`'s draw, walked on leaf counts.  Level l of the
    tree holds bit tree_bits - 1 - l of each scaled weight and of the
    rejection bin; a walk at distance d ends there if the level has more
    than d leaves, on a value if more than d of them are values', and
    else moves on with d minus the level's leaves.  So the walk needs
    only each level's two counts, and the value (the (d + 1)-th leaf)
    is found once, at the level where the walk ended: the same draws as
    a cumulative sum at every level, without one (N, V + 1) pass per bit.
    A row's count at its level is picked by comparing the level with
    each of the tree's levels, not by a gather."""
    n = weights.shape[0]
    m = jnp.maximum(weights, 0)
    m = jnp.where(m.sum(-1, keepdims=True) > 0, m, 1)
    total = jnp.maximum(m.sum(-1, keepdims=True), 1)
    m = m * jnp.maximum(((1 << tree_bits) - 1) // total, 1)
    rejection = (1 << tree_bits) - m.sum(-1)
    shifts = [tree_bits - 1 - level for level in range(tree_bits)]
    value_leaves = jnp.stack([((m >> s) & 1).sum(-1) for s in shifts], -1)
    leaves = value_leaves + jnp.stack([(rejection >> s) & 1 for s in shifts],
                                      -1)
    levels = jnp.arange(tree_bits, dtype=jnp.int32)
    zero = jnp.zeros((n,), jnp.int32)

    def at(counts, level):
        return jnp.where(level[:, None] == levels, counts, 0).sum(-1)

    def step(t, state):
        d, level, done = state
        word = jax.lax.dynamic_index_in_dim(words, t // 32, 1, False)
        bit = ((word >> (t % 32).astype(jnp.uint32)) & 1).astype(jnp.int32)
        active = ~done
        d = jnp.where(active, 2 * d + bit, d)
        here = at(leaves, level)
        ends = active & (here > d)
        accept = ends & (at(value_leaves, level) > d)
        reject = ends & ~accept
        walking = active & ~ends
        d = jnp.where(reject, 0, jnp.where(walking, d - here, d))
        level = jnp.where(reject, 0, jnp.where(walking, level + 1, level))
        return d, level, done | accept

    d, level, done = jax.lax.fori_loop(
        0, tree_bits * max_retries, step,
        (zero, zero, jnp.zeros((n,), bool)))
    column = (m >> (tree_bits - 1 - level)[:, None]) & 1
    value = jnp.argmax(jnp.cumsum(column, axis=-1) > d[:, None], axis=-1)
    return jnp.where(done, value.astype(jnp.int32),
                     jnp.argmax(weights, -1).astype(jnp.int32))


def lut_weights(z: jax.Array, spec: DrawSpec) -> jax.Array:
    """`sampler.lut_weights`, with the table's entries picked by comparing
    the index with each entry's, not by a gather: the same float32
    operations on the same values, so the same weights."""
    table = [jnp.float32(y) for y in spec.table()]
    inv_dx = 1.0 / spec.dx
    u = jnp.clip((z - spec.x_min) * inv_dx, 0.0, spec.lut_size - 1)
    i = jnp.clip(jnp.floor(u), 0, spec.lut_size - 2).astype(jnp.int32)
    frac = u - i.astype(u.dtype)
    y0, y1 = jnp.full_like(u, table[0]), jnp.full_like(u, table[1])
    for j in range(1, spec.lut_size - 1):
        y0 = jnp.where(i == j, table[j], y0)
        y1 = jnp.where(i == j, table[j + 1], y1)
    w = y0 + frac * (y1 - y0)
    return jnp.maximum(jnp.round(w), 0.0).astype(jnp.int32)


class MRFReference:
    def __init__(self, entry: dict, spec: DrawSpec):
        unknown = sorted(set(entry) - IMPLEMENTED)
        if unknown:
            raise ValueError(f"the MRF reference does not implement {unknown}")
        for key, known in (("pairwise", PAIRWISE),
                           ("data_cost", DATA_COSTS)):
            if entry.get(key) not in known:
                raise ValueError(f"the MRF reference does not implement "
                                 f"{key} {entry.get(key)!r}")
        self.pairwise, self.data_cost = entry["pairwise"], entry["data_cost"]
        height, width = entry["height"], entry["width"]
        self.height, self.width, self.v = height, width, entry["labels"]
        self.theta, self.h = float(entry["theta"]), float(entry["h"])
        self.truncation = float(entry["truncation"])
        self.spec = spec
        colors = coloring.dsatur(coloring.grid_adjacency(height, width))
        colors = colors.reshape(height, width)
        parity = (np.arange(height)[:, None] + np.arange(width)[None]) % 2
        # each color's sites, row-major: one whole checkerboard parity
        self.sites = []
        for c in range(int(colors.max()) + 1):
            rows, cols = np.nonzero(colors == c)
            if len(np.unique(parity[rows, cols])) != 1:
                raise AssertionError("grid coloring is not a checkerboard")
            self.sites.append((rows, cols))
        self._run = jax.jit(self._sample,
                            static_argnames=("n_chains", "n_iters"))

    def _log_potentials(self, labels, image, rows, cols):
        """(chains, sites, V) log-potentials of the given sites."""
        values = jnp.arange(self.v, dtype=jnp.int32)
        edge = jnp.pad(labels, ((0, 0), (1, 1), (1, 1)), constant_values=-1)
        smooth = None
        for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
            q = edge[:, rows + 1 + dr, cols + 1 + dc][..., None]
            d = (values - q).astype(jnp.float32)
            n = jnp.where(q >= 0, jnp.minimum(d * d, self.truncation), 0.0)
            smooth = n if smooth is None else smooth + n
        smooth = -self.theta * smooth
        e = image[rows, cols][None, :, None]
        if self.data_cost == "quadratic":
            diff = (e - values).astype(jnp.float32)
            data = -self.h * diff * diff
        else:
            data = self.h * (e == values).astype(jnp.float32)
        return smooth + jnp.where(e >= 0, data, 0.0)

    def _draw(self, labels, image, key, rows, cols):
        """New labels of one color's sites."""
        logp = self._log_potentials(labels, image, rows, cols)
        z = logp - jnp.max(logp, axis=-1, keepdims=True)
        n_chains = labels.shape[0]
        words = jax.random.bits(
            key, (n_chains * self.height * self.width,
                  self.spec.n_words(self.v)), jnp.uint32)
        words = words.reshape(n_chains, self.height * self.width, -1)
        words = words[:, rows * self.width + cols]
        new = knuth_yao(
            lut_weights(z, self.spec).reshape(-1, self.v),
            words.reshape(-1, words.shape[-1]), self.spec.tree_bits(self.v),
            self.spec.max_retries)
        return labels.at[:, rows, cols].set(new.reshape(n_chains, -1))

    def _sample(self, image, seed, *, n_chains, n_iters):
        k0, key = jax.random.split(jax.random.key(seed))
        labels = jax.random.randint(
            k0, (n_chains, self.height, self.width), 0, self.v, jnp.int32)

        def sweep(_, state):
            labels, key = state
            keys = jax.random.split(key, 1 + len(self.sites))
            for (rows, cols), k in zip(self.sites, keys[1:]):
                labels = self._draw(labels, image, k, rows, cols)
            return labels, keys[0]

        labels, _ = jax.lax.fori_loop(0, n_iters, sweep, (labels, key))
        return labels

    def run(self, image, seed: int, *, n_chains: int,
            n_iters: int) -> np.ndarray:
        """Final labels (chains, H, W) of one query."""
        return np.asarray(self._run(
            jnp.asarray(image, jnp.int32), jnp.asarray(seed, jnp.uint32),
            n_chains=n_chains, n_iters=n_iters))
