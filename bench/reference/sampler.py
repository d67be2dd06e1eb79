"""The draw every site update makes: LUT-interpolated exp weights, then a
rejection Knuth-Yao walk over packed random bits.

The datapath as the configuration states it:
  * z = logp - max(logp) per site, in float32;
  * weight = round(lerp of a `size`-entry table of rint(exp(x) * (2^bits-1))
    over x in [x_min, 0]), inputs clamped to the table's range;
  * weights scaled by floor((2^W - 1) / sum), a rejection bin 2^W - sum
    appended, and the DDG tree walked one random bit per level, restarting
    on the rejection bin, for at most W * max_retries bits; a walk that
    never terminates takes the largest weight;
  * W = max(precision, 8 + bit_length(V - 1) + 1): the tree is widened so
    V weights of 8 bits fit below 2^W whatever the table's own width;
  * the random bits are `jax.random.bits(key, (sites, words))`, bit t of a
    site being bit t % 32 of its word t // 32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# Added to log-probabilities of values a node cannot take.
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class DrawSpec:
    """The draw's precision, from the configuration's `lut` and `ky`."""

    weight_bits: int = 8
    lut_size: int = 16
    x_min: float = -8.0
    precision: int = 16
    max_retries: int = 8

    @classmethod
    def from_config(cls, config: dict, weight_bits: int | None = None):
        lut, ky = config["lut"], config["ky"]
        return cls(
            weight_bits=lut["weight_bits"] if weight_bits is None
            else weight_bits,
            lut_size=lut["size"], x_min=float(lut["x_min"]),
            precision=ky["precision"], max_retries=ky["max_retries"],
        )

    @property
    def dx(self) -> float:
        return (0.0 - self.x_min) / (self.lut_size - 1)

    def table(self) -> np.ndarray:
        top = float((1 << self.weight_bits) - 1)
        xs = self.x_min + self.dx * np.arange(self.lut_size, dtype=np.float64)
        return np.rint(np.exp(xs) * top).astype(np.float32)

    def tree_bits(self, n_values: int) -> int:
        # the tree width follows the stated 8-bit weights, so a control
        # computed with narrower weights walks the same random bits
        return max(self.precision, 8 + (n_values - 1).bit_length() + 1)

    def n_words(self, n_values: int) -> int:
        return -(-self.tree_bits(n_values) * self.max_retries // 32)


def lut_weights(z: jax.Array, spec: DrawSpec) -> jax.Array:
    """Integer weights of max-subtracted log-potentials z (<= 0)."""
    table = jnp.asarray(spec.table())
    inv_dx = 1.0 / spec.dx
    u = jnp.clip((z - spec.x_min) * inv_dx, 0.0, spec.lut_size - 1)
    i = jnp.clip(jnp.floor(u), 0, spec.lut_size - 2).astype(jnp.int32)
    frac = u - i.astype(u.dtype)
    y0, y1 = table[i], table[i + 1]
    w = y0 + frac * (y1 - y0)
    return jnp.maximum(jnp.round(w), 0.0).astype(jnp.int32)


def knuth_yao(weights: jax.Array, words: jax.Array, tree_bits: int,
              max_retries: int) -> jax.Array:
    """Rejection Knuth-Yao draw of one value per row of integer weights
    (N, V), walking the bits of words (N, n_words) uint32."""
    n, v = weights.shape
    m = jnp.maximum(weights, 0)
    m = jnp.where(m.sum(-1, keepdims=True) > 0, m, 1)
    total = jnp.maximum(m.sum(-1, keepdims=True), 1)
    m = m * jnp.maximum(((1 << tree_bits) - 1) // total, 1)
    m = jnp.concatenate(
        [m, (1 << tree_bits) - m.sum(-1, keepdims=True)], axis=-1
    )
    zero = jnp.zeros((n,), jnp.int32)

    def step(t, state):
        d, level, label, done = state
        word = jax.lax.dynamic_index_in_dim(words, t // 32, 1, False)
        bit = ((word >> (t % 32).astype(jnp.uint32)) & 1).astype(jnp.int32)
        active = ~done
        d = jnp.where(active, 2 * d + bit, d)
        column = (m >> (tree_bits - 1 - level)[:, None]) & 1
        running = jnp.cumsum(column, axis=-1)
        leaves = running[:, -1]
        ends = active & (leaves > d)
        value = jnp.argmax(running > d[:, None], axis=-1).astype(jnp.int32)
        accept = ends & (value < v)
        reject = ends & (value >= v)
        walking = active & ~ends
        d = jnp.where(reject, 0, jnp.where(walking, d - leaves, d))
        level = jnp.where(reject, 0, jnp.where(walking, level + 1, level))
        label = jnp.where(accept, value, label)
        return d, level, label, done | accept

    _, _, label, done = jax.lax.fori_loop(
        0, tree_bits * max_retries, step,
        (zero, zero, zero - 1, jnp.zeros((n,), bool)),
    )
    return jnp.where(done, label, jnp.argmax(weights, -1).astype(jnp.int32))


def draw(logp: jax.Array, key: jax.Array, spec: DrawSpec) -> jax.Array:
    """One value per site of log-potentials (..., V)."""
    shape, v = logp.shape[:-1], logp.shape[-1]
    flat = logp.reshape(-1, v)
    z = flat - jnp.max(flat, axis=-1, keepdims=True)
    words = jax.random.bits(key, (flat.shape[0], spec.n_words(v)), jnp.uint32)
    labels = knuth_yao(lut_weights(z, spec), words, spec.tree_bits(v),
                       spec.max_retries)
    return labels.reshape(shape)
