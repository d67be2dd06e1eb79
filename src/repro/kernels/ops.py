"""Public jit'd entry points for the Pallas kernels.

These wrappers own all layout plumbing (lane padding, batch padding, random
word generation, interpret-mode selection) so callers see clean shapes.
On CPU the kernels run in interpret mode (the kernel body evaluated as XLA
ops); on TPU Mosaic compiles them; `compat.pallas_interpret` decides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import ky as ky_core
from repro.core.interp import LUTSpec
from repro.kernels import interp_lut as _interp_lut
from repro.kernels import ky_sampler as _ky

LANES = 128


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return compat.pallas_interpret()
    return interpret


def _pad_axis(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def ky_sample(
    weights: jax.Array,
    key: jax.Array,
    *,
    precision: int = 16,
    max_retries: int = 8,
    block_b: int = _ky.DEFAULT_BLOCK_B,
    interpret: bool | None = None,
    return_stats: bool = False,
):
    """Draw one exact sample per row from unnormalized int32 weights.

    weights: (B, N) int32, N < 128 (wider distributions: use token_sampler's
    hierarchical path).  Returns labels (B,) int32 [, stats].
    """
    b, n_bins = weights.shape
    if n_bins >= LANES:  # raised, not asserted: must hold under `python -O`
        raise ValueError(
            f"KY kernel handles <={LANES - 1} bins, got {n_bins}; "
            "see token_sampler"
        )
    wpad = _pad_axis(weights.astype(jnp.int32), 1, LANES)
    n_words = -(-precision * max_retries // 32)
    words = ky_core.random_words(key, (b,), n_words)
    # pad batch to the block size so every grid block is full
    bb = min(block_b, b)
    wpad = _pad_axis(wpad, 0, bb, value=1)
    words_p = _pad_axis(words, 0, bb)
    labels, stats = _ky.ky_sample_kernel(
        wpad,
        words_p,
        n_bins=n_bins,
        precision=precision,
        max_retries=max_retries,
        block_b=bb,
        interpret=_auto_interpret(interpret),
    )
    labels = labels[:b]
    if return_stats:
        return labels, jax.tree.map(lambda s: s[:b], stats)
    return labels


def interp(
    x: jax.Array,
    table: jax.Array,
    spec: LUTSpec,
    *,
    block_m: int = _interp_lut.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
) -> jax.Array:
    """Vectorized LUT lerp over an arbitrary-shaped float array."""
    shape = x.shape
    flat = x.reshape(-1)
    total = flat.shape[0]
    # lay out as (M, 128·k) tiles
    n = LANES
    m = -(-total // n)
    flat = _pad_axis(flat, 0, m * n).reshape(m, n)
    mb = min(block_m, m)
    flat = _pad_axis(flat, 0, mb)
    tab = _pad_axis(table.reshape(1, -1).astype(jnp.float32), 1, LANES)
    y = _interp_lut.interp_kernel(
        flat, tab, spec=spec, block_m=mb, interpret=_auto_interpret(interpret)
    )
    return y.reshape(-1)[:total].reshape(shape)


def lut_exp_weights(
    log_potentials: jax.Array,
    exp_table: jax.Array,
    exp_spec: LUTSpec,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused C2 stage of the sampling pipeline: max-subtracted log-potentials
    -> LUT-exp -> integer KY weights (no softmax, no normalization)."""
    z = log_potentials - jax.lax.stop_gradient(
        jnp.max(log_potentials, axis=-1, keepdims=True)
    )
    w = interp(z, exp_table, exp_spec, interpret=interpret)
    return jnp.maximum(jnp.round(w), 0.0).astype(jnp.int32)
