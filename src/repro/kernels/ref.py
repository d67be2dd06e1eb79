"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(exact equality for the integer/bit-deterministic paths, allclose for the
float paths).  They are also the CPU fallback used by the PM engines when
running outside interpret mode is not desired.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ky as ky_core
from repro.core import mrf as mrf_mod
from repro.core.interp import LUTSpec, interp_ref


def ky_sample(
    weights: jax.Array,
    words: jax.Array,
    *,
    n_bins: int,
    precision: int = 16,
    max_retries: int = 8,
):
    """Oracle for kernels.ky_sampler — bit-deterministic given `words`.

    Accepts either exact-width (B, n_bins) or lane-padded (B, 128) weights.
    """
    w = weights[..., :n_bins]
    return ky_core.ky_sample_ref(
        w, words, n_bins=n_bins, precision=precision, max_retries=max_retries
    )


def interp(x: jax.Array, table: jax.Array, spec: LUTSpec) -> jax.Array:
    """Oracle for kernels.interp_lut."""
    return interp_ref(x, jnp.ravel(table)[: spec.size], spec)


def mrf_gibbs_half_step(
    labels: jax.Array,
    evidence: jax.Array,
    words: jax.Array,
    *,
    parity: int,
    theta: float,
    h: float,
    n_labels: int,
    exp_table: jax.Array,
    exp_spec: LUTSpec,
    data_cost: str = "potts",
    pairwise: str = "potts",
    truncation: float | None = None,
    precision: int = 16,
    max_retries: int = 8,
) -> jax.Array:
    """Oracle for kernels.mrf_gibbs: one checkerboard half-step of chromatic
    Gibbs on a grid MRF (paper Eqn. 7, Alg. 2 with K=2 colors).

    labels, evidence: (H, W) int32, labels in [0, n_labels), evidence < 0
    where unobserved; words: (H, W, n_words) uint32; parity selects the
    color (checkerboard) being updated.

    The energy of assigning value v at site (i,j) is `GridMRF`'s, in its
    float32 operation order (`core.mrf.log_potentials`).  P(v) ∝ exp(E(v));
    exp is evaluated through the integer-weight LUT (C2) and the draw uses
    rejection-KY (C1) — normalization-free end to end.  Op-for-op identical
    to the fused kernel so equality is exact.
    """
    hh, ww = labels.shape
    e = mrf_mod.log_potentials(
        [q[..., None] for q in mrf_mod.neighbor_labels(labels)],
        evidence[..., None], jnp.arange(n_labels, dtype=labels.dtype),
        theta=theta, h=h, data_cost=data_cost, pairwise=pairwise,
        truncation=truncation,
    )  # (H, W, V)
    z = e - e.max(axis=-1, keepdims=True)
    tab = jnp.ravel(exp_table)[: exp_spec.size]
    w_int = jnp.maximum(jnp.round(interp_ref(z, tab, exp_spec)), 0.0)
    w_int = w_int.astype(jnp.int32)

    new, _ = ky_core.ky_sample_ref(
        w_int.reshape(-1, n_labels),
        words.reshape(hh * ww, -1),
        n_bins=n_labels,
        precision=precision,
        max_retries=max_retries,
    )
    new = new.reshape(hh, ww)
    ii = jnp.arange(hh)[:, None] + jnp.arange(ww)[None, :]
    return jnp.where((ii % 2) == parity, new, labels)
