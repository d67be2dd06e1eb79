"""Rejection-based Knuth-Yao (KY) discrete sampling — algorithmic core.

This module implements the paper's C1 contribution (Sec. III-C) as pure
functions on integer weight vectors:

  * a discrete distribution is represented by non-negative integer weights
    ``m_i`` with ``P_i = m_i / sum(m)`` — NO normalization is ever performed;
  * preprocessing appends a *rejection bin* ``rej = 2^W - S`` so the extended
    weights sum to an exact power of two (Eqns. 8-9 of the paper), enabling a
    discrete-distribution-generating (DDG) tree walk;
  * the DDG walk consumes one uniform random bit per tree level and terminates
    in O(H) expected bits (H = entropy), the paper's headline complexity claim;
  * hitting the rejection bin restarts the walk with fresh bits (expected
    number of restarts < 2, typically ~1 thanks to scale-to-fill).

TPU adaptation (DESIGN.md Sec. 2): the paper walks the tree level-by-level and
resolves the terminating bin with a parallel-prefix adder over <=32 bins; we
keep the identical loop structure but resolve bins with a vectorized cumsum
across VPU lanes, batched over many simultaneous samples (the same-color RVs
of the chromatic Gibbs schedule, or the requests of a serving batch).

Everything here is shape-polymorphic pure jnp so it can run inside Pallas
kernel bodies, shard_map regions, and the ref.py oracle alike.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Default tree precision W: extended weights sum to exactly 2^W.
# W=16 reproduces the paper's 16b operating mode (Table II); 8 and 24/31 are
# the packed / high-precision modes. Must satisfy W <= 30 (int32 headroom).
DEFAULT_PRECISION = 16


class KYState(NamedTuple):
    """Per-sample DDG-walk state (all (B,) int32 unless noted)."""

    d: jax.Array  # distance within current tree level
    level: jax.Array  # current tree level, 0-indexed from the MSB
    label: jax.Array  # sampled bin, -1 while walking
    done: jax.Array  # bool
    bits_used: jax.Array  # random bits consumed so far (entropy accounting)
    rejections: jax.Array  # number of rejection-restarts


def scale_to_fill(m: jax.Array, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Multiply integer weights by floor((2^W - 1) / S).

    Scaling all weights by the same positive integer leaves the distribution
    unchanged but pushes the sum toward 2^W, shrinking the rejection bin
    (rej = 2^W - k*S <= S).  This is the software analogue of the paper's
    observation that low-rejection configurations sample fastest.  The
    scaled sum stays below 2^W, so no single bin reaches 2^W: such a weight
    needs bit W, which the W-level DDG tree does not have (a lone nonzero
    bin whose weight divides 2^W would otherwise never terminate).

    m: (..., N) int32, sum(m) in [1, 2^W).  Returns scaled weights.
    """
    s = jnp.sum(m, axis=-1, keepdims=True)
    s = jnp.maximum(s, 1)
    k = ((1 << precision) - 1) // s
    k = jnp.maximum(k, 1)
    return m * k


def extend_with_rejection(
    m: jax.Array, precision: int = DEFAULT_PRECISION
) -> jax.Array:
    """Append the rejection bin: m' = [m_0..m_{N-1}, 2^W - S]  (Eqn. 9).

    Requires sum(m) <= 2^W; the result sums to exactly 2^W so the DDG tree is
    complete and every walk terminates within W levels.
    """
    s = jnp.sum(m, axis=-1, keepdims=True)
    rej = (1 << precision) - s
    return jnp.concatenate([m, rej], axis=-1)


def ddg_matrix(m_ext: jax.Array, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Binary DDG matrix M[i, j] = bit (W-1-j) of m'_i  (Eqn. 10 analogue).

    Column j lists which bins terminate at tree level j.  Only used by tests
    and documentation; the walk extracts columns on the fly with shifts.
    """
    shifts = precision - 1 - jnp.arange(precision)
    return (m_ext[..., :, None] >> shifts) & 1


def ddg_column(m_ext: jax.Array, level: jax.Array, precision: int) -> jax.Array:
    """Column `level` of the DDG matrix, per-sample level. m_ext (B, N+1)."""
    shift = precision - 1 - level
    return (m_ext >> shift[..., None]) & 1


def walk_step(
    m_ext: jax.Array, bit: jax.Array, state: KYState, n_bins: int, precision: int
) -> KYState:
    """One DDG level for a batch of samples (the paper's per-cycle datapath).

    m_ext: (B, N+1) int32 extended weights; bit: (B,) int32 in {0,1}.
    Mirrors Fig. 5: d <- 2d + bit, subtract terminal-leaf counts (cumsum),
    first negative crossing is the sampled label; the rejection bin restarts.
    """
    active = ~state.done
    d = jnp.where(active, 2 * state.d + bit, state.d)
    col = ddg_column(m_ext, state.level, precision)  # (B, N+1)
    c = jnp.cumsum(col, axis=-1)
    total = c[..., -1]
    hit = c > d[..., None]
    terminated = active & (total > d)
    idx = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    is_rej = idx >= n_bins
    accept = terminated & ~is_rej
    reject = terminated & is_rej
    cont = active & ~terminated

    return KYState(
        d=jnp.where(reject, 0, jnp.where(cont, d - total, d)),
        level=jnp.where(reject, 0, jnp.where(cont, state.level + 1, state.level)),
        label=jnp.where(accept, idx, state.label),
        done=state.done | accept,
        bits_used=state.bits_used + active.astype(jnp.int32),
        rejections=state.rejections + reject.astype(jnp.int32),
    )


def bit_at(words: jax.Array, t) -> jax.Array:
    """Bit t of a packed uint32 bit-stream words (B, n_words) (LFSR analogue)."""
    word = jax.lax.dynamic_index_in_dim(words, t // 32, axis=-1, keepdims=False)
    shift = jnp.asarray(t % 32).astype(words.dtype)
    return (jnp.right_shift(word, shift) & jnp.asarray(1, words.dtype)).astype(
        jnp.int32
    )


def init_state(batch_shape) -> KYState:
    z = jnp.zeros(batch_shape, jnp.int32)
    return KYState(
        d=z, level=z, label=z - 1, done=jnp.zeros(batch_shape, bool), bits_used=z,
        rejections=z,
    )


def random_words(key: jax.Array, batch_shape, n_words: int) -> jax.Array:
    """Packed uniform random bits — jax.random stands in for the paper's LFSR."""
    return jax.random.bits(key, batch_shape + (n_words,), jnp.uint32)


def prepare(m: jax.Array, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Full preprocessing: clamp -> scale-to-fill -> rejection-extend."""
    m = jnp.maximum(m.astype(jnp.int32), 0)
    # Guard the all-zero row (caller bug): fall back to uniform.
    s = jnp.sum(m, axis=-1, keepdims=True)
    m = jnp.where(s > 0, m, jnp.ones_like(m))
    m = scale_to_fill(m, precision)
    return extend_with_rejection(m, precision)


def quantize_probs(p: jax.Array, bits: int = 8) -> jax.Array:
    """Float probabilities/potentials -> integer weights (paper Sec. IV: fixed
    point with negligible accuracy loss). max(p) maps to 2^bits - 1."""
    top = (1 << bits) - 1
    scale = top / jnp.maximum(jnp.max(p, axis=-1, keepdims=True), 1e-30)
    return jnp.clip(jnp.round(p * scale), 0, top).astype(jnp.int32)


def entropy(p: np.ndarray) -> float:
    """Shannon entropy in bits — KY consumes at most H+2 bits per accepted
    sample (Knuth-Yao optimality), the basis of the Fig. 11 scaling claim."""
    p = np.asarray(p, np.float64)
    p = p / p.sum()
    nz = p[p > 0]
    return float(-(nz * np.log2(nz)).sum())


@functools.partial(jax.jit, static_argnames=("precision", "max_retries", "n_bins"))
def ky_sample_ref(
    weights: jax.Array,
    words: jax.Array,
    *,
    n_bins: int,
    precision: int = DEFAULT_PRECISION,
    max_retries: int = 8,
):
    """Reference batched rejection-KY walk (fully-masked, fixed trip count).

    weights: (B, N) int32 raw weights (N == n_bins); words: (B, n_words)
    packed random bits with n_words*32 >= precision*max_retries.
    Returns (labels (B,) int32, stats dict).  Deterministic given `words`,
    which is what lets the Pallas kernel be tested for exact equality.
    """
    m_ext = prepare(weights, precision)
    total_steps = precision * max_retries
    if words.shape[-1] * 32 < total_steps:
        # raised, not asserted: a stripped check here would let the walk
        # read past the random stream and silently bias the draw
        raise ValueError(
            f"not enough random bits: {words.shape[-1]} words < "
            f"{total_steps} steps"
        )

    def body(t, st):
        return walk_step(m_ext, bit_at(words, t), st, n_bins, precision)

    st = jax.lax.fori_loop(0, total_steps, body, init_state(weights.shape[:-1]))
    # Fallback (probability < 2^-max_retries): most-probable bin.
    fallback = jnp.argmax(weights, axis=-1).astype(jnp.int32)
    labels = jnp.where(st.done, st.label, fallback)
    stats = {
        "bits_used": st.bits_used,
        "rejections": st.rejections,
        "fallback": ~st.done,
    }
    return labels, stats


@functools.partial(jax.jit, static_argnames=("precision", "max_retries", "n_bins"))
def ky_sample_fast(
    weights: jax.Array,
    words: jax.Array,
    *,
    n_bins: int,
    precision: int = DEFAULT_PRECISION,
    max_retries: int = 8,
):
    """Early-exit variant of ky_sample_ref: identical outputs (same masked
    per-step updates and bit consumption), but the loop stops once every
    sample in the batch has terminated — expected O(H) steps, the software
    analogue of the hardware FSM's data-dependent latency."""
    m_ext = prepare(weights, precision)
    total_steps = precision * max_retries
    if words.shape[-1] * 32 < total_steps:
        # raised, not asserted (see ky_sample_ref): shape check runs at
        # trace time, so a plain ValueError is jit-safe
        raise ValueError(
            f"not enough random bits: {words.shape[-1]} words < "
            f"{total_steps} steps"
        )

    def cond(carry):
        t, st = carry
        return (t < total_steps) & jnp.any(~st.done)

    def body(carry):
        t, st = carry
        return t + 1, walk_step(m_ext, bit_at(words, t), st, n_bins,
                                precision)

    _, st = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), init_state(weights.shape[:-1]))
    )
    fallback = jnp.argmax(weights, axis=-1).astype(jnp.int32)
    labels = jnp.where(st.done, st.label, fallback)
    return labels, {
        "bits_used": st.bits_used,
        "rejections": st.rejections,
        "fallback": ~st.done,
    }
