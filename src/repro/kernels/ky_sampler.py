"""Pallas TPU kernel for the rejection-based Knuth-Yao sampler (paper C1).

One kernel invocation draws one exact sample per batch row from an
unnormalized int32 weight vector, consuming packed random bits.  The paper's
per-cycle datapath (Fig. 5) maps onto the TPU as:

  hardware AIA                          this kernel
  ------------------------------------  -------------------------------------
  32-bin distribution in private RF     one int32 plane per bin in VMEM
  per-cycle DDG column read (SU.B)      on-the-fly shift of every bin plane
  parallel-prefix adder over bins       running sum over the unrolled planes
  LFSR random bit                       packed jax.random words in VMEM
  FSM rejection-restart                 masked per-site restart, early-exit
                                        while_loop => O(H) expected levels

Batching over VPU lanes replaces AIA's 16 parallel cores: all same-color
RVs / serving requests walk their DDG trees in lock-step, each with private
state, exactly like the paper's asynchronous cores between barriers.

Layout: the walk works on *planes* — one 2-D array per bin, sites laid out
lane-dense — so the same `ddg_walk` serves this kernel (batch on lanes, one
bin per sublane row of the transposed weights), the MRF kernel ((rows, W)
tiles) and the BN kernel ((chains, nodes) tiles).  The bins are either a
list of planes, unrolled (this kernel and the BN kernel, N + rejection bin
<= 128), or the leading axis of a VMEM ref, walked by a loop over chunks
of `unroll` bins (the MRF kernel, whose 256 gray levels would otherwise
unroll into hundreds of live planes).  The whole working set is VMEM-
resident; the distribution is produced, walked and discarded without an
HBM round-trip — the paper's private-RF locality argument.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat

pl = compat.pallas()

LANES = 128
DEFAULT_BLOCK_B = 256


def zeros_plane(shape, dtype=jnp.int32) -> jax.Array:
    """A 2-D zero array that Mosaic lays out like data (varying along both
    axes).  A splat constant or a one-axis iota gets a replicated layout,
    and a loop carry initialized with one cannot be relaid into the
    layout its loop body produces."""
    z = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
         + jax.lax.broadcasted_iota(jnp.int32, shape, 1)) >> 31
    return z.astype(dtype)


def fold_bins(n: int, body, carry, *, unroll: int | None = None):
    """carry = body(v, carry) for bins v = 0 .. n-1 in order.

    `unroll=None` unrolls every bin (v a Python int: bins held as a list of
    planes).  Otherwise a loop runs over chunks of `unroll` bins, each chunk
    unrolled, and the last n % unroll bins follow unrolled (v traced inside
    the loop: bins on a VMEM ref's leading axis)."""
    if unroll is None:
        for v in range(n):
            carry = body(v, carry)
        return carry
    n_loop = n - n % unroll

    def chunk(i, c):
        for j in range(unroll):
            c = body(i * unroll + j, c)
        return c

    if n_loop:
        carry = jax.lax.fori_loop(0, n_loop // unroll, chunk, carry)
    for v in range(n_loop, n):
        carry = body(v, carry)
    return carry


def preprocess_planes(w, precision: int, *, unroll: int | None = None):
    """Clamp -> scale-to-fill -> rejection bin (Eqns. 8-9) on per-bin planes.

    `w` holds one int32 plane per bin, all of one shape (any sites layout):
    a list, copied and returned with the rejection bin appended, or, with
    `unroll`, a VMEM ref of n + 1 planes whose first n hold the weights,
    scaled in place, the rejection bin written to the last and the ref
    returned (`fold_bins`' loop).  The n + 1 planes sum to 2^precision.
    Integer-only, so any summation order is exact."""
    m, n = ([*w, None], len(w)) if unroll is None else (w, w.shape[0] - 1)

    def clamped(v):
        return jnp.maximum(m[v], 0)

    total = fold_bins(n - 1, lambda v, acc: acc + clamped(v + 1), clamped(0),
                      unroll=unroll)
    empty = total <= 0  # all-zero site -> uniform: n ones
    # (2^W - 1) // S, as core.ky.scale_to_fill: no bin may reach 2^W
    k = jnp.maximum(((1 << precision) - 1) // jnp.where(empty, n, total), 1)

    def scaled(v):
        x = jnp.where(empty, 1, clamped(v)) * k
        m[v] = x
        return x

    first = scaled(0)
    m[n] = (1 << precision) - fold_bins(
        n - 1, lambda v, acc: acc + scaled(v + 1), first, unroll=unroll)
    return m


def ddg_walk(
    m_ext, words: list, *, n_bins: int, precision: int,
    total_steps: int, unroll: int | None = None,
):
    """Early-exit batched DDG walk on per-bin planes.

    `m_ext` is `preprocess_planes`' output (n_bins + 1 int32 planes, the
    rejection bin last: a list, or with `unroll` a VMEM ref) and `words`
    the n_words uint32 planes of packed random bits, all of one 2-D sites
    shape.  Each site walks its own tree: one bit per level, the running
    sum over the bin planes plays the parallel-prefix adder, and the first
    bin whose prefix exceeds the distance terminates.  Returns (labels,
    bits, rejs, done) planes; labels is -1 where the bit budget ran out
    (`argmax_fallback` fills those).

    Sites stay lane-dense and the bins are unrolled or looped over a ref's
    leading axis, so the walk lowers with Mosaic: no lane-axis dynamic
    slice, no (b, 1) state, no matmul."""
    zi = zeros_plane(words[0].shape)
    one = jnp.asarray(1, words[0].dtype)

    # `done` rides the loop as an int32 0/1 plane: Mosaic cannot carry a
    # vector mask through a while loop
    def cond(carry):
        t, d, level, label, done, bits, rejs = carry
        return (t < total_steps) & jnp.any(done == 0)

    def body(carry):
        t, d, level, label, done, bits, rejs = carry
        word = words[0]
        for k in range(1, len(words)):  # static select: word t // 32
            word = jnp.where(t // 32 == k, words[k], word)
        shift = jnp.asarray(t % 32).astype(word.dtype)
        bit = (jnp.right_shift(word, shift) & one).astype(jnp.int32)
        active = done == 0
        d = jnp.where(active, 2 * d + bit, d)
        col_shift = (precision - 1) - level

        def prefix(v, acc):
            c, idx = acc
            c = c + ((m_ext[v] >> col_shift) & 1)
            return c, idx + (c <= d).astype(jnp.int32)  # first bin, c > d

        c, idx = fold_bins(n_bins + 1, prefix, (zi, zi), unroll=unroll)
        terminated = active & (c > d)
        is_rej = idx >= n_bins
        accept = terminated & ~is_rej
        reject = terminated & is_rej
        cont = active & ~terminated
        return (
            t + 1,
            jnp.where(reject, 0, jnp.where(cont, d - c, d)),
            jnp.where(reject, 0, jnp.where(cont, level + 1, level)),
            jnp.where(accept, idx, label),
            jnp.where(accept, 1, done),
            bits + active.astype(jnp.int32),
            rejs + reject.astype(jnp.int32),
        )

    t0 = jnp.zeros((), jnp.int32)
    carry = (t0, zi, zi, zi - 1, zi, zi, zi)
    _, d, level, label, done, bits, rejs = jax.lax.while_loop(cond, body, carry)
    done = done != 0
    return label, bits, rejs, done


def argmax_fallback(w, labels: jax.Array, done: jax.Array, *, n_bins: int,
                    unroll: int | None = None) -> jax.Array:
    """Fallback for the (<2^-max_retries) bit-exhaustion case: the first of
    bins 0 .. n_bins - 1 of largest weight (`jnp.argmax` semantics), on
    per-bin planes (a list, or with `unroll` a ref).  The weights may be
    `preprocess_planes`' scaled ones: for weights >= 0, one positive factor
    per site keeps the first largest bin where it was."""
    def pick(v, acc):
        best, amax = acc
        x = w[v + 1]
        better = x > best
        return jnp.where(better, x, best), jnp.where(better, v + 1, amax)

    _, amax = fold_bins(n_bins - 1, pick, (w[0], zeros_plane(labels.shape)),
                        unroll=unroll)
    return jnp.where(done, labels, amax)


def _ky_kernel(
    w_ref, words_ref, labels_ref, bits_ref, rej_ref, fb_ref,
    *, n_bins: int, n_words: int, precision: int, total_steps: int,
):
    # bins on sublane rows: row v of the transposed block is bin v's plane
    w = [w_ref[v:v + 1, :] for v in range(n_bins)]
    words = [words_ref[k:k + 1, :] for k in range(n_words)]
    label, bits, rejs, done = ddg_walk(
        preprocess_planes(w, precision), words, n_bins=n_bins,
        precision=precision, total_steps=total_steps,
    )
    labels_ref[...] = argmax_fallback(w, label, done, n_bins=n_bins)
    bits_ref[...] = bits
    rej_ref[...] = rejs
    fb_ref[...] = (~done).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "precision", "max_retries", "block_b", "interpret"),
)
def ky_sample_kernel(
    weights: jax.Array,
    words: jax.Array,
    *,
    n_bins: int,
    precision: int = 16,
    max_retries: int = 8,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    """Draw one sample per row. weights (B, LANES) int32 (bins padded to 128,
    lane `n_bins` reserved for the rejection bin), words (B, n_words) uint32.

    Returns (labels (B,), stats dict) — bit-exact vs core.ky.ky_sample_ref.
    """
    # raised, not asserted: these must hold under `python -O` too — a
    # stripped bits check would let the walk read past the random stream
    if weights.shape[-1] != LANES:
        raise ValueError(
            f"weights have {weights.shape[-1]} lanes; pad bins to {LANES} "
            "(ops.ky_sample)"
        )
    if n_bins >= LANES:
        raise ValueError(f"n_bins {n_bins} needs a free rejection lane")
    b, n_words = words.shape[0], words.shape[1]
    total_steps = precision * max_retries
    if n_words * 32 < total_steps:
        raise ValueError(
            f"not enough random bits: {n_words} words < {total_steps} steps"
        )
    # the batch sits on lanes: a partial block must be whole lane tiles
    block_b = b if block_b >= b else min(-(-block_b // LANES) * LANES, b)
    grid = (pl.cdiv(b, block_b),)

    kernel = functools.partial(
        _ky_kernel, n_bins=n_bins, n_words=n_words, precision=precision,
        total_steps=total_steps,
    )
    vmem = compat.pallas_vmem()
    spec_b = lambda rows: pl.BlockSpec((rows, block_b), lambda i: (0, i),
                                       memory_space=vmem)
    # batch on lanes, bins / words on sublane rows (transposed by XLA)
    labels, bits, rejs, fb = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec_b(LANES), spec_b(n_words)],
        out_specs=[spec_b(1)] * 4,
        out_shape=[jax.ShapeDtypeStruct((1, b), jnp.int32)] * 4,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(weights.T, words.T)
    stats = {
        "bits_used": bits[0],
        "rejections": rejs[0],
        "fallback": fb[0].astype(bool),
    }
    return labels[0], stats
