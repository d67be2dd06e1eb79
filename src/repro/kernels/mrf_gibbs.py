"""Fused Pallas kernel: one checkerboard Gibbs half-step on a grid MRF.

This is the AIA inner loop (paper Sec. III "Approximate Inference Overview")
as a single VMEM-resident pipeline, fusing all four innovations:

  1. neighbor-label exchange (C4)  — halo rows come from the adjacent row
     blocks (BlockSpec index maps i-1 / i / i+1), the intra-tile shifts are
     VMEM slices; across devices, distributed.py replaces the halo load
     with a `ppermute` — the mesh-neighbor register read, ICI-native;
  2. energy computation (programmable ALU) — `GridMRF`'s energy (Potts or
     truncated-quadratic smoothness, a masked data cost) in its float32
     operation order, `core.mrf.log_potentials`;
  3. LUT-exp via the interpolation unit (C2) — `interp_eval`, int8 weights;
  4. rejection-KY draw (C1) — `ddg_walk` over one plane per label value.

The conditional distribution of every site is produced, sampled and
discarded inside the tile — zero HBM round-trips for intermediates, the
paper's private-RF locality argument.  Per label value the tile holds one
float32 energy plane and one int32 weight plane in VMEM scratch, filled
and walked by loops over chunks of `UNROLL` labels, so 256 gray levels cost
loop trips, not live values.  A tile is `tile_rows` rows: a multiple of 8
chosen from the label count, the grid's rows padded to whole tiles with
out-of-grid rows (-1) that are never written back.  Bit-exact against
ref.mrf_gibbs_half_step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import ky as ky_core
from repro.core import mrf as mrf_mod
from repro.core.interp import LUTSpec
from repro.kernels.interp_lut import interp_eval
from repro.kernels.ky_sampler import argmax_fallback, ddg_walk, fold_bins, \
    preprocess_planes
from repro.kernels.mrf_tiles import SUBLANES, round_up, tile_rows

pl = compat.pallas()

KERNEL_NAME = "mrf_gibbs_kernel"
# Labels per unrolled chunk of the per-label loops.
UNROLL = 8


def _pad_rows(x: jax.Array, rows: int, fill) -> jax.Array:
    """(H, ...) -> (rows, ...): rows of `fill` appended, a scalar or a
    (1, ...) row (then -1 rows after it)."""
    extra = rows - x.shape[0]
    if extra == 0:
        return x
    if not isinstance(fill, jax.Array):
        return jnp.pad(x, [(0, extra)] + [(0, 0)] * (x.ndim - 1),
                       constant_values=fill)
    return _pad_rows(jnp.concatenate([x, fill], axis=0), rows, -1)


def _mrf_tile_body(
    lab, up_halo, down_halo, ev, words, tab_ref, e_ref, m_ref, phase,
    *, energy: tuple, n_labels: int, x0: float, inv_dx: float,
    lut_size: int, precision: int, total_steps: int, block_h: int,
    width: int,
):
    """The fused half-step pipeline on one (block_h, W) tile: energies ->
    LUT-exp -> KY walk -> checkerboard scatter.  `up_halo`/`down_halo` are
    the tile's boundary neighbor rows ((1, W); -1 where the grid ends) and
    `phase` the tile's first global row plus the parity updated: the sites
    whose row + column + phase is even update.  `e_ref`
    (V, rows, W) float32 and `m_ref` (V + 1, rows, W) int32 are the VMEM
    scratch of the per-label planes."""
    up = jnp.concatenate([up_halo, lab[:-1, :]], axis=0)
    down = jnp.concatenate([lab[1:, :], down_halo], axis=0)
    neg_col = jnp.full((block_h, 1), -1, jnp.int32)
    left = jnp.concatenate([neg_col, lab[:, :-1]], axis=1)
    right = jnp.concatenate([lab[:, 1:], neg_col], axis=1)
    nbrs = (up, down, left, right)
    fields = dict(energy)

    # --- energies per candidate value, `GridMRF`'s operation order -------
    def energies(v, e_max):
        e = mrf_mod.log_potentials(nbrs, ev, v, **fields)
        e_ref[v] = e
        return jnp.maximum(e_max, e)

    e_max = fold_bins(
        n_labels, energies, jnp.full((block_h, width), -jnp.inf, jnp.float32),
        unroll=UNROLL,
    )

    # --- C2: LUT-exp -> int8 weights, one (block_h, W) plane per value -----
    def weights(v, carry):
        m_ref[v] = jnp.maximum(
            jnp.round(interp_eval(e_ref[v] - e_max, tab_ref, x0, inv_dx,
                                  lut_size)),
            0.0,
        ).astype(jnp.int32)
        return carry

    fold_bins(n_labels, weights, (), unroll=UNROLL)

    # --- C1: rejection-KY walk over all sites of the tile ------------------
    m_ext = preprocess_planes(m_ref, precision, unroll=UNROLL)
    label, bits, rejs, done = ddg_walk(
        m_ext, words, n_bins=n_labels, precision=precision,
        total_steps=total_steps, unroll=UNROLL,
    )
    new = argmax_fallback(m_ext, label, done, n_bins=n_labels,
                          unroll=UNROLL)

    # --- checkerboard scatter (only this color updates) --------------------
    gr = phase + jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 0)
    gc = jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 1)
    mask = ((gr + gc) % 2) == 0
    return jnp.where(mask, new, lab)


def _planes(ref) -> list:
    """The (k, rows, W) block as a list of k (rows, W) planes."""
    return [ref[k] for k in range(ref.shape[0])]


def _mrf_kernel(
    phase_ref, up_ref, down_ref, lab_prev_ref, lab_ref, lab_next_ref, ev_ref,
    words_ref, tab_ref, out_ref, e_ref, m_ref, *, n_blocks: int, **kw,
):
    """One tile per grid step.  The grid's (or a sharded slab's) outermost
    halo rows come in as (1, W) inputs — -1 rows on one device, the
    caller's `lax.ppermute` exchange across devices (the C4 mesh-neighbor
    register read) — interior tiles read theirs from the adjacent row
    blocks, and `phase_ref` ((1, 1), traced) holds the first row's global
    index plus the parity, so one compiled kernel serves both colors."""
    i = pl.program_id(0)
    block_h = kw["block_h"]
    lab = lab_ref[...]  # (block_h, W)
    up_halo = jnp.where(
        i > 0, lab_prev_ref[block_h - 1 : block_h, :], up_ref[...]
    )
    down_halo = jnp.where(
        i < n_blocks - 1, lab_next_ref[0:1, :], down_ref[...]
    )
    out_ref[...] = _mrf_tile_body(
        lab, up_halo, down_halo, ev_ref[...], _planes(words_ref), tab_ref,
        e_ref, m_ref, phase_ref[0, 0] + i * block_h, **kw,
    )


def word_planes(words: jax.Array, width: int) -> jax.Array:
    """(H, W * n_words) row-major words -> (n_words, H, W) planes, so the
    kernel reads word k of every site of a tile as one lane-dense plane."""
    height = words.shape[0]
    return words.reshape(height, width, -1).transpose(2, 0, 1)


def word_planes_spec(n_words: int, block_h: int, width: int, vmem):
    """Row block i of every word plane."""
    return pl.BlockSpec((n_words, block_h, width), lambda i: (0, i, 0),
                        memory_space=vmem)


ENERGY_STATICS = ("theta", "h", "data_cost", "pairwise", "truncation")


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_labels", "spec", "precision", "max_retries", "block_h",
        "interpret",
    ) + ENERGY_STATICS,
)
def _half_step(
    phase, up_halo, down_halo, labels, evidence, words, exp_table, *,
    n_labels, spec, precision, max_retries, block_h, interpret, **energy,
):
    """The `pallas_call` both half-step entries share: rows padded to whole
    tiles (labels with `down_halo` then -1, evidence with -1, words with
    0), one grid step per tile, the padding cut off the result.  `phase`
    is traced, so both colors of a sweep share one kernel."""
    height, width = labels.shape
    if block_h is None:
        block_h = tile_rows(height, width, n_labels)
    block_h = min(block_h, round_up(height, SUBLANES))
    rows = round_up(height, block_h)
    n_blocks = rows // block_h
    total_steps = precision * max_retries
    n_words = -(-total_steps // 32)
    if words.shape != (height, width * n_words):
        raise ValueError(
            f"random words shaped {words.shape}, kernel needs "
            f"{(height, width * n_words)}"
        )
    kernel = functools.partial(
        _mrf_kernel, n_blocks=n_blocks, energy=tuple(energy.items()),
        n_labels=n_labels, x0=spec.x0, inv_dx=spec.inv_dx,
        lut_size=spec.size, precision=precision, total_steps=total_steps,
        block_h=block_h, width=width,
    )
    vmem = compat.pallas_vmem()

    def blk(idx_fn):
        return pl.BlockSpec((block_h, width), idx_fn, memory_space=vmem)

    def resident(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                            memory_space=vmem)

    labels = _pad_rows(labels, rows, down_halo)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            resident((1, 1)),  # phase: first global row + parity
            resident((1, width)),  # up halo
            resident((1, width)),  # down halo
            blk(lambda i: (jnp.maximum(i - 1, 0), 0)),  # halo above
            blk(lambda i: (i, 0)),
            blk(lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0)),
            blk(lambda i: (i, 0)),  # evidence
            word_planes_spec(n_words, block_h, width, vmem),
            resident(exp_table.shape),
        ],
        out_specs=blk(lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.int32),
        scratch_shapes=[vmem((n_labels, block_h, width), jnp.float32),
                        vmem((n_labels + 1, block_h, width), jnp.int32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(phase, up_halo, down_halo, labels, labels, labels,
      _pad_rows(evidence, rows, -1),
      word_planes(_pad_rows(words, rows, 0), width), exp_table)
    return out[:height]


def mrf_half_step_kernel(
    labels: jax.Array,
    evidence: jax.Array,
    words: jax.Array,
    exp_table: jax.Array,
    *,
    parity: int,
    theta: float,
    h: float,
    n_labels: int,
    spec: LUTSpec,
    data_cost: str = "potts",
    pairwise: str = "potts",
    truncation: float | None = None,
    precision: int = 16,
    max_retries: int = 8,
    block_h: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """labels, evidence: (H, W) int32 (evidence < 0 where unobserved);
    words: (H, W * n_words) uint32 (row-major (H, W, n_words) flattened);
    exp_table: (1, L) f32 weight table.  `block_h` defaults to
    `tile_rows`; H need not be a multiple of it."""
    edge = jnp.full((1, labels.shape[1]), -1, jnp.int32)
    return _half_step(
        jnp.full((1, 1), parity, jnp.int32), edge, edge, labels, evidence,
        words, exp_table, n_labels=n_labels, spec=spec, precision=precision,
        max_retries=max_retries, block_h=block_h, interpret=interpret,
        theta=theta, h=h, data_cost=data_cost, pairwise=pairwise,
        truncation=truncation,
    )


def kernel_precision(n_labels: int, precision: int = 16) -> int:
    """`draw_from_logits`' widening of the tree for the weight sum bound."""
    return max(precision, 8 + (n_labels - 1).bit_length() + 1)


def mrf_round_step(
    mrf,
    labels: jax.Array,  # (B, H, W) int32
    evidence: jax.Array,  # (H, W) int32
    key: jax.Array,
    parity: int,
    exp_table: jax.Array,
    exp_spec: LUTSpec,
    *,
    precision: int = 16,
    max_retries: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """One schedule round (single checkerboard parity) through the fused
    kernel, vmapped over the chains axis — the `repro.compile.backend`
    entry point for `fused=True` MRF execution.

    Random words come from `ky_core.random_words(key, (B, H, W), n_words)`,
    the same stream `draw_from_logits` consumes for the (B, H, W, V) logits
    of the eager half-step, so lut_ky outputs are bit-identical to
    `mrf.half_step` under the same key."""
    b, height, width = labels.shape
    precision = kernel_precision(mrf.n_labels, precision)
    n_words = -(-precision * max_retries // 32)
    words = ky_core.random_words(key, (b, height, width), n_words)
    tab = jnp.reshape(exp_table, (1, -1)).astype(jnp.float32)
    step = functools.partial(
        mrf_half_step_kernel, parity=parity, n_labels=mrf.n_labels,
        spec=exp_spec, precision=precision, max_retries=max_retries,
        interpret=interpret, **mrf_mod.energy_fields(mrf),
    )
    return jax.vmap(
        lambda lab, wds: step(lab, evidence, wds.reshape(height, -1), tab)
    )(labels, words)


def mrf_halo_half_step_kernel(
    labels: jax.Array,
    up_halo: jax.Array,
    down_halo: jax.Array,
    row0: jax.Array,
    evidence: jax.Array,
    words: jax.Array,
    exp_table: jax.Array,
    *,
    parity: int,
    theta: float,
    h: float,
    n_labels: int,
    spec: LUTSpec,
    data_cost: str = "potts",
    pairwise: str = "potts",
    truncation: float | None = None,
    precision: int = 16,
    max_retries: int = 8,
    block_h: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """`mrf_half_step_kernel` over a local row *slab* of a sharded grid:
    labels/evidence/words cover the (h_loc, W) slab only, `up_halo` /
    `down_halo` ((1, W) int32; -1 beyond the global boundary) are the
    neighbor shards' border rows, and `row0` ((1, 1) int32, traced) is the
    slab's global row offset for the checkerboard parity.  A slab padded
    to whole tiles takes `down_halo` as its first padded row, so its last
    real row still sees the neighbor shard."""
    return _half_step(
        row0 + parity, up_halo, down_halo, labels, evidence, words,
        exp_table, n_labels=n_labels, spec=spec, precision=precision,
        max_retries=max_retries, block_h=block_h, interpret=interpret,
        theta=theta, h=h, data_cost=data_cost, pairwise=pairwise,
        truncation=truncation,
    )


def mrf_sharded_round_step(
    mrf,
    labels: jax.Array,  # (B_loc, h_loc, W) int32 local row slab
    evidence: jax.Array,  # (h_loc, W) int32 local evidence rows
    key: jax.Array,
    parity: int,
    exp_table: jax.Array,
    exp_spec: LUTSpec,
    *,
    row0: jax.Array,  # () int32, traced: global row of labels[:, 0]
    chain0: jax.Array,  # () int32, traced: global index of chain 0
    n_chains_total: int,
    up_halo: jax.Array,  # (B_loc, 1, W) int32 neighbor-shard border rows
    down_halo: jax.Array,
    precision: int = 16,
    max_retries: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """One schedule round on a sharded row slab — `mrf_round_step` inside a
    `shard_map` body.  The random stream is generated over the FULL grid
    (and full chain batch) on every device and sliced to the local slab, so
    each site consumes exactly the words the single-device fused round
    would hand it: outputs are bit-identical shard-count-independently.
    Halo rows come from the caller's `lax.ppermute` exchange (the
    `ppermute_halo` comm mechanism)."""
    b_loc, h_loc, width = labels.shape
    height = mrf.height
    precision = kernel_precision(mrf.n_labels, precision)
    n_words = -(-precision * max_retries // 32)
    words = ky_core.random_words(
        key, (n_chains_total, height, width), n_words
    )
    words = jax.lax.dynamic_slice(
        words, (chain0, row0, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32)),
        (b_loc, h_loc, width, n_words),
    )
    tab = jnp.reshape(exp_table, (1, -1)).astype(jnp.float32)
    row0_arr = jnp.reshape(row0, (1, 1)).astype(jnp.int32)
    step = functools.partial(
        mrf_halo_half_step_kernel, parity=parity, n_labels=mrf.n_labels,
        spec=exp_spec, precision=precision, max_retries=max_retries,
        interpret=interpret, **mrf_mod.energy_fields(mrf),
    )
    return jax.vmap(
        lambda lab, uh, dh, wds: step(
            lab, uh, dh, row0_arr, evidence, wds.reshape(h_loc, -1), tab
        )
    )(labels, up_halo, down_halo, words)
