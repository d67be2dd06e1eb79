"""Fused Pallas kernel: one checkerboard Gibbs half-step on a grid MRF.

This is the AIA inner loop (paper Sec. III "Approximate Inference Overview")
as a single VMEM-resident pipeline, fusing all four innovations:

  1. neighbor-label exchange (C4)  — halo rows come from the adjacent row
     blocks (BlockSpec index maps i-1 / i / i+1), the intra-tile shifts are
     VMEM slices; across devices, distributed.py replaces the halo load
     with a `ppermute` — the mesh-neighbor register read, ICI-native;
  2. energy computation (programmable ALU) — Potts smoothness + data cost;
  3. LUT-exp via the interpolation unit (C2) — `interp_eval`, int8 weights;
  4. rejection-KY draw (C1) — `ddg_walk` over one plane per label value.

The conditional distribution of every site is produced, sampled and
discarded inside the tile — zero HBM round-trips for intermediates, the
paper's private-RF locality argument. Bit-exact against ref.mrf_gibbs_half_step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import ky as ky_core
from repro.core.interp import LUTSpec
from repro.kernels.interp_lut import interp_eval
from repro.kernels.ky_sampler import LANES, argmax_fallback, ddg_walk, \
    preprocess_planes

pl = compat.pallas()

DEFAULT_BLOCK_H = 32


def _mrf_tile_body(
    lab, up_halo, down_halo, ev, words, tab_ref, gr0,
    *, parity: int, theta: float, h: float, n_labels: int, data_cost: str,
    x0: float, inv_dx: float, lut_size: int, precision: int, total_steps: int,
    block_h: int, width: int,
):
    """The fused half-step pipeline on one (block_h, W) tile: energies ->
    LUT-exp -> KY walk -> checkerboard scatter.  `up_halo`/`down_halo` are
    the tile's boundary neighbor rows ((1, W); -1 where the grid ends) and
    `gr0` the tile's global row offset — the single-device and sharded-slab
    kernels differ only in how they produce these three, so sharing the
    body keeps the two datapaths bit-identical by construction."""
    up = jnp.concatenate([up_halo, lab[:-1, :]], axis=0)
    down = jnp.concatenate([lab[1:, :], down_halo], axis=0)
    neg_col = jnp.full((block_h, 1), -1, jnp.int32)
    left = jnp.concatenate([neg_col, lab[:, :-1]], axis=1)
    right = jnp.concatenate([lab[:, 1:], neg_col], axis=1)

    # --- energies per candidate value, same op order as the ref oracle -----
    e_max = jnp.full((block_h, width), -jnp.inf, jnp.float32)
    energies = []
    for v in range(n_labels):
        cnt = (
            ((up == v).astype(jnp.float32) + (down == v).astype(jnp.float32))
            + (left == v).astype(jnp.float32)
        ) + (right == v).astype(jnp.float32)
        if data_cost == "potts":
            data = h * (ev == v).astype(jnp.float32)
        else:
            diff = (ev - v).astype(jnp.float32)
            data = -h * diff * diff
        e = theta * cnt + data
        energies.append(e)
        e_max = jnp.maximum(e_max, e)

    # --- C2: LUT-exp -> int8 weights, one (block_h, W) plane per value -----
    w = [
        jnp.maximum(
            jnp.round(interp_eval(e - e_max, tab_ref, x0, inv_dx, lut_size)),
            0.0,
        ).astype(jnp.int32)
        for e in energies
    ]

    # --- C1: rejection-KY walk over all sites of the tile ------------------
    label, bits, rejs, done = ddg_walk(
        preprocess_planes(w, precision), words, n_bins=n_labels,
        precision=precision, total_steps=total_steps,
    )
    new = argmax_fallback(w, label, done)

    # --- checkerboard scatter (only this color updates) --------------------
    gr = gr0 + jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 0)
    gc = jax.lax.broadcasted_iota(jnp.int32, (block_h, width), 1)
    mask = ((gr + gc) % 2) == parity
    return jnp.where(mask, new, lab)


def _planes(ref) -> list:
    """The (k, rows, W) block as a list of k (rows, W) planes."""
    return [ref[k] for k in range(ref.shape[0])]


def _mrf_kernel(
    lab_prev_ref, lab_ref, lab_next_ref, ev_ref, words_ref, tab_ref, out_ref,
    *, parity: int, theta: float, h: float, n_labels: int, data_cost: str,
    x0: float, inv_dx: float, lut_size: int, precision: int, total_steps: int,
    block_h: int, n_blocks: int, width: int,
):
    i = pl.program_id(0)
    lab = lab_ref[...]  # (block_h, W)
    neg = jnp.full((1, width), -1, jnp.int32)

    # --- C4: neighbor labels; halo rows from adjacent blocks ---------------
    up_halo = jnp.where(i > 0, lab_prev_ref[block_h - 1 : block_h, :], neg)
    down_halo = jnp.where(i < n_blocks - 1, lab_next_ref[0:1, :], neg)
    out_ref[...] = _mrf_tile_body(
        lab, up_halo, down_halo, ev_ref[...], _planes(words_ref), tab_ref,
        i * block_h, parity=parity, theta=theta, h=h, n_labels=n_labels,
        data_cost=data_cost, x0=x0, inv_dx=inv_dx, lut_size=lut_size,
        precision=precision, total_steps=total_steps, block_h=block_h,
        width=width,
    )


def _mrf_halo_kernel(
    off_ref, up_ref, down_ref, lab_prev_ref, lab_ref, lab_next_ref, ev_ref,
    words_ref, tab_ref, out_ref,
    *, parity: int, theta: float, h: float, n_labels: int, data_cost: str,
    x0: float, inv_dx: float, lut_size: int, precision: int, total_steps: int,
    block_h: int, n_blocks: int, width: int,
):
    """The sharded-slab variant: the slab's outermost halo rows come in as
    explicit (1, W) inputs (the caller's `lax.ppermute` exchange — the C4
    mesh-neighbor register read), interior tiles still read them from the
    adjacent row blocks, and the checkerboard parity is computed against
    the slab's global row offset (`off_ref`, a traced (1, 1) scalar)."""
    i = pl.program_id(0)
    lab = lab_ref[...]  # (block_h, W)
    up_halo = jnp.where(
        i > 0, lab_prev_ref[block_h - 1 : block_h, :], up_ref[...]
    )
    down_halo = jnp.where(
        i < n_blocks - 1, lab_next_ref[0:1, :], down_ref[...]
    )
    out_ref[...] = _mrf_tile_body(
        lab, up_halo, down_halo, ev_ref[...], _planes(words_ref), tab_ref,
        off_ref[0, 0] + i * block_h, parity=parity, theta=theta, h=h,
        n_labels=n_labels, data_cost=data_cost, x0=x0, inv_dx=inv_dx,
        lut_size=lut_size, precision=precision, total_steps=total_steps,
        block_h=block_h, width=width,
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


@functools.partial(
    jax.jit,
    static_argnames=(
        "parity", "theta", "h", "n_labels", "data_cost", "spec",
        "precision", "max_retries", "block_h", "interpret",
    ),
)
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
    precision: int = 16,
    max_retries: int = 8,
    block_h: int = DEFAULT_BLOCK_H,
    interpret: bool = False,
) -> jax.Array:
    """labels, evidence: (H, W) int32; words: (H, W * n_words) uint32 (row-
    major (H, W, n_words) flattened); exp_table: (1, L) f32 weight table."""
    height, width = labels.shape
    # raised, not asserted: shape gates must hold under `python -O` too
    if n_labels >= LANES:
        raise ValueError(f"n_labels {n_labels} >= {LANES} KY lanes")
    block_h = min(block_h, height)
    if height % block_h != 0:
        raise ValueError(
            f"height {height} not a multiple of block_h {block_h}; pad H"
        )
    n_blocks = height // block_h
    total_steps = precision * max_retries
    want_words = (height, width * (-(-total_steps // 32)))
    if words.shape != want_words:
        raise ValueError(
            f"random words shaped {words.shape}, kernel needs {want_words}"
        )

    kernel = functools.partial(
        _mrf_kernel, parity=parity, theta=theta, h=h, n_labels=n_labels,
        data_cost=data_cost, x0=spec.x0, inv_dx=spec.inv_dx, lut_size=spec.size,
        precision=precision, total_steps=total_steps, block_h=block_h,
        n_blocks=n_blocks, width=width,
    )

    vmem = compat.pallas_vmem()

    def blk(idx_fn, cols):
        return pl.BlockSpec((block_h, cols), idx_fn, memory_space=vmem)

    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            blk(lambda i: (jnp.maximum(i - 1, 0), 0), width),  # halo above
            blk(lambda i: (i, 0), width),
            blk(lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0), width),
            blk(lambda i: (i, 0), width),  # evidence
            word_planes_spec(want_words[1] // width, block_h, width, vmem),
            pl.BlockSpec((1, exp_table.shape[1]), lambda i: (0, 0),
                         memory_space=vmem),
        ],
        out_specs=blk(lambda i: (i, 0), width),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(labels, labels, labels, evidence, word_planes(words, width), exp_table)


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
    # match draw_from_logits' precision widening for the weight sum bound
    precision = max(precision, 8 + (mrf.n_labels - 1).bit_length() + 1)
    n_words = -(-precision * max_retries // 32)
    words = ky_core.random_words(key, (b, height, width), n_words)
    tab = jnp.reshape(exp_table, (1, -1)).astype(jnp.float32)
    # largest divisor of H that fits the default tile (the kernel requires
    # H % block_h == 0)
    block_h = next(
        bh for bh in range(min(DEFAULT_BLOCK_H, height), 0, -1)
        if height % bh == 0
    )
    step = functools.partial(
        mrf_half_step_kernel,
        parity=parity, theta=mrf.theta, h=mrf.h, n_labels=mrf.n_labels,
        spec=exp_spec, data_cost=mrf.data_cost, precision=precision,
        max_retries=max_retries, block_h=block_h, interpret=interpret,
    )
    return jax.vmap(
        lambda lab, wds: step(lab, evidence, wds.reshape(height, -1), tab)
    )(labels, words)


@functools.partial(
    jax.jit,
    static_argnames=(
        "parity", "theta", "h", "n_labels", "data_cost", "spec",
        "precision", "max_retries", "block_h", "interpret",
    ),
)
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
    precision: int = 16,
    max_retries: int = 8,
    block_h: int = DEFAULT_BLOCK_H,
    interpret: bool = False,
) -> jax.Array:
    """`mrf_half_step_kernel` over a local row *slab* of a sharded grid:
    labels/evidence/words cover the (h_loc, W) slab only, `up_halo` /
    `down_halo` ((1, W) int32; -1 beyond the global boundary) are the
    neighbor shards' border rows, and `row0` ((1, 1) int32, traced) is the
    slab's global row offset for the checkerboard parity."""
    height, width = labels.shape
    if n_labels >= LANES:
        raise ValueError(f"n_labels {n_labels} >= {LANES} KY lanes")
    block_h = min(block_h, height)
    if height % block_h != 0:
        raise ValueError(
            f"slab height {height} not a multiple of block_h {block_h}"
        )
    n_blocks = height // block_h
    total_steps = precision * max_retries
    want_words = (height, width * (-(-total_steps // 32)))
    if words.shape != want_words:
        raise ValueError(
            f"random words shaped {words.shape}, kernel needs {want_words}"
        )

    kernel = functools.partial(
        _mrf_halo_kernel, parity=parity, theta=theta, h=h, n_labels=n_labels,
        data_cost=data_cost, x0=spec.x0, inv_dx=spec.inv_dx, lut_size=spec.size,
        precision=precision, total_steps=total_steps, block_h=block_h,
        n_blocks=n_blocks, width=width,
    )

    vmem = compat.pallas_vmem()

    def blk(idx_fn, cols):
        return pl.BlockSpec((block_h, cols), idx_fn, memory_space=vmem)

    def resident(rows, cols):
        return pl.BlockSpec((rows, cols), lambda i: (0, 0),
                            memory_space=vmem)

    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            resident(1, 1),  # global row offset of the slab
            resident(1, width),  # up halo from the mesh neighbor
            resident(1, width),  # down halo from the mesh neighbor
            blk(lambda i: (jnp.maximum(i - 1, 0), 0), width),  # halo above
            blk(lambda i: (i, 0), width),
            blk(lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0), width),
            blk(lambda i: (i, 0), width),  # evidence
            word_planes_spec(want_words[1] // width, block_h, width, vmem),
            pl.BlockSpec((1, exp_table.shape[1]), lambda i: (0, 0),
                         memory_space=vmem),
        ],
        out_specs=blk(lambda i: (i, 0), width),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(row0, up_halo, down_halo, labels, labels, labels, evidence,
      word_planes(words, width), exp_table)


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
    # match draw_from_logits' precision widening for the weight sum bound
    precision = max(precision, 8 + (mrf.n_labels - 1).bit_length() + 1)
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
    block_h = next(
        bh for bh in range(min(DEFAULT_BLOCK_H, h_loc), 0, -1)
        if h_loc % bh == 0
    )
    row0_arr = jnp.reshape(row0, (1, 1)).astype(jnp.int32)
    step = functools.partial(
        mrf_halo_half_step_kernel,
        parity=parity, theta=mrf.theta, h=mrf.h, n_labels=mrf.n_labels,
        spec=exp_spec, data_cost=mrf.data_cost, precision=precision,
        max_retries=max_retries, block_h=block_h, interpret=interpret,
    )
    return jax.vmap(
        lambda lab, uh, dh, wds: step(
            lab, uh, dh, row0_arr, evidence, wds.reshape(h_loc, -1), tab
        )
    )(labels, up_halo, down_halo, words)
