"""Fused Pallas kernel: one full Bayes-net color round per grid step.

This is the paper's fused C1+C2 datapath on its headline workload: where
the unfused BN engine runs each color round as ~6 separate XLA kernels —
`group_log_conditionals` materializes a (B, n_c, F, V) address/log-prob
tensor in HBM, `draw_from_logits` re-reads it, a scatter writes the state —
this kernel executes the whole round on VMEM-resident state:

  1. flat-CPT gather              — addresses computed in-kernel from the
     (base, stride, scope_var) tables against the log-CPT arena, reading
     the chain values straight out of the resident value block (the
     paper's shared-RF access, C4-adjacent);
  2. LUT-exp weight interpolation — `interp_eval` on the same (1, L) table
     layout as the MRF kernel (C2; exact_ky runs the exact-exp ablation);
  3. non-normalized rejection-KY  — the early-exit `ddg_walk` from
     `ky_sampler.py` over all (chain, node) sites of the round at once (C1);
  4. in-place scatter             — each node lane of the value block reads
     its drawn label back through the round's inverse node map.

Layout: chains on sublanes, a round's nodes on lanes (padded to whole
128-lane tiles), one plane per candidate value — the (chain, node) sites of
a round are one lane-dense tile, which is what Mosaic lowers.  Every gather
(chain values at scope slots, the CPT arena at computed addresses, labels
back into the value block) is a `take_along_axis` within 128-lane tiles,
selected across tiles by the index's high bits.

The grid iterates over schedule rounds ("arbitrary" semantics); the value
block's index map is constant, so the chain state stays in VMEM across the
*entire sweep* and is written back to HBM once — zero HBM round-trips for
the per-round conditionals, the paper's private-RF locality argument.

Random words are derived exactly as `draw_from_logits` derives them (one
`ky_core.random_words` stream per round over the round's *real* row count),
and every float expression (factor sum order, LUT lerp) is the unfused
path's, so lut_ky/exact_ky outputs are bit-identical to the unfused
`gibbs_sweep` under the same key — asserted by `tests/test_bn_fused.py` and
by the backend's first-use cross-check (`compile/backend.cross_check_fused`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core import ky as ky_core
from repro.core.bayesnet import NEG_INF, CompiledBayesNet
from repro.kernels.interp_lut import interp_eval
from repro.kernels.ky_sampler import LANES, argmax_fallback, ddg_walk, \
    preprocess_planes, zeros_plane

pl = compat.pallas()

# The samplers whose draw pipeline this kernel implements; anything else
# must be rejected loudly by the callers (never silently fall back).
FUSED_BN_SAMPLERS = ("lut_ky", "exact_ky")

# The kernel's custom-call name in compiled HLO and in device traces,
# stable across edits to the surrounding program.
KERNEL_NAME = "bn_gibbs_kernel"


def check_fused_sampler(sampler: str) -> None:
    """The fused-BN sampler gate, shared by every entry layer (program.run,
    the backend wrappers, the run loop, the kernel itself): cdf/gumbel draw
    from a different random stream entirely, so a silent fallback would
    change which engine served without anyone noticing."""
    if sampler not in FUSED_BN_SAMPLERS:
        raise ValueError(
            f"fused BN rounds implement the {'/'.join(FUSED_BN_SAMPLERS)} "
            f"datapaths only, got sampler={sampler!r}"
        )


@dataclasses.dataclass
class BNFusedRounds:
    """A round-group list in the kernel's planar layout, stacked on a
    leading rounds axis so one `pallas_call` grid step reads round r.

    Nodes sit on lanes, padded to `c_pad` (whole 128-lane tiles); factor
    slot f is row f of `base` and (f, s) is row f * s_max + s of the scope
    tables.  Padding reuses the dummy-slot convention of
    `bayesnet.build_color_group`: base/stride/scope 0 slots address the
    arena's zero entry and add log-prob 0.0, pad lanes have node id -1 and
    card 0."""

    nodes: jax.Array  # (R, 1, c_pad) int32; -1 = padded lane
    cards: jax.Array  # (R, 1, c_pad) int32; 0 = padded lane
    base: jax.Array  # (R, F, c_pad) int32
    stride: jax.Array  # (R, F*S, c_pad) int32
    scope_var: jax.Array  # (R, F*S, c_pad) int32
    is_self: jax.Array  # (R, F*S, c_pad) int32 (0/1)
    n_c: tuple[int, ...]  # static: real node count per round
    c_max: int
    f_max: int
    s_max: int


jax.tree_util.register_dataclass(
    BNFusedRounds,
    ["nodes", "cards", "base", "stride", "scope_var", "is_self"],
    ["n_c", "c_max", "f_max", "s_max"],
)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def round_layout(nodes, cards, base, stride, scope_var, is_self, *,
                 c_pad: int, f_max: int, s_max: int):
    """One round's (C,)/(C, F)/(C, F, S) gather tensors -> the planar
    tables of `BNFusedRounds` (without the rounds axis).  Pure jnp, so it
    runs at trace time."""
    c, f = base.shape
    s = stride.shape[2]

    def planes(x):  # (C, F, S) -> (F*S, c_pad)
        x = jnp.pad(x.astype(jnp.int32),
                    ((0, c_pad - c), (0, f_max - f), (0, s_max - s)))
        return x.reshape(c_pad, f_max * s_max).T

    return dict(
        nodes=jnp.pad(nodes.astype(jnp.int32), (0, c_pad - c),
                      constant_values=-1)[None],
        cards=jnp.pad(cards.astype(jnp.int32), (0, c_pad - c))[None],
        base=jnp.pad(base.astype(jnp.int32),
                     ((0, c_pad - c), (0, f_max - f))).T,
        stride=planes(stride),
        scope_var=planes(scope_var),
        is_self=planes(is_self),
    )


def inverse_map(nodes: jax.Array, n_nodes: int) -> jax.Array:
    """(R, 1, c_pad) round node ids -> (R, 1, n_pad): the lane holding node
    j in round r, -1 where round r does not update j (ids outside
    [0, n_nodes) are pad slots)."""
    lanes = jnp.arange(_round_up(n_nodes, LANES), dtype=jnp.int32)
    ids = nodes[:, 0, :, None]  # (R, c_pad, 1)
    hit = (ids == lanes) & (ids < n_nodes)
    pos = jnp.arange(nodes.shape[-1], dtype=jnp.int32)[:, None]
    return jnp.max(jnp.where(hit, pos, -1), axis=1)[:, None, :]


def build_fused_rounds(groups) -> BNFusedRounds:
    """Stack a `ColorGroup` list into the fused kernel's planar layout.

    Pure jnp (shapes are static), so it runs at trace time inside the
    jitted run loops — the fused tensors are a deterministic function of
    the groups pytree and never need a separate compile-time artifact."""
    c_max = max(g.nodes.shape[0] for g in groups)
    f_max = max(g.base.shape[1] for g in groups)
    s_max = max(g.stride.shape[2] for g in groups)
    c_pad = _round_up(c_max, LANES)
    per_round = [
        round_layout(g.nodes, g.cards, g.base, g.stride, g.scope_var,
                     g.is_self, c_pad=c_pad, f_max=f_max, s_max=s_max)
        for g in groups
    ]
    stacked = {k: jnp.stack([t[k] for t in per_round]) for k in per_round[0]}
    return BNFusedRounds(
        **stacked,
        n_c=tuple(int(g.nodes.shape[0]) for g in groups),
        c_max=c_max, f_max=f_max, s_max=s_max,
    )


def _take_tiles(src: jax.Array, lo: jax.Array) -> jax.Array:
    """src (B, 128) gathered at every 128-lane tile of lo (B, 128 m)."""
    tiles = [jnp.take_along_axis(src, lo[:, j:j + LANES], axis=1)
             for j in range(0, lo.shape[1], LANES)]
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _lane_gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """out[b, l] = src[b, idx[b, l]] for src (B, 128 k) and idx in
    [0, 128 k): Mosaic gathers within one 128-lane tile, so each source
    tile is gathered and picked where the index's high bits name it."""
    hi, lo = idx >> 7, idx & (LANES - 1)
    acc = _take_tiles(src[:, :LANES], lo)
    for k in range(1, src.shape[1] // LANES):
        got = _take_tiles(src[:, k * LANES:(k + 1) * LANES], lo)
        acc = jnp.where(hi == k, got, acc)
    return acc


def _arena_gather(logf_ref, addr: jax.Array) -> jax.Array:
    """logf[addr] from the (rows, 128) log-CPT arena: `_lane_gather` with
    the source tiles looped over (one arena row per iteration, broadcast
    over the chains) instead of unrolled.  Addresses past the arena read
    0.0 (only candidate values beyond a node's card form them, and those
    are masked)."""
    b = addr.shape[0]
    hi, lo = addr >> 7, addr & (LANES - 1)

    def body(k, acc):
        row = jnp.broadcast_to(logf_ref[pl.ds(k, 1), :], (b, LANES))
        return jnp.where(hi == k, _take_tiles(row, lo), acc)

    acc0 = zeros_plane(addr.shape, jnp.float32)
    return jax.lax.fori_loop(0, logf_ref.shape[0], body, acc0)


def _broadcast_rows(ref, i: int, b: int) -> jax.Array:
    """Row i of a (rows, 128 k) ref broadcast to (b, 128 k), one 128-lane
    tile at a time: Mosaic cannot broadcast a row slice that starts at a
    nonzero lane tile, which is what slicing a whole broadcast row becomes."""
    return jnp.concatenate(
        [jnp.broadcast_to(ref[i:i + 1, j:j + LANES], (b, LANES))
         for j in range(0, ref.shape[-1], LANES)], axis=1,
    )


def bn_round_step(
    vals_ref, cards_ref, base_ref, stride_ref, scope_ref, self_ref, inv_ref,
    words_ref, logf_ref, tab_ref, out_ref, *,
    f_max: int, s_max: int, v_max: int, sampler: str, x0: float,
    inv_dx: float, lut_size: int, weight_bits: int, precision: int,
    total_steps: int,
):
    """One full color round on the VMEM-resident value block (grid step r).

    The op order mirrors `group_log_conditionals` + `draw_from_logits`
    exactly — same gather addresses, same factor summation order, same
    float expressions — which is what makes the fused path bit-exact rather
    than merely statistically equivalent."""
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _():
        out_ref[...] = vals_ref[...]

    vals = out_ref[...]  # (B, n_pad) chain state, resident across rounds
    b = vals.shape[0]
    c_pad = cards_ref.shape[-1]

    def row(ref, i):  # table row i broadcast over the chains
        return _broadcast_rows(ref, i, b)

    # --- chain values at every scope slot (C4-adjacent shared-RF read) ----
    sv = [[_lane_gather(vals, row(scope_ref, f * s_max + s))
           for s in range(s_max)] for f in range(f_max)]

    # --- flat-CPT gather + factor sum per candidate value ------------------
    logp = []
    for v in range(v_max):
        acc = None
        for f in range(f_max):
            addr = row(base_ref, f)
            for s in range(s_max):
                k = f * s_max + s
                val = jnp.where(row(self_ref, k) != 0, v, sv[f][s])
                addr = addr + row(stride_ref, k) * val
            term = _arena_gather(logf_ref, addr)
            acc = term if acc is None else acc + term
        logp.append(jnp.where(v < row(cards_ref, 0), acc, NEG_INF))

    # --- C2: LUT-exp (or exact-exp ablation) -> integer weights -----------
    top = logp[0]
    for x in logp[1:]:
        top = jnp.maximum(top, x)
    z = [x - top for x in logp]
    if sampler == "lut_ky":
        w = [jnp.maximum(jnp.round(interp_eval(x, tab_ref, x0, inv_dx,
                                               lut_size)), 0.0)
             .astype(jnp.int32) for x in z]
    else:  # exact_ky — the exact-exp ablation, as ky_core.quantize_probs
        p = [jnp.exp(x) for x in z]
        pmax = p[0]
        for x in p[1:]:
            pmax = jnp.maximum(pmax, x)
        wmax = (1 << weight_bits) - 1
        scale = wmax / jnp.maximum(pmax, 1e-30)
        w = [jnp.clip(jnp.round(x * scale), 0, wmax).astype(jnp.int32)
             for x in p]

    # --- C1: early-exit rejection-KY walk over every (chain, node) site ---
    words = [words_ref[k] for k in range(words_ref.shape[0])]
    label, _, _, done = ddg_walk(
        preprocess_planes(w, precision), words, n_bins=v_max,
        precision=precision, total_steps=total_steps,
    )
    labels = argmax_fallback(w, label, done, n_bins=v_max)  # (B, c_pad)

    # --- in-place scatter: node lanes read their label via the inverse map
    inv = _broadcast_rows(inv_ref, 0, b)
    out_ref[...] = jnp.where(
        inv >= 0, _lane_gather(labels, jnp.maximum(inv, 0)), vals
    )


def draw_params(v_max: int, sampler: str, precision: int = 16,
                max_retries: int = 8) -> tuple[int, int, int, int]:
    """(weight_bits, precision, total_steps, n_words) for a fused draw,
    widened exactly as `draw_from_logits` widens them."""
    weight_bits = 8 if sampler == "lut_ky" else 15
    precision = max(precision, weight_bits + (v_max - 1).bit_length() + 1)
    total_steps = precision * max_retries
    return weight_bits, precision, total_steps, -(-total_steps // 32)


def fused_round_words(
    fr: BNFusedRounds, key: jax.Array, n_chains: int, n_words: int,
    b_pad: int,
) -> jax.Array:
    """Per-round packed random words in the kernel's plane layout.

    Round r's stream is `ky_core.random_words(keys[r], (B * n_c_r,), W)` —
    byte-for-byte what `draw_from_logits` would draw for that round's
    (B, n_c_r, V) logits — reshaped to (B, n_c_r, W), padded to
    (b_pad, c_pad) sites (pad sites read zero bits; their draws are
    discarded) and laid out as W word planes: (R, W, b_pad, c_pad)."""
    keys = jax.random.split(key, len(fr.n_c))
    c_pad = fr.cards.shape[-1]
    out = []
    for r, nc in enumerate(fr.n_c):
        wr = ky_core.random_words(keys[r], (n_chains * nc,), n_words)
        out.append(word_planes(wr.reshape(n_chains, nc, n_words), b_pad,
                               c_pad))
    return jnp.stack(out)


def word_planes(words: jax.Array, b_pad: int, c_pad: int) -> jax.Array:
    """(B, C, W) words -> (W, b_pad, c_pad) zero-padded word planes."""
    b, c, _ = words.shape
    words = jnp.pad(words, ((0, b_pad - b), (0, c_pad - c), (0, 0)))
    return words.transpose(2, 0, 1)


def _fused_rounds_call(
    vals, fr: BNFusedRounds, words, log_flat, exp_table, *, sampler: str,
    exp_spec, v_max: int, weight_bits: int, precision: int,
    total_steps: int, interpret: bool,
) -> jax.Array:
    """Every round of `fr` on the resident value block, one grid step per
    round.  vals (B, n) -> (B, n); words (R, W, b_pad, c_pad)."""
    b, n = vals.shape
    n_rounds, n_words, b_pad, c_pad = words.shape
    n_pad = _round_up(n, LANES)
    vals_p = jnp.pad(vals, ((0, b_pad - b), (0, n_pad - n)))
    arena = jnp.pad(jnp.ravel(log_flat),
                    (0, _round_up(log_flat.size, LANES) - log_flat.size))
    arena = arena.reshape(-1, LANES)
    tab = jnp.reshape(exp_table, (1, -1)).astype(jnp.float32)

    kernel = functools.partial(
        bn_round_step, f_max=fr.f_max, s_max=fr.s_max, v_max=v_max,
        sampler=sampler, x0=exp_spec.x0, inv_dx=exp_spec.inv_dx,
        lut_size=exp_spec.size, weight_bits=weight_bits,
        precision=precision, total_steps=total_steps,
    )
    vmem = compat.pallas_vmem()

    def per_round(x):  # round r's slice of a rounds-stacked table
        tail = x.shape[1:]
        return pl.BlockSpec((None,) + tail,
                            lambda i: (i,) + (0,) * len(tail),
                            memory_space=vmem)

    def resident(x):
        return pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim,
                            memory_space=vmem)

    tables = (fr.cards, fr.base, fr.stride, fr.scope_var, fr.is_self,
              inverse_map(fr.nodes, n))
    out = pl.pallas_call(
        kernel,
        grid=(n_rounds,),
        in_specs=[resident(vals_p)]  # initial chain values (step 0 only)
        + [per_round(t) for t in tables]
        + [per_round(words), resident(arena), resident(tab)],
        out_specs=resident(vals_p),
        out_shape=jax.ShapeDtypeStruct(vals_p.shape, jnp.int32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(vals_p, *tables, words, arena, tab)
    return out[:b, :n]


def fused_gibbs_sweep(
    cbn: CompiledBayesNet,
    fr: BNFusedRounds,
    vals: jax.Array,
    key: jax.Array,
    sampler: str = "lut_ky",
    *,
    precision: int = 16,
    max_retries: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for `bayesnet.gibbs_sweep` on the fused samplers: one
    pallas_call executes every round of the sweep with the chain values
    VMEM-resident throughout, bit-exact with the unfused sweep.

    Raises on samplers outside `FUSED_BN_SAMPLERS` (`check_fused_sampler`)
    — never a silent fallback."""
    check_fused_sampler(sampler)
    b = vals.shape[0]
    v = cbn.max_card
    if v >= LANES:  # raised, not asserted: must hold under `python -O`
        raise ValueError(
            f"max_card {v} >= {LANES} KY lanes; pad wider alphabets "
            "hierarchically (token_sampler)"
        )
    weight_bits, precision, total_steps, n_words = draw_params(
        v, sampler, precision, max_retries
    )
    words = fused_round_words(fr, key, b, n_words, _round_up(b, 8))
    return _fused_rounds_call(
        vals, fr, words, cbn.log_flat, cbn.exp_table, sampler=sampler,
        exp_spec=cbn.exp_spec, v_max=v, weight_bits=weight_bits,
        precision=precision, total_steps=total_steps, interpret=interpret,
    )


def fused_color_round(
    vals: jax.Array,  # (B, n) chain values
    nodes: jax.Array,  # (C,) local node ids; id >= n marks a pad slot
    cards: jax.Array,  # (C,) cards; 0 = pad
    base: jax.Array,  # (C, F)
    stride: jax.Array,  # (C, F, S)
    scope_var: jax.Array,
    is_self: jax.Array,
    words: jax.Array,  # (B, C, n_words) uint32
    log_flat: jax.Array,  # log-CPT arena, any shape
    exp_table: jax.Array,  # exp-weight LUT, any shape
    *,
    sampler: str,
    exp_spec,
    v_max: int,
    weight_bits: int,
    precision: int,
    total_steps: int,
    interpret: bool = False,
) -> jax.Array:
    """One fused color round as a standalone grid=(1,) `pallas_call`.

    The sharded engine (`core/distributed.py`) cannot place `lax`
    collectives inside a kernel, so its one-shard_map-body route runs one
    `bn_round_step` per schedule round with the `psum_broadcast` merge in
    between.  Reusing the exact sweep kernel (its r==0 branch seeds the
    resident value block from `vals`) keeps the per-round datapath — and
    therefore every draw — bit-identical to `fused_gibbs_sweep`'s grid
    steps; only how halo state moves differs."""
    check_fused_sampler(sampler)
    b = vals.shape[0]
    c, f_max = base.shape
    s_max = stride.shape[2]
    c_pad = _round_up(c, LANES)
    tables = round_layout(nodes, cards, base, stride, scope_var, is_self,
                          c_pad=c_pad, f_max=f_max, s_max=s_max)
    fr = BNFusedRounds(
        **{k: x[None] for k, x in tables.items()},
        n_c=(c,), c_max=c, f_max=f_max, s_max=s_max,
    )
    planes = word_planes(words, _round_up(b, 8), c_pad)[None]
    return _fused_rounds_call(
        vals, fr, planes, log_flat, exp_table, sampler=sampler,
        exp_spec=exp_spec, v_max=v_max, weight_bits=weight_bits,
        precision=precision, total_steps=total_steps, interpret=interpret,
    )
