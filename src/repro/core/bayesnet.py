"""Chromatic parallel Gibbs sampling for Bayes nets (paper Alg. 2 + Sec. IV).

This is the software half of AIA: the "compiler" lowers an irregular DAG into
dense, padded per-color update tensors (the analogue of mapping RVs onto the
4x4 core mesh), and the jitted engine executes one color at a time:

  compile time (numpy)                      run time (jit, per color)
  ----------------------------------------  -------------------------------
  moral graph -> DSATUR colors (C3)         gather CPT entries for all
  per node: Markov-blanket factor list        (chain, node, factor, value)
  factor -> (base, stride, scope) tensors     in one vectorized address calc
  pad to (n_c, F, S) per color              logp -> LUT-exp weights (C2)
                                            -> rejection-KY draw (C1)
                                            -> scatter into the state vector

The state-vector scatter/gather between colors is the paper's shared-RF
exchange; on one chip it is a VMEM gather, across devices `distributed.py`
turns it into an all-gather of the (tiny) value vector.

All samplers are pluggable so the Fig. 12 ablations are first-class:
  lut_ky   : LUT-exp int8 weights + rejection-KY      (AIA, C1+C2)
  exact_ky : exact exp, 16-bit weights + rejection-KY (ablate C2)
  cdf      : normalized softmax + inverse-CDF search  (PULP/CPU baseline)
  gumbel   : Gumbel-max argmax                        (beyond-paper TPU-native)
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# The jitted wrappers donate the whole chain-state pytree; the PRNG-key and
# scalar-counter leaves have no aliasable output when return_state=False and
# jax warns once per compile.  The partial donation is deliberate (vals and
# the histogram are the big buffers) — silence exactly that warning.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

from repro.core import coloring as coloring_mod
from repro.core import ky as ky_core
from repro.core.draws import SAMPLERS, draw_from_logits
from repro.core.graphs import DiscreteBayesNet
from repro.core.interp import LUTSpec, build_exp_weight_lut
from repro.diag import accum as diag_accum

NEG_INF = -1e30


@dataclasses.dataclass
class ColorGroup:
    nodes: jax.Array  # (n_c,) int32
    cards: jax.Array  # (n_c,) int32
    base: jax.Array  # (n_c, F) int32; 0 => padded factor slot (dummy entry)
    stride: jax.Array  # (n_c, F, S) int32
    scope_var: jax.Array  # (n_c, F, S) int32
    is_self: jax.Array  # (n_c, F, S) bool


@dataclasses.dataclass
class CompiledBayesNet:
    log_flat: jax.Array  # (T,) f32: [0.0] + concat(log cpts)
    groups: list[ColorGroup]
    cards: jax.Array  # (n,) int32
    init_vals: jax.Array  # (n,) int32 (evidence baked in)
    free_mask: jax.Array  # (n,) bool
    max_card: int
    n_nodes: int
    colors: tuple[int, ...]  # hashable: this dataclass crosses jit boundaries
    exp_table: jax.Array
    exp_spec: LUTSpec
    name: str = "bn"


def cpt_bases(bn: DiscreteBayesNet) -> np.ndarray:
    """Offset of each node's CPT in the flat log-CPT arena (entry 0 is the
    dummy used by padded factor slots)."""
    bases = np.zeros(bn.n_nodes, np.int64)
    off = 1
    for i, cpt in enumerate(bn.cpts):
        bases[i] = off
        off += cpt.size
    return bases


def build_color_group(
    bn: DiscreteBayesNet, free: list[int], bases: np.ndarray | None = None
) -> ColorGroup:
    """Dense CPT-gather tensors for one conditionally-independent node set.

    `compile_bayesnet` calls this per color; `repro.compile.backend` calls it
    per *schedule round*, so passes that regroup rounds (e.g. color merging)
    change execution without touching this module."""
    if bases is None:
        bases = cpt_bases(bn)

    def factor_slots(fnode: int):
        """(base, stride-per-scope-var, scope vars) for CPT of `fnode`."""
        scope = list(bn.parents[fnode]) + [fnode]
        dims = [int(bn.cards[v]) for v in scope]
        strides = np.ones(len(dims), np.int64)
        for k in range(len(dims) - 2, -1, -1):
            strides[k] = strides[k + 1] * dims[k + 1]
        return bases[fnode], strides, scope

    factor_lists = [[i] + bn.children(i) for i in free]
    f_max = max(len(fl) for fl in factor_lists)
    s_max = max(len(bn.parents[f]) + 1 for fl in factor_lists for f in fl)
    nc = len(free)
    base = np.zeros((nc, f_max), np.int64)
    stride = np.zeros((nc, f_max, s_max), np.int64)
    scope_var = np.zeros((nc, f_max, s_max), np.int64)
    is_self = np.zeros((nc, f_max, s_max), bool)
    for a, (i, fl) in enumerate(zip(free, factor_lists)):
        for b, f in enumerate(fl):
            fb, fs, sc = factor_slots(f)
            base[a, b] = fb
            stride[a, b, : len(sc)] = fs
            scope_var[a, b, : len(sc)] = sc
            is_self[a, b, : len(sc)] = [v == i for v in sc]
    return ColorGroup(
        nodes=jnp.asarray(free, jnp.int32),
        cards=jnp.asarray([bn.cards[i] for i in free], jnp.int32),
        base=jnp.asarray(base, jnp.int32),
        stride=jnp.asarray(stride, jnp.int32),
        scope_var=jnp.asarray(scope_var, jnp.int32),
        is_self=jnp.asarray(is_self),
    )


def build_clamped_groups(
    bn: DiscreteBayesNet,
    node_lists,
    clamp_nodes,
    bases: np.ndarray | None = None,
) -> list[ColorGroup]:
    """Rebuild gather groups with a runtime-evidence set removed.

    `node_lists` is the unclamped grouping (eager: `[g.nodes for g in
    cbn.groups]`; schedule backend: `[r.nodes for r in rounds]`); clamped
    nodes are dropped from every group and all-clamped groups vanish —
    exactly what `compile_bayesnet` does when the same evidence is baked,
    which is what makes the runtime-clamp path bit-exact with it."""
    if bases is None:
        bases = cpt_bases(bn)
    clamp = set(int(v) for v in clamp_nodes)
    out: list[ColorGroup] = []
    for nodes in node_lists:
        free = [int(v) for v in nodes if int(v) not in clamp]
        if free:
            out.append(build_color_group(bn, free, bases))
    return out


def compile_bayesnet(
    bn: DiscreteBayesNet,
    evidence: dict[int, int] | None = None,
    lut_size: int = 16,
    lut_range: float = 8.0,
    lut_bits: int = 8,
    seed: int = 0,
    colors: np.ndarray | None = None,
) -> CompiledBayesNet:
    """Backend code generation (Fig. 8 right half): per-color CPT-gather
    tensors.  `repro.compile` drives this with the pass pipeline's coloring
    (`colors=`); called standalone it runs DSATUR itself."""
    bn.validate()
    evidence = dict(evidence or {})
    n = bn.n_nodes
    if colors is None:
        colors = coloring_mod.dsatur(bn.moral_adjacency())
    # raised, not asserted: a bad imported coloring is the parallel-Gibbs
    # race condition, and that check must survive `python -O`
    from repro.analysis import verify as verify_mod

    verify_mod.require_proper_coloring(
        bn.moral_adjacency(), colors, loc=f"{bn.name}:compile_bayesnet"
    )

    # flat log-CPT arena; entry 0 is the dummy used by padded factor slots
    bases = cpt_bases(bn)
    tables = [np.zeros(1)] + [np.log(cpt.reshape(-1)) for cpt in bn.cpts]
    log_flat = jnp.asarray(np.concatenate(tables), jnp.float32)

    groups: list[ColorGroup] = []
    for group_nodes in coloring_mod.color_groups(colors):
        free = [v for v in group_nodes if v not in evidence]
        if not free:
            continue
        groups.append(build_color_group(bn, free, bases))

    rng = np.random.default_rng(seed)
    init = rng.integers(0, np.asarray(bn.cards), size=n)
    free_mask = np.ones(n, bool)
    for v, x in evidence.items():
        init[v] = x
        free_mask[v] = False

    # integer-weight exp table (paper Sec. III-D: 16 entries, 8-bit values)
    exp_table, exp_spec = build_exp_weight_lut(
        bits=lut_bits, x_min=-lut_range, size=lut_size
    )
    return CompiledBayesNet(
        log_flat=log_flat,
        groups=groups,
        cards=jnp.asarray(np.asarray(bn.cards), jnp.int32),
        init_vals=jnp.asarray(init, jnp.int32),
        free_mask=jnp.asarray(free_mask),
        max_card=int(np.max(bn.cards)),
        n_nodes=n,
        colors=tuple(int(c) for c in colors),
        exp_table=exp_table,
        exp_spec=exp_spec,
        name=bn.name,
    )


@dataclasses.dataclass
class BNChainState:
    """Everything a BN Gibbs run needs to resume exactly where it stopped.

    Carrying (vals, key, hist, t) across `gibbs_run_loop` calls makes a
    sliced run bit-identical to an uninterrupted one: the key is split once
    per sweep in sequence, the marginal histogram keeps accumulating, and
    `t` (global sweeps completed) keeps the burn-in/thinning gate aligned
    with where the chain actually is, not where the current slice started.

    `quality` is the optional streaming quality accumulator
    (`repro.diag.accum.QualityAccum`) — None (the default, an empty
    pytree subtree) when diagnostics are off, so every existing jit cache
    and carry pattern is bit-compatible.  When present it rides the carry
    exactly like the histogram, which is what makes R-hat/ESS bit-exact
    across sliced runs."""

    vals: jax.Array  # (B, n) int32 current chain states
    key: jax.Array  # PRNG key as of the next sweep
    hist: jax.Array  # (n, V) int32 marginal histogram so far
    t: jax.Array  # () int32 sweeps completed
    quality: object = None  # diag.accum.QualityAccum | None


jax.tree_util.register_dataclass(
    ColorGroup, ["nodes", "cards", "base", "stride", "scope_var", "is_self"], []
)
jax.tree_util.register_dataclass(
    CompiledBayesNet,
    ["log_flat", "groups", "cards", "init_vals", "free_mask", "exp_table"],
    ["max_card", "n_nodes", "colors", "exp_spec", "name"],
)
jax.tree_util.register_dataclass(
    BNChainState, ["vals", "key", "hist", "t", "quality"], []
)


def group_log_conditionals(
    cbn: CompiledBayesNet, g: ColorGroup, vals: jax.Array
) -> jax.Array:
    """log P(X_i = v | MB(X_i)) up to a constant, for all chains and all
    nodes of one color at once.  vals: (B, n) -> (B, n_c, V)."""
    v_range = jnp.arange(cbn.max_card, dtype=jnp.int32)
    sv = vals[:, g.scope_var]  # (B, n_c, F, S)
    val_or_v = jnp.where(
        g.is_self[None, ..., None], v_range, sv[..., None]
    )  # (B, n_c, F, S, V)
    addr = g.base[None, :, :, None] + jnp.sum(
        g.stride[None, ..., None] * val_or_v, axis=-2
    )  # (B, n_c, F, V)
    terms = cbn.log_flat[addr]
    # factor by factor, left to right: a reduction's order is the compiler's
    # choice, and the fused kernel (kernels/bn_gibbs.py) adds in this order
    logp = terms[:, :, 0]
    for f in range(1, terms.shape[2]):
        logp = logp + terms[:, :, f]  # (B, n_c, V)
    return jnp.where(v_range < g.cards[None, :, None], logp, NEG_INF)




def update_color_group(
    cbn: CompiledBayesNet,
    g: ColorGroup,
    vals: jax.Array,
    key: jax.Array,
    sampler: str = "lut_ky",
) -> jax.Array:
    logp = group_log_conditionals(cbn, g, vals)
    labels = draw_from_logits(logp, key, sampler, cbn.exp_table, cbn.exp_spec)
    return vals.at[:, g.nodes].set(labels)


def gibbs_sweep(
    cbn: CompiledBayesNet,
    vals: jax.Array,
    key: jax.Array,
    sampler: str,
    groups: list[ColorGroup] | None = None,
) -> jax.Array:
    """One iteration of Alg. 2: loop over rounds, parallel within a round.
    `groups` defaults to the eager color groups; the schedule backend passes
    its round-ordered groups (same key-split structure either way)."""
    groups = cbn.groups if groups is None else groups
    keys = jax.random.split(key, len(groups))
    for g, k in zip(groups, keys):
        vals = update_color_group(cbn, g, vals, k, sampler)
    return vals


def init_chain_values(
    cbn: CompiledBayesNet,
    key: jax.Array,
    n_chains: int,
    clamp_vals: jax.Array | None = None,
    clamp_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-chain random initialization of the free RVs (evidence stays
    clamped).  Draws are uniform in [0, card_i) via `jax.random.randint`
    with the per-node maxval broadcast — NOT `randint(...) % card`, whose
    modulo fold is biased for cards that do not divide the draw range.

    `clamp_vals`/`clamp_mask` ((n,) int32 / (n,) bool) add *runtime*
    evidence on top of whatever the compile baked in: masked nodes start at
    their clamped value instead of a random draw.  The random tensor is
    drawn for every node either way, so a runtime-clamped init is bit-exact
    with a compile that baked the same evidence.  Returns (vals (B, n),
    advanced key)."""
    k0, key = jax.random.split(key)
    rnd = jax.random.randint(
        k0, (n_chains, cbn.n_nodes), 0,
        jnp.maximum(cbn.cards[None], 1), jnp.int32,
    )
    fixed = cbn.init_vals
    free = cbn.free_mask
    if clamp_mask is not None:
        fixed = jnp.where(clamp_mask, clamp_vals, fixed)
        free = free & ~clamp_mask
    vals = jnp.where(free[None], rnd, fixed[None])
    return vals, key


def gibbs_run_loop(
    cbn: CompiledBayesNet,
    groups: list[ColorGroup],
    vals: jax.Array | None,
    key: jax.Array | None,
    n_iters: int,
    burn_in: int,
    sampler: str,
    thin: int = 1,
    carry: BNChainState | None = None,
    return_state: bool = False,
    fused: bool = False,
    interpret: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """The iteration loop shared by the eager engine (`groups=cbn.groups`)
    and the schedule-direct backend (`groups` built from `Schedule.rounds`):
    identical tensors + identical key-split structure => identical bits.

    `fused=True` executes every sweep through the Pallas kernel in
    `kernels/bn_gibbs.py` — one `pallas_call` per sweep, chain values
    VMEM-resident across all rounds — bit-exact with the unfused sweep for
    the samplers the kernel implements (anything else raises here, at
    trace time, rather than silently falling back).  The key-split
    structure, histogram accumulation, and carry-state semantics are shared
    with the unfused path, so slicing and runtime clamps work unchanged:
    clamped nodes are simply absent from `groups` (the same rebuild baked
    evidence gets), mirroring how the fused MRF path restores pins.

    `thin` keeps every thin-th post-burn-in sweep in the marginal histogram
    (streaming accumulation — no sample matrix is ever materialized); the
    chain itself always advances every sweep, so thin=1 reproduces today's
    bits exactly and any thin leaves the final state unchanged.

    `carry` resumes a previous call's `BNChainState` (then `vals`/`key` are
    ignored and may be None) and `n_iters` counts *additional* sweeps; the
    burn-in/thinning gate tests the carried global sweep count, so a run
    sliced at any boundaries — with the same static burn_in/thin/groups per
    slice — is bit-exact with the uninterrupted run.  `return_state=True`
    appends the state needed to continue.

    `diag_total` (the query's *total* sweep budget — under slicing that is
    more than this call's `n_iters`) switches the streaming quality
    accumulator on for a fresh run: a `diag.accum.QualityAccum` joins the
    carry and ingests the same one-hot tensor the histogram does, masked
    by the same keep gate — pure jax, no extra randomness, so the draw
    stream is untouched.  On a resumed carry the accumulator (or its
    absence) rides in with the state and `diag_total` is ignored — the
    split point was fixed at creation, which is what makes sliced and
    unsliced accumulation bit-identical."""
    if fused:
        # lazy import: kernels/bn_gibbs imports this module for NEG_INF
        from repro.kernels import bn_gibbs

        bn_gibbs.check_fused_sampler(sampler)
        fr = bn_gibbs.build_fused_rounds(groups)
        sweep = lambda v, k: bn_gibbs.fused_gibbs_sweep(
            cbn, fr, v, k, sampler, interpret=interpret
        )
    else:
        sweep = lambda v, k: gibbs_sweep(cbn, v, k, sampler, groups)

    if carry is None:
        quality = None
        if diag_total is not None:
            quality = diag_accum.make_accum(
                vals.shape[0], cbn.n_nodes, cbn.max_card,
                diag_accum.kept_count(diag_total, burn_in, thin), diag_batch,
            )
        carry = BNChainState(
            vals=vals,
            key=key,
            hist=jnp.zeros((cbn.n_nodes, cbn.max_card), jnp.int32),
            t=jnp.zeros((), jnp.int32),
            quality=quality,
        )

    def body(_, st):
        key, sub = jax.random.split(st.key)
        vals = sweep(st.vals, sub)
        onehot = (
            vals[..., None] == jnp.arange(cbn.max_card, dtype=jnp.int32)
        ).astype(jnp.int32)
        keep = (st.t >= burn_in) & ((st.t - burn_in) % thin == 0)
        hist = st.hist + jnp.where(keep, onehot.sum(0), 0)
        quality = st.quality
        if quality is not None:
            quality = diag_accum.update(quality, onehot, keep)
        return BNChainState(
            vals=vals, key=key, hist=hist, t=st.t + 1, quality=quality
        )

    carry = jax.lax.fori_loop(0, n_iters, body, carry)
    card_mask = (
        jnp.arange(cbn.max_card, dtype=jnp.int32)[None] < cbn.cards[:, None]
    )
    denom = jnp.maximum(carry.hist.sum(-1, keepdims=True), 1)
    marginals = jnp.where(card_mask, carry.hist / denom, 0.0)
    if return_state:
        return marginals, carry.vals, carry
    return marginals, carry.vals


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_chains", "n_iters", "burn_in", "sampler", "thin", "return_state",
    ),
    # sliced serving resumes a chain it will never touch again: donating the
    # carried state lets XLA update it in place instead of copying (B, n)
    # vals + histogram every slice.  Callers must treat a passed carry as
    # consumed (tests/test_bn_fused.py has the donation smoke test).
    donate_argnames=("carry",),
)
def run_gibbs(
    cbn: CompiledBayesNet,
    key: jax.Array | None,
    n_chains: int = 32,
    n_iters: int = 200,
    burn_in: int = 50,
    sampler: str = "lut_ky",
    thin: int = 1,
    carry: BNChainState | None = None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """Multi-chain chromatic Gibbs; returns (marginals (n, V), final vals).

    Chains are the data-parallel axis (AIA's MaxChain loop, Alg. 1 line 1);
    the single-marginal histogram accumulates over all chains and kept
    iterations, giving every node's marginal at no extra cost (the paper's
    "compute all single marginals without overhead" observation).

    `carry`/`return_state` slice the run: see `gibbs_run_loop`
    (`diag_total`/`diag_batch` switch its quality accumulator on)."""
    vals = None
    if carry is None:
        vals, key = init_chain_values(cbn, key, n_chains)
    return gibbs_run_loop(
        cbn, cbn.groups, vals, key, n_iters, burn_in, sampler, thin,
        carry=carry, return_state=return_state,
        diag_total=diag_total, diag_batch=diag_batch,
    )
