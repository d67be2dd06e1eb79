"""Distributed chromatic Gibbs via shard_map (paper Sec. III mesh, at pod scale).

AIA's 4x4 core mesh becomes the JAX device mesh; the two data-movement
mechanisms map 1:1 onto collectives:

  * neighbor shared-RF access (C4)  ->  `lax.ppermute` halo exchange between
    mesh-adjacent devices (MRF grids are row-partitioned over the "model"
    axis; only boundary rows move, one ICI hop, contention-free);
  * global barrier / event unit (C5) -> the implicit synchronization at each
    collective boundary: one per color, exactly Alg. 2's schedule;
  * shared-RF value broadcast (BN)   -> a psum of the (tiny) int delta of the
    state vector after each color update — each node is owned by exactly one
    device (the Sec. IV-B mapping), so deltas are disjoint.

Chains are the pure-DP axis ("data"; "pod" stacks more of it multi-pod):
no cross-chain communication at all, mirroring Alg. 1's MaxChain loop.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import bayesnet as bnet
from repro.core import compat
from repro.core import ky as ky_core
from repro.core import mrf as mrf_mod
from repro.core.draws import draw_from_logits
from repro.core.graphs import GridMRF
from repro.core.interp import build_exp_weight_lut
from repro.core.mapping import MeshPlacement
from repro.diag import accum as diag_accum
from repro.kernels import bn_gibbs
from repro.kernels import mrf_gibbs as mrf_kernels

# ---------------------------------------------------------------------------
# MRF: row-partitioned grid with ppermute halo exchange
# ---------------------------------------------------------------------------


def _halo_exchange(lab: jax.Array, axis: str) -> tuple[jax.Array, jax.Array]:
    """Send boundary rows to mesh neighbors; returns (up_halo, down_halo) of
    shape (..., 1, W).  Global grid boundary gets -1 (no neighbor)."""
    n = compat.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    down_perm = [(i, (i + 1) % n) for i in range(n)]
    up_perm = [(i, (i - 1) % n) for i in range(n)]
    up_halo = jax.lax.ppermute(lab[..., -1:, :], axis, down_perm)
    down_halo = jax.lax.ppermute(lab[..., :1, :], axis, up_perm)
    up_halo = jnp.where(idx == 0, -1, up_halo)
    down_halo = jnp.where(idx == n - 1, -1, down_halo)
    return up_halo, down_halo


def _local_half_step(
    mrf: GridMRF,
    lab: jax.Array,  # (B, h_loc, W)
    ev: jax.Array,  # (h_loc, W)
    key: jax.Array,
    parity: int,
    sampler: str,
    exp_table,
    exp_spec,
    axis: str,
) -> jax.Array:
    up_halo, down_halo = _halo_exchange(lab, axis)
    padded = jnp.concatenate([up_halo, lab, down_halo], axis=-2)
    up, down = padded[..., :-2, :], padded[..., 2:, :]
    neg_col = jnp.full(lab.shape[:-1] + (1,), -1, lab.dtype)
    left = jnp.concatenate([neg_col, lab[..., :, :-1]], axis=-1)
    right = jnp.concatenate([lab[..., :, 1:], neg_col], axis=-1)

    v_range = jnp.arange(mrf.n_labels, dtype=lab.dtype)
    logp = mrf_mod.log_potentials(
        [nb[..., None] for nb in (up, down, left, right)], ev[..., None],
        v_range, **mrf_mod.energy_fields(mrf),
    )
    new = draw_from_logits(logp, key, sampler, exp_table, exp_spec)

    h_loc, w = lab.shape[-2], lab.shape[-1]
    row0 = jax.lax.axis_index(axis) * h_loc
    gr = row0 + jnp.arange(h_loc)[:, None]
    gc = jnp.arange(w)[None, :]
    mask = ((gr + gc) % 2) == parity
    return jnp.where(mask, new, lab)


def mrf_gibbs_sharded(
    mrf: GridMRF,
    evidence: jax.Array,
    key: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int,
    n_iters: int,
    sampler: str = "lut_ky",
    chain_axes: tuple[str, ...] = ("data",),
    grid_axis: str = "model",
    parities: tuple[int, ...] = (0, 1),
):
    """Chromatic Gibbs with the grid row-sharded over `grid_axis` and chains
    sharded over `chain_axes`.  Returns final labels (B, H, W).  `parities`
    is the per-round checkerboard order — (0, 1) eagerly, or the compiled
    `Schedule`'s round order under the schedule backend; each round's halo
    read is the `ppermute_halo` comm op lowered to `lax.ppermute`.

    Potts smoothness only: other pairwise families serve through the
    fused sharded engine (`mrf_fused_sharded`)."""
    if mrf.pairwise != "potts":
        raise ValueError(
            f"mrf_gibbs_sharded implements Potts smoothness only, not "
            f"pairwise={mrf.pairwise!r}; use mrf_fused_sharded"
        )
    exp_table, exp_spec = build_exp_weight_lut()
    n_grid = int(np.prod([mesh.shape[a] for a in (grid_axis,)]))
    assert mrf.height % n_grid == 0, "grid rows must divide over devices"
    n_chain_dev = int(np.prod([mesh.shape[a] for a in chain_axes]))
    assert n_chains % n_chain_dev == 0

    chain_spec = P(chain_axes if len(chain_axes) > 1 else chain_axes[0])

    def body(ev_loc, key):
        ci = jax.lax.axis_index(chain_axes[0])
        for a in chain_axes[1:]:
            ci = ci * compat.axis_size(a) + jax.lax.axis_index(a)
        gi = jax.lax.axis_index(grid_axis)
        key = jax.random.fold_in(jax.random.fold_in(key, ci), gi)
        k0, key = jax.random.split(key)
        lab = jax.random.randint(
            k0,
            (n_chains // n_chain_dev, mrf.height // n_grid, mrf.width),
            0,
            mrf.n_labels,
            jnp.int32,
        )

        def it(t, carry):
            lab, key = carry
            ks = jax.random.split(key, 1 + len(parities))
            for i, parity in enumerate(parities):
                lab = _local_half_step(
                    mrf, lab, ev_loc, ks[1 + i], parity, sampler, exp_table,
                    exp_spec, grid_axis,
                )
            return lab, ks[0]

        lab, _ = jax.lax.fori_loop(0, n_iters, it, (lab, key))
        return lab

    f = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(grid_axis, None), P()),
        out_specs=P(chain_spec[0] if len(chain_axes) == 1 else chain_axes,
                    grid_axis, None),
        check_vma=False,
    )
    return jax.jit(f)(evidence, key)


# ---------------------------------------------------------------------------
# Bayes nets: color groups partitioned over devices per the Sec. IV-B mapping
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedGroup:
    """One color group partitioned over n_dev devices, padded to equal width.
    All arrays carry a leading (n_dev,) axis; node id == n_nodes marks a pad
    slot (dropped by out-of-bounds scatter)."""

    nodes: jax.Array  # (n_dev, nc_max)
    cards: jax.Array
    base: jax.Array  # (n_dev, nc_max, F)
    stride: jax.Array  # (n_dev, nc_max, F, S)
    scope_var: jax.Array
    is_self: jax.Array


jax.tree_util.register_dataclass(
    ShardedGroup, ["nodes", "cards", "base", "stride", "scope_var", "is_self"], []
)


def shard_bn_groups(
    cbn: bnet.CompiledBayesNet,
    n_dev: int,
    placement: MeshPlacement | None = None,
    groups: list[bnet.ColorGroup] | None = None,
) -> list[ShardedGroup]:
    """Partition each color group across devices.  With a mapping (Sec. IV-B)
    nodes go to their placed core modulo n_dev; otherwise round-robin.
    `groups` overrides `cbn.groups` — the schedule-direct backend passes its
    round-ordered groups here."""
    out = []
    for g in groups if groups is not None else cbn.groups:
        nodes = np.asarray(g.nodes)
        if placement is not None:
            owner = placement.placement[nodes] % n_dev
        else:
            owner = np.arange(len(nodes)) % n_dev
        parts = [np.where(owner == d)[0] for d in range(n_dev)]
        nc_max = max(1, max(len(p) for p in parts))

        def pack(arr, pad_value=0):
            arr = np.asarray(arr)
            res = np.full((n_dev, nc_max) + arr.shape[1:], pad_value,
                          arr.dtype)
            for d, p in enumerate(parts):
                res[d, : len(p)] = arr[p]
            return jnp.asarray(res)

        out.append(
            ShardedGroup(
                nodes=pack(np.asarray(g.nodes), pad_value=cbn.n_nodes),
                cards=pack(np.asarray(g.cards), pad_value=1),
                base=pack(np.asarray(g.base)),  # pad base 0 -> dummy entry
                stride=pack(np.asarray(g.stride)),
                scope_var=pack(np.asarray(g.scope_var)),
                is_self=pack(np.asarray(g.is_self)),
            )
        )
    return out


def bn_gibbs_sharded(
    cbn: bnet.CompiledBayesNet,
    key: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int,
    n_iters: int,
    burn_in: int,
    sampler: str = "lut_ky",
    placement: MeshPlacement | None = None,
    chain_axis: str = "data",
    node_axis: str = "model",
    groups: list[bnet.ColorGroup] | None = None,
):
    """Distributed Alg. 2: nodes of a color split over `node_axis` devices,
    chains over `chain_axis`.  After each color/round, the disjoint updates
    are merged with one small integer psum — the `psum_broadcast` comm op of
    the schedule, i.e. the shared-RF exchange.  `groups` overrides the
    eager color groups with schedule-round groups.
    Returns (marginals (n, V), final local vals)."""
    n_dev = mesh.shape[node_axis]
    n_chain_dev = mesh.shape[chain_axis]
    assert n_chains % n_chain_dev == 0
    sgroups = shard_bn_groups(cbn, n_dev, placement, groups=groups)
    b_loc = n_chains // n_chain_dev

    def body(key):
        ci = jax.lax.axis_index(chain_axis)
        di = jax.lax.axis_index(node_axis)
        kc = jax.random.fold_in(key, ci)
        vals, kc = bnet.init_chain_values(cbn, kc, b_loc)

        def sweep(vals, kk):
            keys = jax.random.split(kk, len(sgroups))
            for sg, k in zip(sgroups, keys):
                g = bnet.ColorGroup(
                    nodes=sg.nodes[di],
                    cards=sg.cards[di],
                    base=sg.base[di],
                    stride=sg.stride[di],
                    scope_var=sg.scope_var[di],
                    is_self=sg.is_self[di],
                )
                logp = bnet.group_log_conditionals(cbn, g, vals)
                lab = draw_from_logits(
                    logp, jax.random.fold_in(k, di), sampler,
                    cbn.exp_table, cbn.exp_spec,
                )
                upd = vals.at[:, g.nodes].set(lab, mode="drop")
                # disjoint ownership => one psum merges all devices' updates
                vals = vals + jax.lax.psum(upd - vals, node_axis)
            return vals

        hist0 = jnp.zeros((cbn.n_nodes, cbn.max_card), jnp.int32)

        def it(t, carry):
            vals, kk, hist = carry
            kk, sub = jax.random.split(kk)
            vals = sweep(vals, sub)
            onehot = (
                vals[..., None] == jnp.arange(cbn.max_card, dtype=jnp.int32)
            ).astype(jnp.int32)
            hist = hist + jnp.where(t >= burn_in, onehot.sum(0), 0)
            return vals, kk, hist

        vals, _, hist = jax.lax.fori_loop(0, n_iters, it, (vals, kc, hist0))
        hist = jax.lax.psum(hist, chain_axis)
        return hist, vals

    f = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(),),
        out_specs=(P(), P(chain_axis, None)),
        check_vma=False,
    )
    hist, vals = jax.jit(f)(key)
    card_mask = (
        jnp.arange(cbn.max_card, dtype=jnp.int32)[None] < cbn.cards[:, None]
    )
    denom = jnp.maximum(hist.sum(-1, keepdims=True), 1)
    return jnp.where(card_mask, hist / denom, 0.0), vals


# ---------------------------------------------------------------------------
# Fused sharded engines: ONE shard_map body wraps the Pallas round kernel and
# its collectives, so the sharded route executes the same VMEM-resident
# datapath as single-device fused (the mesh-scale inter-core register-sharing
# analogue).  Bit-exact with the single-device fused schedule backend: the
# random stream is generated over the full grid/round on every device and
# sliced/gathered to the local shard, so each site consumes exactly the words
# the unsharded kernel would hand it.
# ---------------------------------------------------------------------------


def _quality_spec(chain_axis: str | None, site_axis: str | None):
    """PartitionSpecs for a `QualityAccum` carry: the (…, B, S, V) moment
    leaves shard over the chain and/or site axes; the scalar counters are
    replicated (their update depends only on the keep gate, which every
    device computes identically)."""
    return diag_accum.QualityAccum(
        counts=P(),
        mean=P(None, chain_axis, site_axis, None),
        m2=P(None, chain_axis, site_axis, None),
        split_at=P(),
        batch_len=P(),
        bm_count=P(),
        bm_mean=P(chain_axis, site_axis, None),
        bm_m2=P(chain_axis, site_axis, None),
        cur_sum=P(chain_axis, site_axis, None),
        cur_n=P(),
    )


def mrf_fused_sharded_program(
    mrf: GridMRF,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int,
    n_iters: int,
    parities: tuple[int, ...],
    with_quality: bool = False,
    chain_axis: str = "data",
    grid_axis: str = "model",
    interpret: bool | None = None,
):
    """The jitted one-`shard_map`-body program `mrf_fused_sharded` runs:
    `(labels (B, H, W), key, quality | None, evidence (H, W)) -> (labels,
    key, quality)`, labels sharded (chain_axis, grid_axis) and evidence
    rows over grid_axis.  Per shard and round, one `pallas_call` half-step
    over the local row slab, with the halo rows exchanged via
    `lax.ppermute` (the `ppermute_halo` mechanism) between rounds — comm
    and compute in a single scanned body instead of separate engine ops."""
    if interpret is None:
        interpret = compat.pallas_interpret()
    exp_table, exp_spec = build_exp_weight_lut()
    n_grid = mesh.shape[grid_axis]
    n_chain_dev = mesh.shape[chain_axis]
    if mrf.height % n_grid != 0:
        raise ValueError(
            f"grid height {mrf.height} must divide over {n_grid} devices"
        )
    if n_chains % n_chain_dev != 0:
        raise ValueError(
            f"n_chains {n_chains} must divide over {n_chain_dev} devices"
        )
    h_loc = mrf.height // n_grid
    b_loc = n_chains // n_chain_dev
    qspec = _quality_spec(chain_axis, grid_axis) if with_quality else None
    lab_spec = P(chain_axis, grid_axis, None)

    def body(labels, key, quality, ev_loc):
        gi = jax.lax.axis_index(grid_axis)
        ci = jax.lax.axis_index(chain_axis)
        row0 = gi * h_loc
        chain0 = ci * b_loc

        def it(t, st):
            labels, key, quality = st
            ks = jax.random.split(key, 1 + len(parities))
            for i, parity in enumerate(parities):
                up_halo, down_halo = _halo_exchange(labels, grid_axis)
                labels = mrf_kernels.mrf_sharded_round_step(
                    mrf, labels, ev_loc, ks[1 + i], parity, exp_table,
                    exp_spec, row0=row0, chain0=chain0,
                    n_chains_total=n_chains, up_halo=up_halo,
                    down_halo=down_halo, interpret=interpret,
                )
            if quality is not None:
                onehot = (
                    labels.reshape(labels.shape[0], -1)[..., None]
                    == jnp.arange(mrf.n_labels, dtype=labels.dtype)
                ).astype(jnp.int32)
                quality = diag_accum.update(quality, onehot,
                                            jnp.asarray(True))
            return labels, ks[0], quality

        return jax.lax.fori_loop(0, n_iters, it, (labels, key, quality))

    return jax.jit(compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(lab_spec, P(), qspec, P(grid_axis, None)),
        out_specs=(lab_spec, P(), qspec),
        check_vma=False,
    ))


def mrf_fused_sharded(
    mrf: GridMRF,
    evidence: jax.Array,  # (H, W) int32
    key: jax.Array | None,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int,
    n_iters: int,
    parities: tuple[int, ...],
    carry: mrf_mod.MRFChainState | None = None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
    chain_axis: str = "data",
    grid_axis: str = "model",
    interpret: bool | None = None,
    profile_sig: str | None = None,
):
    """The fused MRF schedule rounds inside one `shard_map` body
    (`mrf_fused_sharded_program`).

    Bit-exact with `compile/backend.run_mrf_schedule(fused=True)`: the init,
    key-split structure, and per-site word streams are identical (full-grid
    streams sliced to the slab), so carries cross the vmap<->sharded route
    boundary freely and sliced serving rides the sharded route.  The
    `MRFChainState` carry shards its labels (and `QualityAccum` site-moment
    leaves) over `grid_axis`; pins never route here (`executor.route`
    excludes them)."""
    if carry is None:
        labels, key = mrf_mod.init_labels(mrf, key, n_chains)
        quality = None
        if diag_total is not None:
            quality = diag_accum.make_accum(
                n_chains, mrf.height * mrf.width, mrf.n_labels,
                jnp.asarray(diag_total, jnp.int32), diag_batch,
            )
    else:
        labels, key, quality = carry.labels, carry.key, carry.quality

    jf = mrf_fused_sharded_program(
        mrf, mesh, n_chains=n_chains, n_iters=n_iters, parities=parities,
        with_quality=quality is not None, chain_axis=chain_axis,
        grid_axis=grid_axis, interpret=interpret,
    )
    args = (labels, key, quality, evidence)
    _maybe_capture(profile_sig, jf, args, mesh, kind="mrf",
                   model=getattr(mrf, "name", None), sampler="lut_ky",
                   fused=True, n_chains=n_chains, n_iters=n_iters)
    labels, key, quality = jf(*args)
    if return_state:
        return labels, mrf_mod.MRFChainState(
            labels=labels, key=key, quality=quality
        )
    return labels


@dataclasses.dataclass
class ShardedFusedRounds:
    """The fused-BN round tables partitioned over n_dev devices.

    Like `BNFusedRounds` but with a leading (n_dev,) ownership axis (the
    Sec. IV-B node->core mapping) and a `word_pos` gather table: each local
    node's position in its round's *full* group ordering, so every device
    can slice its rows out of the full random-word stream — the key to
    sharded draws being bit-identical to the single-device kernel's.
    Pad slots carry node id n_nodes (no lane of the scatter reads them),
    cards 0 (masked to NEG_INF) and word_pos 0 (a real row whose draw is
    discarded)."""

    nodes: jax.Array  # (n_dev, R, C) int32
    cards: jax.Array  # (n_dev, R, C) int32
    base: jax.Array  # (n_dev, R, C, F) int32
    stride: jax.Array  # (n_dev, R, C, F, S) int32
    scope_var: jax.Array
    is_self: jax.Array
    word_pos: jax.Array  # (n_dev, R, C) int32
    n_c: tuple[int, ...]  # static: full real node count per round
    c_max: int  # static: local per-device node envelope
    f_max: int
    s_max: int


jax.tree_util.register_dataclass(
    ShardedFusedRounds,
    ["nodes", "cards", "base", "stride", "scope_var", "is_self", "word_pos"],
    ["n_c", "c_max", "f_max", "s_max"],
)


def build_sharded_fused_rounds(
    cbn: bnet.CompiledBayesNet,
    groups: list[bnet.ColorGroup],
    n_dev: int,
    placement: MeshPlacement | None = None,
) -> ShardedFusedRounds:
    """Partition each round's gather tensors across devices (same ownership
    rule as `shard_bn_groups`: placed core modulo n_dev, else round-robin)
    and stack them on a rounds axis padded to the common local envelope."""
    parts_by_round = []
    for g in groups:
        nodes = np.asarray(g.nodes)
        if placement is not None:
            owner = placement.placement[nodes] % n_dev
        else:
            owner = np.arange(len(nodes)) % n_dev
        parts_by_round.append([np.where(owner == d)[0] for d in range(n_dev)])
    c_max = max(
        1, max(len(p) for parts in parts_by_round for p in parts)
    )
    f_max = max(g.base.shape[1] for g in groups)
    s_max = max(g.stride.shape[2] for g in groups)
    n_rounds = len(groups)

    def table(field, pad_value=0, extra=()):
        res = np.full((n_dev, n_rounds, c_max) + extra, pad_value, np.int32)
        return res

    nodes = table("nodes", cbn.n_nodes)
    cards = table("cards", 0)
    base = table("base", 0, (f_max,))
    stride = table("stride", 0, (f_max, s_max))
    scope_var = table("scope_var", 0, (f_max, s_max))
    is_self = table("is_self", 0, (f_max, s_max))
    word_pos = table("word_pos", 0)
    for r, (g, parts) in enumerate(zip(groups, parts_by_round)):
        g_nodes = np.asarray(g.nodes)
        g_cards = np.asarray(g.cards)
        g_base = np.asarray(g.base)
        g_stride = np.asarray(g.stride)
        g_scope = np.asarray(g.scope_var)
        g_self = np.asarray(g.is_self).astype(np.int32)
        f, s = g_base.shape[1], g_stride.shape[2]
        for d, p in enumerate(parts):
            k = len(p)
            nodes[d, r, :k] = g_nodes[p]
            cards[d, r, :k] = g_cards[p]
            base[d, r, :k, :f] = g_base[p]
            stride[d, r, :k, :f, :s] = g_stride[p]
            scope_var[d, r, :k, :f, :s] = g_scope[p]
            is_self[d, r, :k, :f, :s] = g_self[p]
            word_pos[d, r, :k] = p
    return ShardedFusedRounds(
        nodes=jnp.asarray(nodes), cards=jnp.asarray(cards),
        base=jnp.asarray(base), stride=jnp.asarray(stride),
        scope_var=jnp.asarray(scope_var), is_self=jnp.asarray(is_self),
        word_pos=jnp.asarray(word_pos),
        n_c=tuple(int(np.asarray(g.nodes).shape[0]) for g in groups),
        c_max=c_max, f_max=f_max, s_max=s_max,
    )


def bn_fused_sharded(
    cbn: bnet.CompiledBayesNet,
    key: jax.Array | None,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int,
    n_iters: int,
    burn_in: int,
    sampler: str = "lut_ky",
    thin: int = 1,
    placement: MeshPlacement | None = None,
    groups: list[bnet.ColorGroup] | None = None,
    carry: bnet.BNChainState | None = None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
    chain_axis: str = "data",
    node_axis: str = "model",
    interpret: bool | None = None,
    profile_sig: str | None = None,
):
    """The fused BN color rounds inside one `shard_map` body: per round, one
    grid=(1,) `pallas_call` (`kernels/bn_gibbs.fused_color_round`) over the
    device's owned node slice, then the disjoint state deltas merge with the
    `psum_broadcast` collective — all inside the scanned sweep loop.

    Bit-exact with `compile/backend.run_bn_schedule(fused=True)`: the same
    `bn_round_step` kernel runs per round, the init/key-split/keep-gate
    structure matches `bayesnet.gibbs_run_loop`, and each device gathers its
    word rows out of the round's full stream via `word_pos`.  The
    `BNChainState` carry shards its vals/quality chain leaves over
    `chain_axis`; the histogram is merged exactly (int32 psum)."""
    if interpret is None:
        interpret = compat.pallas_interpret()
    bn_gibbs.check_fused_sampler(sampler)
    groups = cbn.groups if groups is None else groups
    n_dev = mesh.shape[node_axis]
    n_chain_dev = mesh.shape[chain_axis]
    if n_chains % n_chain_dev != 0:
        raise ValueError(
            f"n_chains {n_chains} must divide over {n_chain_dev} devices"
        )
    b_loc = n_chains // n_chain_dev
    v = cbn.max_card
    weight_bits, precision, total_steps, n_words = bn_gibbs.draw_params(
        v, sampler
    )
    sfr = build_sharded_fused_rounds(cbn, groups, n_dev, placement)
    n_rounds = len(sfr.n_c)

    if carry is None:
        vals, key = bnet.init_chain_values(cbn, key, n_chains)
        quality = None
        if diag_total is not None:
            quality = diag_accum.make_accum(
                n_chains, cbn.n_nodes, cbn.max_card,
                diag_accum.kept_count(diag_total, burn_in, thin), diag_batch,
            )
        carry = bnet.BNChainState(
            vals=vals, key=key,
            hist=jnp.zeros((cbn.n_nodes, cbn.max_card), jnp.int32),
            t=jnp.zeros((), jnp.int32), quality=quality,
        )
    quality = carry.quality

    qspec = None
    if quality is not None:
        qspec = _quality_spec(chain_axis, None)
    table_spec = jax.tree_util.tree_map(
        lambda _: P(node_axis), sfr,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )

    def body(vals, key, hist0, t0, quality, sfr_loc):
        ci = jax.lax.axis_index(chain_axis)
        chain0 = ci * b_loc
        nodes = sfr_loc.nodes[0]  # (R, C)
        cards = sfr_loc.cards[0]
        base = sfr_loc.base[0]
        stride = sfr_loc.stride[0]
        scope_var = sfr_loc.scope_var[0]
        is_self = sfr_loc.is_self[0]
        word_pos = sfr_loc.word_pos[0]

        def sweep(vals, sub):
            keys = jax.random.split(sub, n_rounds)
            for r in range(n_rounds):
                nc_r = sfr.n_c[r]
                # the round's FULL word stream — byte-for-byte what the
                # single-device kernel draws — sliced to local chains and
                # gathered to the owned nodes' rows
                wr = ky_core.random_words(
                    keys[r], (n_chains * nc_r,), n_words
                ).reshape(n_chains, nc_r, n_words)
                wr = jax.lax.dynamic_slice_in_dim(wr, chain0, b_loc, axis=0)
                wr = jnp.take(wr, word_pos[r], axis=1)  # (b_loc, C, W)
                new_vals = bn_gibbs.fused_color_round(
                    vals, nodes[r], cards[r], base[r], stride[r],
                    scope_var[r], is_self[r], wr, cbn.log_flat, cbn.exp_table,
                    sampler=sampler, exp_spec=cbn.exp_spec, v_max=v,
                    weight_bits=weight_bits, precision=precision,
                    total_steps=total_steps, interpret=interpret,
                )
                # disjoint ownership => one int psum merges all updates
                # (the psum_broadcast mechanism, exact in int32)
                vals = vals + jax.lax.psum(new_vals - vals, node_axis)
            return vals

        delta0 = jnp.zeros_like(hist0)

        def it(_, st):
            vals, key, delta, t, quality = st
            key, sub = jax.random.split(key)
            vals = sweep(vals, sub)
            onehot = (
                vals[..., None]
                == jnp.arange(cbn.max_card, dtype=jnp.int32)
            ).astype(jnp.int32)
            keep = (t >= burn_in) & ((t - burn_in) % thin == 0)
            delta = delta + jnp.where(keep, onehot.sum(0), 0)
            if quality is not None:
                quality = diag_accum.update(quality, onehot, keep)
            return vals, key, delta, t + 1, quality

        vals, key, delta, t, quality = jax.lax.fori_loop(
            0, n_iters, it, (vals, key, delta0, t0, quality)
        )
        hist = hist0 + jax.lax.psum(delta, chain_axis)
        return vals, key, hist, t, quality

    f = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(chain_axis, None), P(), P(), P(), qspec, table_spec),
        out_specs=(P(chain_axis, None), P(), P(), P(), qspec),
        check_vma=False,
    )
    jf = jax.jit(f)
    args = (carry.vals, carry.key, carry.hist, carry.t, quality, sfr)
    _maybe_capture(profile_sig, jf, args, mesh, kind="bn",
                   model=getattr(cbn, "name", None), sampler=sampler,
                   fused=True, n_chains=n_chains, n_iters=n_iters)
    vals, key, hist, t, quality = jf(*args)
    out = bnet.BNChainState(vals=vals, key=key, hist=hist, t=t,
                            quality=quality)
    card_mask = (
        jnp.arange(cbn.max_card, dtype=jnp.int32)[None] < cbn.cards[:, None]
    )
    denom = jnp.maximum(hist.sum(-1, keepdims=True), 1)
    marginals = jnp.where(card_mask, hist / denom, 0.0)
    if return_state:
        return marginals, vals, out
    return marginals, vals


def _maybe_capture(profile_sig, jf, args, mesh, **meta) -> None:
    """Stamp the shard_map executable into the profile registry (when
    profiling is on): the sharded-fused HLO is where the collective-permute
    / all-reduce bytes live, and `obs.profile.join_dispatches` attributes
    sharded dispatches by this signature like any other bucket."""
    if profile_sig is None:
        return
    from repro.obs import profile as profile_mod

    reg = profile_mod.get()
    if reg is None:
        return
    reg.capture(
        profile_sig, lambda: jf.lower(*args), n_chips=mesh.size,
        route="sharded", **meta,
    )


def _check_comm_mechanisms(program, expected: str) -> None:
    """The schedule backend routes each round's comm op onto the collective
    its mechanism names (`psum_broadcast` -> lax.psum, `ppermute_halo` ->
    lax.ppermute); a round carrying any other mechanism has no lowering in
    this engine and must be rejected, not silently psum'd."""
    for r in program.schedule.rounds:
        for op in r.comm:
            if op.mechanism != expected:
                raise ValueError(
                    f"round {r.color} comm op uses mechanism "
                    f"{op.mechanism!r}; this engine lowers {expected!r} only"
                )


def run_program_sharded(
    program,
    key: jax.Array | None,
    mesh: jax.sharding.Mesh,
    *,
    n_chains: int = 32,
    n_iters: int = 200,
    burn_in: int | None = None,
    sampler: str = "lut_ky",
    evidence: jax.Array | None = None,
    backend: str = "eager",
    fused: bool = False,
    thin: int = 1,
    carry=None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
    profile_sig: str | None = None,
    **axes,
):
    """Execute a `repro.compile.CompiledProgram` across a device mesh.

    BNs run the psum-broadcast engine with node ownership taken from the
    program's Sec. IV-B placement; MRFs run the ppermute-halo engine (the
    row partition *is* the placement for a grid).  Same key, same program
    => same states as calling these engines directly.

    With `backend="schedule"` the rounds and their order come from the
    compiled `Schedule` (via the program's lowered executable), and each
    round's comm ops are routed onto the collectives their mechanisms name:
    `psum_broadcast` -> the per-round `lax.psum` of the disjoint state
    delta, `ppermute_halo` -> the `lax.ppermute` boundary-row exchange.

    `fused=True` (schedule backend only) executes the whole run through ONE
    shard_map body wrapping the Pallas round kernels and those collectives
    (`mrf_fused_sharded` / `bn_fused_sharded`) — bit-exact with the
    single-device fused backend, so `carry`/`return_state` slicing and the
    `diag_total` quality accumulator are supported there (and only there:
    the legacy per-device-folded engines have neither a shared key
    structure nor carry pytrees)."""
    if backend not in ("eager", "schedule"):
        raise ValueError(f"unknown backend {backend!r}")
    if fused and backend != "schedule":
        raise ValueError("fused sharded execution is schedule-backend only")
    if not fused and (carry is not None or return_state
                      or diag_total is not None):
        raise ValueError(
            "carry/return_state/diag_total ride the fused sharded route "
            "only (the legacy sharded engines fold keys per device and "
            "carry no state)"
        )
    if program.kind == "bn":
        if evidence is not None:
            raise ValueError(
                "BN evidence is baked into the program at compile time"
            )
        groups = None
        if backend == "schedule":
            _check_comm_mechanisms(program, "psum_broadcast")
            groups = program.schedule_executable().round_groups
        if fused:
            return bn_fused_sharded(
                program.cbn, key, mesh,
                n_chains=n_chains, n_iters=n_iters,
                burn_in=50 if burn_in is None else burn_in,
                sampler=sampler, thin=thin, placement=program.placement,
                groups=groups, carry=carry, return_state=return_state,
                diag_total=diag_total, diag_batch=diag_batch,
                profile_sig=profile_sig, **axes,
            )
        return bn_gibbs_sharded(
            program.cbn, key, mesh,
            n_chains=n_chains, n_iters=n_iters,
            burn_in=50 if burn_in is None else burn_in,
            sampler=sampler, placement=program.placement, groups=groups,
            **axes,
        )
    if evidence is None:
        raise ValueError("MRF programs take the evidence image at run time")
    if burn_in is not None:
        raise ValueError(
            "MRF programs return final states only; burn_in does not apply"
        )
    parities = (0, 1)
    if backend == "schedule":
        _check_comm_mechanisms(program, "ppermute_halo")
        parities = program.schedule_executable().parities
    if fused:
        if sampler != "lut_ky":
            raise ValueError(
                f"fused sharded MRF rounds implement the lut_ky datapath "
                f"only, got sampler={sampler!r}"
            )
        if program.ir.evidence:
            raise ValueError(
                "baked MRF pins have no sharded-fused lowering (the "
                "executor route excludes pinned buckets)"
            )
        return mrf_fused_sharded(
            program.mrf, evidence, key, mesh,
            n_chains=n_chains, n_iters=n_iters, parities=parities,
            carry=carry, return_state=return_state,
            diag_total=diag_total, diag_batch=diag_batch,
            profile_sig=profile_sig, **axes,
        )
    return mrf_gibbs_sharded(
        program.mrf, evidence, key, mesh,
        n_chains=n_chains, n_iters=n_iters, sampler=sampler,
        parities=parities, **axes,
    )
