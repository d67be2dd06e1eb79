"""Static VMEM footprint estimator for the fused Pallas kernels.

A fused bucket that exceeds per-core VMEM fails at dispatch time, on
device, after the batcher has already committed the microbatch.  This
linter estimates the footprint *statically* — from the model structure,
chain width, and sampler alone, no JAX import, no trace — so
`runtime.batcher.fused_eligible` can demote an oversized bucket to the
unfused route up front (`fused_fits`), and the CLI can flag wide replicas
(hepar2/pigs-class models) before anyone benchmarks them.

The estimate mirrors the kernels' actual buffer structure:

  * **BN** (`kernels.bn_gibbs.fused_gibbs_sweep`): the kernel's planar
    layout.  A plane is one (b_pad, c_pad) int32 array — chains on
    sublanes padded to 8, a round's nodes on lanes padded to whole
    128-lane tiles — and the chain state is one (b_pad, n_pad) value
    block.  Inputs are the pipelined blocks, double-buffered: the value
    block in and out, one round's tables ((F + 3 F S) rows of c_pad lanes,
    rows padded to 8) and inverse node map, its W random-word planes, the
    whole log-CPT arena as (rows, 128) and the exp LUT.  Intermediates
    are the planes that live across the kernel's loops: the chain values
    at every (factor, scope) slot, one log-prob plane per candidate value
    plus the gather's address and accumulator, and the KY draw stage — V
    weight planes, V + 1 preprocessed planes, the word planes and the
    walk's carry — plus the value block's state and merge.  The
    per-candidate values (`val_or_v`, addresses, arena reads) are built
    plane by plane inside the (v, f, s) loops and never held whole.  The
    envelope is re-derived here numpy-only: DSATUR over the IR's moral
    adjacency gives c_max (bit-identical to `DsaturPass`;
    `MergeSmallColorsPass` is the identity on DSATUR output, so the
    runtime pipeline matches too), and f_max/s_max are
    coloring-independent structural maxima.  A clamped executable's
    rounds are subsets of these, so the unclamped envelope bounds them.
  * **MRF** (`kernels.mrf_gibbs.mrf_half_step_kernel`): one row-block
    tile — 3 label blocks + evidence + words + the per-candidate energy
    stack and (site, LANES) draw-stage buffers — times the chain count
    (the chain vmap batches the grid, so each grid step still holds one
    chain's tile; chains share nothing, and we budget for the batcher's
    whole chain width resident at once to stay conservative).

Estimates are deliberately *upper-ish* bounds, not bit-accurate sums:
Mosaic's scratch allocation is not modeled (nor, for the MRF, the
pipeline's double buffers), so the headroom factor below absorbs it.
For BN buckets of 8 to 512 chains the estimate lies 1.3 to 2.2 times
above the smallest scoped VMEM limit Mosaic compiles the kernel under
(`tests/test_tpu_compile.py` holds it above that limit for served
buckets).  The point is to demote buckets that are clearly over budget,
not to pack VMEM to the last byte.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.analysis import Finding
from repro.core import coloring as coloring_mod

# Per-core VMEM on current TPUs (see /opt/skills/guides: ~16 MiB/core).
DEFAULT_VMEM_BYTES = 16 * 2**20
# Fraction of the budget at which a warning (not an error) fires.
PRESSURE_FRACTION = 0.75
# Mosaic scratch / double-buffering headroom multiplier on intermediates.
HEADROOM = 1.25

# Mirrors kernels.ky_sampler.LANES (the KY walk's fixed lane width).  Kept
# as a literal so this module stays jax-free; tests/test_analysis.py pins
# the two constants together.
KY_LANES = 128
# Mirrors bayesnet.build_exp_weight_lut defaults (paper Sec. III-D).
EXP_LUT_SIZE = 16
ITEM_BYTES = 4  # int32 / float32 throughout both kernels
# Rows of one (8, 128) vreg tile: a table's rows and the chains pad to it.
SUBLANES = 8
# BN kernel planes besides the per-candidate log-probs in the flat-CPT
# gather (address, factor sum, the arena loop's carry and tile pick), and
# besides the bins in the KY walk (its carry: distance, level, label, done
# flag, bit and rejection counters).
_GATHER_PLANES = 4
_WALK_PLANES = 6

_VMEM_BUDGET = DEFAULT_VMEM_BYTES


def vmem_budget() -> int:
    return _VMEM_BUDGET


def set_vmem_budget(n_bytes: int) -> int:
    """Set the global VMEM budget the linter (and through `fused_fits`,
    the batcher's fused-demotion check) enforces.  Returns the previous
    budget so tests can restore it."""
    global _VMEM_BUDGET
    if n_bytes < 1:
        raise ValueError(f"VMEM budget must be positive, got {n_bytes}")
    prev, _VMEM_BUDGET = _VMEM_BUDGET, int(n_bytes)
    _FIT_CACHE.clear()
    return prev


@dataclasses.dataclass(frozen=True)
class KernelFootprint:
    """A kernel's estimated per-core VMEM residency, with the breakdown
    that tells a human *which* buffer blew the budget."""

    kernel: str  # "bn_fused" | "mrf_fused"
    model: str
    n_chains: int
    sampler: str
    input_bytes: int
    intermediate_bytes: int
    breakdown: dict
    # mesh-slice width the estimate assumes: > 1 budgets the per-shard
    # envelope (local row slab + halo rows MRF; owned node slice BN)
    shard_width: int = 1

    @property
    def total_bytes(self) -> int:
        return self.input_bytes + int(self.intermediate_bytes * HEADROOM)

    def findings(
        self, budget: int | None = None, demotable: bool = True
    ) -> list[Finding]:
        """`demotable=True` (the default) means the batcher's `fused_fits`
        guard will route this bucket unfused before it ever dispatches, so
        an over-budget estimate is a capacity advisory (warning) rather
        than an OOM-in-waiting (error).  Pass False when lint is asked
        about a forced-fused path with no demotion guard."""
        budget = _VMEM_BUDGET if budget is None else budget
        total = self.total_bytes
        loc = f"{self.model}:{self.kernel}"
        if self.shard_width > 1:
            loc += f"@sh{self.shard_width}"
        top = max(self.breakdown, key=self.breakdown.get)
        detail = (
            f"estimated {total / 2**20:.2f} MiB resident "
            f"(B={self.n_chains}, sampler={self.sampler}; dominant buffer "
            f"{top!r} at {self.breakdown[top] / 2**20:.2f} MiB) vs "
            f"{budget / 2**20:.2f} MiB budget"
        )
        if total > budget:
            if demotable:
                detail += "; batcher demotes this bucket to the unfused route"
            return [Finding(
                rule="vmem-budget", loc=loc, message=detail,
                severity="warning" if demotable else "error",
                fixit="shrink n_chains / block size, or keep the bucket on "
                      "the unfused route",
            )]
        if total > PRESSURE_FRACTION * budget:
            return [Finding(rule="vmem-pressure", loc=loc, message=detail)]
        return []


def bn_group_envelope(graph) -> tuple[int, int, int]:
    """(c_max, f_max, s_max) of `build_fused_rounds`' padded envelope,
    re-derived without compiling: DSATUR over the IR's (moral) adjacency
    for the group sizes, structural maxima for the factor/scope dims."""
    adj = graph.adjacency()
    colors = coloring_mod.dsatur(adj)
    evid = {node for node, _ in graph.evidence}
    c_max = 0
    if len(colors):
        for c in range(int(colors.max()) + 1):
            group = [v for v in np.where(colors == c)[0] if v not in evid]
            c_max = max(c_max, len(group))
    bn = graph.source
    n_children = np.zeros(graph.n_nodes, np.int64)
    for j, ps in enumerate(bn.parents):
        for p in ps:
            n_children[p] += 1
    f_max = int(n_children.max() + 1) if graph.n_nodes else 0
    s_max = max((len(ps) + 1 for ps in bn.parents), default=0)
    return c_max, f_max, s_max


def _bn_arena_size(bn) -> int:
    # flat log-CPT arena: dummy entry 0 + every CPT flattened
    return 1 + sum(int(np.prod(np.shape(cpt))) for cpt in bn.cpts)


def _ky_words(v: int, sampler: str, precision: int = 16,
              max_retries: int = 8) -> int:
    # mirrors fused_gibbs_sweep's precision widening + word-count math
    weight_bits = 8 if sampler == "lut_ky" else 15
    precision = max(precision, weight_bits + max(v - 1, 1).bit_length() + 1)
    return -(-(precision * max_retries) // 32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def bn_fused_footprint(
    graph, n_chains: int, sampler: str = "lut_ky", shard_width: int = 1
) -> KernelFootprint:
    """Estimate `fused_gibbs_sweep`'s per-core VMEM residency for one
    model at one chain width (the batcher vmaps buckets over query lanes,
    which batches the *grid*, so per-step residency stays one lane's).

    Every buffer is counted in the kernel's planar layout: a plane is one
    (b_pad, c_pad) int32 array — chains padded to 8 sublanes, the round's
    nodes to whole 128-lane tiles — and the value block is (b_pad, n_pad).

    `shard_width > 1` models the sharded fused engine
    (`distributed.bn_fused_sharded`): each device's round kernel sees only
    its *owned node slice* — round-robin (or placement-mod) ownership
    caps the per-device group width at ceil(c_max / shard_width) — while
    the value block, CPT arena, and LUT stay fully resident (the psum
    merge needs whole-state values on every device)."""
    b = int(n_chains)
    b_pad = _round_up(b, SUBLANES)
    n_pad = _round_up(graph.n_nodes, KY_LANES)
    c, f, s = bn_group_envelope(graph)
    c = -(-c // max(1, int(shard_width)))  # per-device owned slice
    c_pad = _round_up(c, KY_LANES)
    v = max(graph.cards) if graph.cards else 0
    w = _ky_words(v, sampler)
    arena_rows = -(-_bn_arena_size(graph.source) // KY_LANES)
    plane = b_pad * c_pad
    block = b_pad * n_pad
    # pipelined operands are double-buffered; a table's rows pad to whole
    # 8-row sublane tiles
    inputs = {
        "value_block": 2 * 2 * block,  # vals_ref + resident out_ref
        # base (F rows), stride, scope_var, is_self (F S rows each), cards
        "round_tables": 2 * (_round_up(f, SUBLANES)
                             + 3 * _round_up(f * s, SUBLANES)
                             + SUBLANES) * c_pad,
        "inverse_map": 2 * SUBLANES * n_pad,
        "random_words": 2 * w * plane,
        "cpt_arena": 2 * _round_up(arena_rows, SUBLANES) * KY_LANES,
        "exp_lut": 2 * SUBLANES * KY_LANES,
    }
    inter = {
        "scope_vals": f * s * plane,  # chain values at every scope slot
        "candidates": (v + _GATHER_PLANES) * plane,  # logp + addr/gather
        # weights (kept for the argmax fallback), the v + 1 preprocessed
        # bins, the word planes and the walk's carry
        "ky_planes": (2 * v + 1 + w + _WALK_PLANES) * plane,
        "value_planes": 4 * block,  # state, inverse map, labels, merge
    }
    breakdown = {k: x * ITEM_BYTES for k, x in {**inputs, **inter}.items()}
    return KernelFootprint(
        kernel="bn_fused", model=graph.name, n_chains=b, sampler=sampler,
        input_bytes=sum(inputs.values()) * ITEM_BYTES,
        intermediate_bytes=sum(inter.values()) * ITEM_BYTES,
        breakdown=breakdown, shard_width=int(shard_width),
    )


def mrf_fused_footprint(
    graph, n_chains: int, sampler: str = "lut_ky", block_h: int = 32,
    shard_width: int = 1,
) -> KernelFootprint:
    """Estimate `mrf_half_step_kernel`'s residency for one model.  Chains
    (and bucket lanes) are vmapped over the kernel, which batches the
    *grid* — grid steps execute sequentially, so per-step residency is one
    chain's (block_h, W) tile regardless of `n_chains` (kept in the record
    for the fit-cache key and the report).

    `shard_width > 1` models the sharded fused engine
    (`distributed.mrf_fused_sharded` via `mrf_halo_half_step_kernel`):
    each device tiles its *local row slab* of height // shard_width rows,
    with the two ppermute'd halo rows and the traced row offset resident
    beside the tile."""
    b = int(n_chains)
    mrf = graph.source
    height, width = int(mrf.height), int(mrf.width)
    h_loc = -(-height // max(1, int(shard_width)))
    bh = min(block_h, h_loc)
    v = int(mrf.n_labels)
    sites = bh * width
    w = _ky_words(v, sampler)
    inputs = {
        "label_blocks": 4 * sites,  # prev/cur/next halo blocks + out
        "evidence_block": sites,
        "random_words": sites * w,
        "exp_lut": EXP_LUT_SIZE,
    }
    if shard_width > 1:
        # the slab-edge halo rows + the (1, 1) row-offset ref
        inputs["halo_rows"] = 2 * width + 1
    inter = {
        "neighbor_shifts": 4 * sites,
        "energies": (2 * v + 1) * sites,  # energies + z columns + e_max
        "ky_weights": 3 * sites * KY_LANES,  # w + m_ext + walk state
    }
    breakdown = {k: x * ITEM_BYTES for k, x in {**inputs, **inter}.items()}
    return KernelFootprint(
        kernel="mrf_fused", model=graph.name, n_chains=b, sampler=sampler,
        input_bytes=sum(inputs.values()) * ITEM_BYTES,
        intermediate_bytes=sum(inter.values()) * ITEM_BYTES,
        breakdown=breakdown, shard_width=int(shard_width),
    )


def estimate_footprint(
    graph, n_chains: int, sampler: str = "lut_ky", shard_width: int = 1
) -> KernelFootprint:
    if graph.kind == "bn":
        return bn_fused_footprint(graph, n_chains, sampler, shard_width)
    return mrf_fused_footprint(graph, n_chains, sampler,
                               shard_width=shard_width)


# fit verdicts memoized by content hash — bucket_key calls this per query,
# so the steady-state cost must be a dict hit, not a DSATUR run
_FIT_CACHE: dict[tuple, bool] = {}


def fused_fits(graph, n_chains: int, sampler: str = "lut_ky",
               shard_width: int = 1) -> bool:
    """Demotion oracle for `runtime.batcher.fused_eligible`: does this
    (model, chain width, sampler, mesh-slice width) bucket fit the fused
    kernel's VMEM budget?  False means "route unfused" — bit-exact, just
    slower — instead of OOMing on device.  Sharded buckets
    (`shard_width > 1`) are judged on the per-shard envelope, since that
    is what each device of the shard_map body actually allocates."""
    key = (graph.ir_key, int(n_chains), sampler, int(shard_width),
           _VMEM_BUDGET)
    hit = _FIT_CACHE.get(key)
    if hit is None:
        fp = estimate_footprint(graph, n_chains, sampler, shard_width)
        hit = fp.total_bytes <= _VMEM_BUDGET
        _FIT_CACHE[key] = hit
    return hit


def lint_kernels(
    graphs, n_chains: int = 32, sampler: str = "lut_ky",
    budget: int | None = None, demotable: bool = True,
) -> list[Finding]:
    """Footprint findings for a set of IRs — the CLI/CI entry point."""
    out: list[Finding] = []
    for g in graphs:
        out.extend(
            estimate_footprint(g, n_chains, sampler).findings(
                budget, demotable=demotable
            )
        )
    return out
