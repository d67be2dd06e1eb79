"""Schedule-direct execution backend: the `Schedule` IS the execution plan.

Until this module existed, `CompiledProgram.run()` delegated to the eager
Gibbs engines and the round schedule the pass pipeline built was used only
for cost reporting.  Here the schedule is *lowered* to an executable:

  * BN: one CPT-gather tensor set (`ColorGroup`) per `Round`, built from the
    round's node list — not from `cbn.groups` — and swept in schedule order
    inside one jitted loop.  A future pass that merges tiny colors or splits
    a round changes execution through this lowering alone; `core/bayesnet.py`
    never hears about it.
  * MRF: each round is recognized as one checkerboard parity and executed in
    schedule order.  The default path is the vectorized engine math (bit-
    exact with eager for every sampler); `fused=True` routes `lut_ky` rounds
    through the Pallas kernel in `kernels/mrf_gibbs.py` (same random-word
    derivation as `draw_from_logits`, so still bit-identical).

Bit-exactness with the eager backend is not an aspiration but a checked
invariant: `cross_check()` runs both backends on a tiny budget and compares
bits; `CompiledProgram` invokes it the first time a program is lowered (and
eagerly at compile time under `compile_graph(..., cross_check=True)`).

The sharded counterpart lives in `core/distributed.py`
(`run_program_sharded(..., backend="schedule")`), which routes each round's
named comm mechanism onto its collective: `psum_broadcast` -> a per-round
`lax.psum` of the disjoint state delta, `ppermute_halo` -> the `lax.ppermute`
boundary exchange.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.compile.schedule import Schedule, verify_schedule
from repro.core import bayesnet as bnet
from repro.core import compat
from repro.core import mrf as mrf_mod
from repro.core.graphs import GridMRF
from repro.core.interp import build_exp_weight_lut
from repro.diag import accum as diag_accum
from repro.kernels import mrf_gibbs as mrf_kernels
from repro.kernels.bn_gibbs import FUSED_BN_SAMPLERS, check_fused_sampler
from repro.obs import tracer


class ScheduleLoweringError(RuntimeError):
    """The schedule cannot be lowered to this backend's execution form."""


class BackendMismatch(AssertionError):
    """The schedule backend produced different bits than the eager engine."""


# The schedule's named comm mechanisms and the collective each lowers to in
# the sharded execution path (core/distributed.py).
MECHANISM_COLLECTIVES = {
    "psum_broadcast": "lax.psum",
    "ppermute_halo": "lax.ppermute",
}


@dataclasses.dataclass
class BNScheduleExec:
    """A BN schedule lowered to per-round gather tensors."""

    cbn: bnet.CompiledBayesNet
    round_groups: list[bnet.ColorGroup]  # one per Round, schedule-ordered
    # runtime-evidence node set the groups were specialized for (static:
    # it determines the gather-tensor shapes); () = unclamped lowering
    clamp_nodes: tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class MRFScheduleExec:
    """A grid-MRF schedule lowered to a checkerboard parity sequence."""

    mrf: GridMRF
    parities: tuple[int, ...]  # per-round parity, schedule-ordered
    pinned: tuple[tuple[int, int], ...] = ()  # baked (site, label) pins


def lower_schedule(
    program, clamp_nodes: tuple[int, ...] = ()
) -> BNScheduleExec | MRFScheduleExec:
    """Lower a `CompiledProgram`'s schedule into an executable form.

    Legality is re-verified first: round-ordered execution is only correct if
    the rounds still partition the free RVs with no intra-round conflicts
    (a buggy future pass must fail here, not corrupt samples).

    `clamp_nodes` specializes a BN lowering for a runtime-evidence node set
    (`evidence_mode="runtime"` IRs): clamped nodes drop out of every round's
    gather tensors exactly as baked evidence drops out at compile time,
    which is what keeps the two paths bit-identical.  MRF pins need no
    specialization (the pin mask is a plain runtime array), so `clamp_nodes`
    must be empty for MRF programs; *baked* MRF pins ride in from the IR."""
    ir = program.ir
    schedule: Schedule = program.schedule
    verify_schedule(ir, schedule)
    if ir.kind == "bn":
        bn = ir.source
        clamp = set(clamp_nodes)
        bases = bnet.cpt_bases(bn)
        groups = bnet.build_clamped_groups(
            bn, [r.nodes for r in schedule.rounds], clamp, bases
        )
        if not groups:
            raise ScheduleLoweringError(
                "runtime evidence clamps every free RV; nothing to sample"
            )
        return BNScheduleExec(
            cbn=program.cbn, round_groups=groups, clamp_nodes=tuple(
                sorted(clamp))
        )
    if clamp_nodes:
        raise ScheduleLoweringError(
            "MRF pins are runtime arrays (run(pins=...)), not a lowering "
            "specialization"
        )
    mrf = ir.source
    pinned_sites = {node for node, _ in ir.evidence}
    class_size = {
        p: sum(
            (r + c) % 2 == p and (r * mrf.width + c) not in pinned_sites
            for r in range(mrf.height) for c in range(mrf.width)
        )
        for p in (0, 1)
    }
    parities = []
    for r in schedule.rounds:
        pars = {(v // mrf.width + v % mrf.width) % 2 for v in r.nodes}
        if len(pars) != 1:
            raise ScheduleLoweringError(
                f"MRF round {r.color} mixes checkerboard parities {pars}; "
                "the fused grid path needs single-parity rounds"
            )
        parity = pars.pop()
        if len(r.nodes) != class_size[parity]:
            # the grid path executes whole parity classes (minus baked
            # pins); a round holding only part of one (e.g. from a round-
            # splitting pass) has no lowering here and must fail loudly,
            # not run the wrong plan
            raise ScheduleLoweringError(
                f"MRF round {r.color} covers {len(r.nodes)} of the "
                f"{class_size[parity]} free parity-{parity} sites; partial-"
                "parity rounds are not loweable by the grid backend"
            )
        parities.append(parity)
    return MRFScheduleExec(
        mrf=mrf, parities=tuple(parities), pinned=ir.evidence
    )


def pin_arrays(
    mrf: GridMRF, pinned
) -> tuple[jax.Array, jax.Array]:
    """(site, label) pin pairs -> ((H, W) bool mask, (H, W) int32 values).
    Accepts a dict or an iterable of pairs; values are validated against
    the label alphabet."""
    import numpy as np

    mask = np.zeros((mrf.height, mrf.width), bool)
    vals = np.zeros((mrf.height, mrf.width), np.int64)
    items = pinned.items() if isinstance(pinned, dict) else pinned
    for site, lab in items:
        site, lab = int(site), int(lab)
        if not (0 <= site < mrf.height * mrf.width and
                0 <= lab < mrf.n_labels):
            raise ValueError(f"pinned pixel {site}={lab} out of range")
        mask[site // mrf.width, site % mrf.width] = True
        vals[site // mrf.width, site % mrf.width] = lab
    return jnp.asarray(mask), jnp.asarray(vals, jnp.int32)


# ---------------------------------------------------------------------------
# BN: round-ordered jitted sweep
# ---------------------------------------------------------------------------


def bn_rounds_core(
    cbn, round_groups, key, *, n_chains, n_iters, burn_in, sampler, thin=1,
    clamp_vals=None, clamp_mask=None, carry=None, return_state=False,
    fused=False, interpret=False,
    diag_total=None, diag_batch=diag_accum.DEFAULT_BATCH_LEN,
):
    """Un-jitted BN round sweep: init (with optional runtime clamps) + the
    shared `gibbs_run_loop`.  `run_bn_schedule` jits it; the serving batcher
    vmaps it over per-query (key, clamp_vals) with shared static groups.

    A `carry` (`bayesnet.BNChainState`) skips the init and resumes the
    chain exactly — the clamped values already live in the carried state and
    clamped nodes are absent from the (same) groups, so slicing a clamped
    run needs nothing beyond the state itself.

    `fused=True` routes every sweep through the Pallas kernel in
    `kernels/bn_gibbs.py` (lut_ky/exact_ky only — anything else raises);
    clamps need no extra handling because clamped nodes are absent from
    `round_groups` on both paths."""
    if carry is None:
        vals, key = bnet.init_chain_values(
            cbn, key, n_chains, clamp_vals=clamp_vals, clamp_mask=clamp_mask
        )
    else:
        vals = None
    return bnet.gibbs_run_loop(
        cbn, round_groups, vals, key, n_iters, burn_in, sampler, thin,
        carry=carry, return_state=return_state,
        fused=fused, interpret=interpret,
        diag_total=diag_total, diag_batch=diag_batch,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_chains", "n_iters", "burn_in", "sampler", "thin", "return_state",
        "fused", "interpret",
    ),
    # sliced serving: resume the carried chain state in place (the caller
    # must treat a passed carry as consumed — see bayesnet.run_gibbs)
    donate_argnames=("carry",),
)
def _run_bn_rounds(
    cbn, round_groups, key, clamp_vals, clamp_mask, carry, *,
    n_chains, n_iters, burn_in, sampler, thin, return_state,
    fused=False, interpret=False,
    diag_total=None, diag_batch=diag_accum.DEFAULT_BATCH_LEN,
):
    return bn_rounds_core(
        cbn, round_groups, key, n_chains=n_chains, n_iters=n_iters,
        burn_in=burn_in, sampler=sampler, thin=thin,
        clamp_vals=clamp_vals, clamp_mask=clamp_mask,
        carry=carry, return_state=return_state,
        fused=fused, interpret=interpret,
        diag_total=diag_total, diag_batch=diag_batch,
    )


def run_bn_schedule(
    ex: BNScheduleExec,
    key: jax.Array | None,
    *,
    clamp_vals: jax.Array | None = None,
    clamp_mask: jax.Array | None = None,
    **kwargs,
):
    """Execute a lowered BN schedule; same contract as `bayesnet.run_gibbs`
    (returns (marginals (n, V), final vals)).  For a clamped lowering
    (`ex.clamp_nodes` non-empty) `clamp_vals`/`clamp_mask` carry the
    per-query evidence values; the mask must cover exactly the nodes the
    lowering was specialized for.  Convenience unpacking of
    `bn_run_clamped` — one body, two spellings."""
    return bn_run_clamped(
        ex.cbn, ex.round_groups, clamp_vals, clamp_mask, key, **kwargs
    )


def bn_run_clamped(
    cbn,
    round_groups,
    clamp_vals: jax.Array | None,
    clamp_mask: jax.Array | None,
    key: jax.Array | None,
    *,
    n_chains: int = 32,
    n_iters: int = 200,
    burn_in: int = 50,
    sampler: str = "lut_ky",
    thin: int = 1,
    carry=None,
    return_state: bool = False,
    fused: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """Execute an already-specialized clamped grouping (from
    `CompiledProgram.clamped_executable`, either backend's) with per-query
    evidence values; same contract as `bayesnet.run_gibbs`.

    `fused=True` drives the sweeps through the Pallas BN kernel
    (lut_ky/exact_ky only — the kernel hard-codes the C1+C2 datapath);
    random words are derived exactly as `draw_from_logits` derives them, so
    the fused path stays bit-identical to the eager engine."""
    if fused:
        check_fused_sampler(sampler)
    interpret = compat.pallas_interpret()
    # host-level kernel entry span only: the rounds themselves run inside
    # jit/fori_loop where the tracer must never be called
    with tracer.span(
        "bn_rounds", cat="kernel", sampler=sampler, fused=fused,
        n_chains=n_chains, n_iters=n_iters, n_rounds=len(round_groups),
        resumed=carry is not None,
    ):
        return _run_bn_rounds(
            cbn, round_groups, key, clamp_vals, clamp_mask, carry,
            n_chains=n_chains, n_iters=n_iters, burn_in=burn_in,
            sampler=sampler, thin=thin, return_state=return_state,
            fused=fused, interpret=interpret,
            diag_total=diag_total, diag_batch=diag_batch,
        )


# ---------------------------------------------------------------------------
# MRF: schedule-ordered rounds, optionally fused through the Pallas kernel
# ---------------------------------------------------------------------------


def mrf_rounds_core(
    mrf, parities, evidence, key, *, n_chains, n_iters, sampler, fused,
    interpret, pin_mask=None, pin_vals=None, carry=None, return_state=False,
    diag_total=None, diag_batch=diag_accum.DEFAULT_BATCH_LEN,
):
    """Un-jitted schedule-ordered MRF sweep (the batcher vmaps this over
    per-query evidence images and pin masks — pins are runtime arrays, so
    one trace serves every pin pattern).  The fused Pallas kernel computes
    the whole parity update and pinned sites are restored afterwards, which
    matches the unfused path's masked `where` bit for bit because pinned
    sites always hold their pinned value going in.

    A `carry` (`mrf.MRFChainState`) skips the init and resumes the chain
    exactly — sliced runs are bit-exact with uninterrupted ones on the
    fused path too, because the per-iteration key-split structure is the
    carry itself."""
    exp_table, exp_spec = build_exp_weight_lut()
    if carry is None:
        labels, key = mrf_mod.init_labels(
            mrf, key, n_chains, pin_mask, pin_vals
        )
        quality = None
        if diag_total is not None:
            quality = diag_accum.make_accum(
                n_chains, mrf.height * mrf.width, mrf.n_labels,
                jnp.asarray(diag_total, jnp.int32), diag_batch,
            )
    else:
        labels, key, quality = carry.labels, carry.key, carry.quality

    def body(t, carry):
        labels, key, quality = carry
        ks = jax.random.split(key, 1 + len(parities))
        for i, parity in enumerate(parities):
            if fused:
                labels = mrf_kernels.mrf_round_step(
                    mrf, labels, evidence, ks[1 + i], parity,
                    exp_table, exp_spec, interpret=interpret,
                )
                if pin_mask is not None:
                    labels = jnp.where(pin_mask[None], pin_vals[None], labels)
            else:
                labels = mrf_mod.half_step(
                    mrf, labels, evidence, ks[1 + i], parity, sampler,
                    exp_table, exp_spec, pin_mask,
                )
        if quality is not None:
            onehot = (
                labels.reshape(labels.shape[0], -1)[..., None]
                == jnp.arange(mrf.n_labels, dtype=labels.dtype)
            ).astype(jnp.int32)
            quality = diag_accum.update(quality, onehot, jnp.asarray(True))
        return labels, ks[0], quality

    labels, key, quality = jax.lax.fori_loop(
        0, n_iters, body, (labels, key, quality)
    )
    if return_state:
        return labels, mrf_mod.MRFChainState(
            labels=labels, key=key, quality=quality
        )
    return labels


@functools.partial(
    jax.jit,
    static_argnames=(
        "mrf", "parities", "n_chains", "n_iters", "sampler", "fused",
        "interpret", "return_state",
    ),
    # sliced serving: resume the carried labels in place (a passed carry is
    # consumed — see bayesnet.run_gibbs)
    donate_argnames=("carry",),
)
def _run_mrf_rounds(
    mrf, parities, evidence, key, pin_mask, pin_vals, carry, *,
    n_chains, n_iters, sampler, fused, interpret, return_state,
    diag_total=None, diag_batch=diag_accum.DEFAULT_BATCH_LEN,
):
    return mrf_rounds_core(
        mrf, parities, evidence, key, n_chains=n_chains, n_iters=n_iters,
        sampler=sampler, fused=fused, interpret=interpret,
        pin_mask=pin_mask, pin_vals=pin_vals,
        carry=carry, return_state=return_state,
        diag_total=diag_total, diag_batch=diag_batch,
    )


def run_mrf_schedule(
    ex: MRFScheduleExec,
    evidence: jax.Array,
    key: jax.Array | None,
    *,
    n_chains: int = 32,
    n_iters: int = 200,
    sampler: str = "lut_ky",
    fused: bool = False,
    pin_mask: jax.Array | None = None,
    pin_vals: jax.Array | None = None,
    carry=None,
    return_state: bool = False,
    diag_total=None,
    diag_batch: int = diag_accum.DEFAULT_BATCH_LEN,
):
    """Execute a lowered MRF schedule; same contract as `mrf.run_mrf_gibbs`
    (returns final labels (B, H, W)).

    `fused=True` drives the rounds through the Pallas half-step kernel
    (lut_ky only — the kernel hard-codes the C1+C2 datapath); random words
    are derived exactly as `draw_from_logits` derives them, so the fused
    path stays bit-identical to the eager engine.

    Pins come from either the lowering (baked into the IR) or the caller
    (runtime queries) — `program.run()` guarantees they never both apply.
    `carry`/`return_state` slice the run: see `mrf_rounds_core`."""
    if fused and sampler != "lut_ky":
        raise ValueError(
            f"fused schedule rounds implement the lut_ky datapath only, "
            f"got sampler={sampler!r}"
        )
    if pin_mask is None and ex.pinned:
        pin_mask, pin_vals = pin_arrays(ex.mrf, ex.pinned)
    interpret = compat.pallas_interpret()
    # host-level kernel entry span only (see bn_run_clamped)
    with tracer.span(
        "mrf_rounds", cat="kernel", sampler=sampler, fused=fused,
        n_chains=n_chains, n_iters=n_iters, n_rounds=len(ex.parities),
        resumed=carry is not None, pinned=pin_mask is not None,
    ):
        return _run_mrf_rounds(
            ex.mrf, ex.parities, evidence, key, pin_mask, pin_vals, carry,
            n_chains=n_chains, n_iters=n_iters, sampler=sampler, fused=fused,
            interpret=interpret, return_state=return_state,
            diag_total=diag_total, diag_batch=diag_batch,
        )


# ---------------------------------------------------------------------------
# Bit-exactness cross-check between the two backends
# ---------------------------------------------------------------------------

_CHECK_KEY = 0xA1A  # fixed: the check must be deterministic per program
_CHECK_CHAINS = 2
_CHECK_ITERS = 3


def _check_evidence(mrf: GridMRF) -> jax.Array:
    """A fixed observation for the MRF checks: every value of the label
    alphabet somewhere, and every fifth pixel unobserved (-1)."""
    r = jnp.arange(mrf.height, dtype=jnp.int32)[:, None]
    c = jnp.arange(mrf.width, dtype=jnp.int32)[None, :]
    site = r * mrf.width + c
    return jnp.where(site % 5 == 0, -1, (7 * r + 3 * c) % mrf.n_labels)


def cross_check(program, ex=None) -> None:
    """Run both backends on a tiny budget and require identical bits.

    Raises `BackendMismatch` on any divergence — a cached program whose
    schedule execution drifted from the eager engines must never serve."""
    import numpy as np

    ex = lower_schedule(program) if ex is None else ex
    key = jax.random.key(_CHECK_KEY)
    if program.kind == "bn":
        marg_e, vals_e = bnet.run_gibbs(
            program.cbn, key, n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS,
            burn_in=0,
        )
        marg_s, vals_s = run_bn_schedule(
            ex, key, n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS, burn_in=0,
        )
        same = (np.asarray(vals_e) == np.asarray(vals_s)).all() and (
            np.asarray(marg_e) == np.asarray(marg_s)
        ).all()
    else:
        mrf = program.mrf
        ev = _check_evidence(mrf)
        pin_mask = pin_vals = None
        if program.ir.evidence:  # baked pins bind the eager side too
            pin_mask, pin_vals = pin_arrays(mrf, program.ir.evidence)
        lab_e = mrf_mod.run_mrf_gibbs(
            mrf, ev, key, n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS,
            pin_mask=pin_mask, pin_vals=pin_vals,
        )
        lab_s = run_mrf_schedule(
            ex, ev, key, n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS,
        )
        same = (np.asarray(lab_e) == np.asarray(lab_s)).all()
    if not same:
        raise BackendMismatch(
            f"schedule backend diverged from eager on program "
            f"{program.program_key[:12]} ({program.kind})"
        )


def _check_mesh(program, mesh=None):
    """A tiny mesh for the sharded cross-check leg: (1, w) over the host's
    devices, with w a legal shard width for the program (divides the MRF
    grid height; any width partitions BN nodes).  A single available device
    still exercises the full shard_map body (self-permute halos)."""
    if mesh is not None:
        return mesh
    n_dev = len(jax.devices())
    if program.kind == "mrf":
        h = program.mrf.height
        w = next(d for d in range(min(n_dev, h), 0, -1) if h % d == 0)
    else:
        w = min(2, n_dev)

    return compat.make_mesh((1, w), ("data", "model"))


def cross_check_fused(
    program, ex, sampler: str = "lut_ky", *, sharded: bool = False,
    mesh=None,
) -> None:
    """First-use guarantee for the fused kernel paths: before a Pallas
    round kernel ever serves a program, a tiny fused run must match the
    eager engine bit for bit (the eager side never touches the kernels, so
    a word-derivation or layout drift in `kernels/bn_gibbs.py` /
    `kernels/mrf_gibbs.py` is caught here, not in production posteriors).

    `sharded=True` additionally runs the one-shard_map-body engine
    (`core/distributed.py`) on a tiny mesh and requires its bits to match
    the single-device fused run AND (transitively) eager — the acceptance
    invariant of the sharded-fused datapath.  Checked lazily at first
    sharded-fused use (`CompiledProgram.ensure_fused_cross_check`), so
    single-device fused serving never pays the shard_map compile."""
    import numpy as np

    key = jax.random.key(_CHECK_KEY)
    kwargs = dict(n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS,
                  sampler=sampler)
    if program.kind == "bn":
        marg_e, vals_e = bnet.run_gibbs(program.cbn, key, burn_in=0,
                                        **kwargs)
        marg_f, vals_f = run_bn_schedule(ex, key, fused=True, burn_in=0,
                                         **kwargs)
        if not ((np.asarray(vals_e) == np.asarray(vals_f)).all()
                and (np.asarray(marg_e) == np.asarray(marg_f)).all()):
            raise BackendMismatch(
                f"fused BN rounds diverged from eager on program "
                f"{program.program_key[:12]} (sampler={sampler})"
            )
        if sharded:
            from repro.core import distributed as dist_mod

            marg_s, vals_s = dist_mod.run_program_sharded(
                program, key, _check_mesh(program, mesh), burn_in=0,
                backend="schedule", fused=True, **kwargs,
            )
            if not ((np.asarray(vals_s) == np.asarray(vals_f)).all()
                    and (np.asarray(marg_s) == np.asarray(marg_f)).all()):
                raise BackendMismatch(
                    f"sharded fused BN rounds diverged from single-device "
                    f"fused on program {program.program_key[:12]} "
                    f"(sampler={sampler})"
                )
        return
    mrf = program.mrf
    ev = _check_evidence(mrf)
    pin_mask = pin_vals = None
    if program.ir.evidence:  # baked pins bind the eager side too
        pin_mask, pin_vals = pin_arrays(mrf, program.ir.evidence)
    lab_e = mrf_mod.run_mrf_gibbs(
        mrf, ev, key, pin_mask=pin_mask, pin_vals=pin_vals, **kwargs
    )
    lab_f = run_mrf_schedule(ex, ev, key, fused=True, **kwargs)
    if not (np.asarray(lab_e) == np.asarray(lab_f)).all():
        raise BackendMismatch(
            f"fused MRF rounds diverged from eager on program "
            f"{program.program_key[:12]} (sampler={sampler})"
        )
    if sharded:
        from repro.core import distributed as dist_mod

        lab_s = dist_mod.run_program_sharded(
            program, key, _check_mesh(program, mesh), evidence=ev,
            backend="schedule", fused=True, **kwargs,
        )
        if not (np.asarray(lab_s) == np.asarray(lab_f)).all():
            raise BackendMismatch(
                f"sharded fused MRF rounds diverged from single-device "
                f"fused on program {program.program_key[:12]} "
                f"(sampler={sampler})"
            )


def cross_check_clamped(program, ex: BNScheduleExec) -> None:
    """The clamped-lowering counterpart of `cross_check`: before a runtime-
    evidence specialization ever serves, both backends run a tiny clamped
    budget (every clamped node observed at value 0, which every alphabet
    admits) and must agree bit for bit.  The eager side rebuilds its groups
    from `cbn.groups` independently of the schedule lowering, so a pass
    that breaks the rounds/groups correspondence is caught here too."""
    import numpy as np

    bn = program.ir.source
    clamp = ex.clamp_nodes
    clamp_vals = jnp.zeros(bn.n_nodes, jnp.int32)
    clamp_mask = jnp.zeros(bn.n_nodes, bool).at[jnp.asarray(
        clamp, jnp.int32)].set(True)
    key = jax.random.key(_CHECK_KEY)
    eager_groups = bnet.build_clamped_groups(
        bn, [np.asarray(g.nodes) for g in program.cbn.groups], clamp
    )
    kwargs = dict(
        n_chains=_CHECK_CHAINS, n_iters=_CHECK_ITERS, burn_in=0,
        sampler="lut_ky", thin=1,
    )
    marg_e, vals_e = _run_bn_rounds(
        program.cbn, eager_groups, key, clamp_vals, clamp_mask, None,
        return_state=False, **kwargs,
    )
    marg_s, vals_s = run_bn_schedule(
        ex, key, clamp_vals=clamp_vals, clamp_mask=clamp_mask, **kwargs
    )
    if not ((np.asarray(vals_e) == np.asarray(vals_s)).all()
            and (np.asarray(marg_e) == np.asarray(marg_s)).all()):
        raise BackendMismatch(
            f"clamped schedule backend diverged from eager on program "
            f"{program.program_key[:12]} (clamp={clamp})"
        )
