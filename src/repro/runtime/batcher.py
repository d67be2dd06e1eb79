"""Query batching: group, pad, and vmap posterior queries onto programs.

The unit of execution is a *bucket*: every pending query that resolves to
the same compiled program AND the same static execution signature (BN
observed-node set, chain/iteration budget, sampler, backend).  Within a
bucket only per-query *data* varies — evidence values, pin masks,
observation images, PRNG seeds — so the whole microbatch runs as one
`jax.vmap` over one jitted executable: one dispatch answers Q queries.

Buckets are padded up to a fixed ladder of sizes (1, 2, 4, ...) so the jit
cache holds a handful of shapes per bucket signature instead of one per
occupancy; pad lanes replicate query 0 and their results are dropped.

vmap is semantics-preserving in JAX, so a query's draw stream inside a
microbatch is bit-identical to running it alone — asserted by
tests/test_runtime.py, which is what makes batched serving a pure
throughput win, never an answer change.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import kernel_lint
from repro.compile import backend as backend_mod
from repro.core import compat
from repro.core import mrf as mrf_mod
from repro.obs import profile as profile_mod
from repro.obs import tracer
from repro.kernels.bn_gibbs import FUSED_BN_SAMPLERS

PAD_SIZES = (1, 2, 4, 8, 16, 32)


def fused_eligible(
    kind: str, sampler: str, backend: str,
    graph=None, n_chains: int | None = None, shard_width: int = 1,
) -> bool:
    """Whether a bucket's static signature can route onto the fused Pallas
    executables: schedule backend + a sampler the kernels implement (BN:
    lut_ky/exact_ky; MRF: lut_ky).  Eligibility is decided here — per
    bucket, from statics alone — so an engine with `fused=True` serves
    eligible buckets fused and the rest unfused, instead of rejecting
    mixed traffic the way the single-program `run(fused=True)` API does.

    With `graph` and `n_chains` (the `bucket_key` route supplies both),
    eligibility additionally requires the static VMEM estimate to fit the
    budget (`analysis.kernel_lint.fused_fits`): an oversized bucket —
    wide replica × deep chain width — is demoted to the unfused route
    here, on estimate, instead of OOMing on device at dispatch.  The
    verdict is memoized per (ir_key, n_chains, sampler, width, budget),
    so the steady-state per-query cost is a dict hit.

    `shard_width > 1` (a bucket the engine will route sharded) budgets
    the *per-shard* envelope — each device holds its local row slab plus
    two halo rows (MRF) or its owned node slice (BN), not the whole
    model — which is the estimate the shard_map body actually allocates
    under.  (The too-few-devices fallback then runs the full-envelope
    vmap executable; the estimator is upper-ish enough that this only
    matters for models near the budget edge.)"""
    if backend != "schedule":
        return False
    if kind == "bn":
        if sampler not in FUSED_BN_SAMPLERS:
            return False
    elif sampler != "lut_ky":
        return False
    if graph is not None and n_chains is not None:
        return kernel_lint.fused_fits(
            graph, n_chains, sampler, shard_width=shard_width
        )
    return True


@dataclasses.dataclass
class Query:
    """One posterior-sampling request against a registered model.

    `carry` is engine-internal: a slice continuation is the same query
    re-entering the arrival queue with its chain state attached and
    `n_iters` counting the *remaining* sweeps — user-submitted queries
    leave it None."""

    qid: int
    model: str
    evidence: dict | None = None  # BN: {node: value} clamps; MRF: pins
    image: np.ndarray | None = None  # MRF observation image (H, W)
    n_chains: int = 8
    n_iters: int = 40
    burn_in: int = 10  # BN marginal accumulation only; ignored for MRF
    thin: int = 1  # BN marginal accumulation only; ignored for MRF
    sampler: str = "lut_ky"
    seed: int = 0
    arrival_s: float = 0.0
    carry: object = None  # chain state of a slice continuation


@dataclasses.dataclass
class QueryResult:
    """What the engine hands back: the posterior payload plus the timeline
    the simulated clock assigned to this query."""

    qid: int
    model: str
    kind: str  # "bn" | "mrf"
    marginals: np.ndarray | None  # BN: (n, V) streaming marginal estimate
    final_state: np.ndarray  # BN: (B, n) vals; MRF: (B, H, W) labels
    arrival_s: float = 0.0
    start_s: float = 0.0
    finish_s: float = 0.0
    batch_size: int = 1
    carry: object = None  # chain state, when the bucket ran return_state
    # diag.accum.QualitySnapshot.brief() of this lane's accumulator, when
    # the bucket ran with diagnostics (intermediate slices carry the
    # snapshot as-of-that-slice; the final slice's is the query's verdict)
    quality: dict | None = None

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """Everything that must be *static* across a microbatch.

    `n_iters` is the sweeps *this dispatch* runs — under slicing that is
    one slice, not the query's whole budget, which is how a long query's
    second slice can share a bucket with another long query that asked for
    a different total.  `resumed` separates fresh buckets (executable
    initializes chains from seeds) from continuation buckets (executable
    resumes carried chain state) — they are different jit programs.
    `fused` routes the bucket through the fused Pallas round kernels
    (bit-exact with unfused, but a different jit program — and a different
    calibration signature, since its service time differs).  `diagnostics`
    threads the streaming quality accumulator through the bucket (also a
    different jit program: the chain-state pytree grows the accumulator
    subtree) — per-lane draw streams stay bit-identical either way."""

    program_key: str
    kind: str
    clamp_nodes: tuple[int, ...]  # BN observed-node set; () for MRF
    has_pins: bool  # MRF: whether pin arrays ride along
    n_chains: int
    n_iters: int
    burn_in: int
    thin: int
    sampler: str
    backend: str
    resumed: bool = False
    fused: bool = False
    diagnostics: bool = False


def bucket_key(
    query: Query, graph, backend: str, slice_iters: int | None = None,
    fused: bool = False, diagnostics: bool = False, shard_width: int = 1,
) -> BucketKey:
    """The bucket a query lands in, derived without compiling anything
    (`graph` is the model's structure-only IR from engine registration).

    MRF execution has no burn-in/thinning concept (it returns final
    states), so those fields are normalized to 0/1 for MRF queries — both
    to make the "ignored" semantics explicit and so queries differing only
    in dead fields share a bucket instead of splintering microbatches.

    With `slice_iters`, a query whose remaining budget exceeds it lands in
    a bucket that runs exactly one slice; the engine re-enqueues the rest
    as a continuation (`query.carry` set, `n_iters` = what remains).

    `fused=True` (the engine config knob) routes *eligible* buckets onto
    the fused Pallas executables (`fused_eligible`); ineligible buckets
    keep the unfused route — never a silent answer change, since fused and
    unfused are bit-exact for every eligible signature.  `shard_width`
    (the engine supplies the slice width when the bucket will route
    sharded) makes the VMEM eligibility check budget the per-shard
    envelope instead of the whole model."""
    if graph.kind == "bn":
        clamp = tuple(sorted(int(k) for k in (query.evidence or {})))
        has_pins = False
        burn_in, thin = query.burn_in, query.thin
    else:
        clamp = ()
        has_pins = bool(query.evidence)
        burn_in, thin = 0, 1
    n_iters = query.n_iters
    if slice_iters is not None:
        n_iters = min(n_iters, slice_iters)
    return BucketKey(
        program_key=graph.ir_key,
        kind=graph.kind,
        clamp_nodes=clamp,
        has_pins=has_pins,
        n_chains=query.n_chains,
        n_iters=n_iters,
        burn_in=burn_in,
        thin=thin,
        sampler=query.sampler,
        backend=backend,
        resumed=query.carry is not None,
        fused=fused and fused_eligible(
            graph.kind, query.sampler, backend,
            graph=graph, n_chains=query.n_chains, shard_width=shard_width,
        ),
        diagnostics=diagnostics,
    )


def pad_size(n: int, sizes=PAD_SIZES) -> int:
    """Next bucket-ladder size >= n.  Beyond the ladder the batch runs at
    its exact occupancy — correct, but each distinct size is its own XLA
    compile, which is why the engine refuses max_batch > max(pad_sizes)."""
    for s in sizes:
        if n <= s:
            return s
    return n


def _seed_array(queries) -> jax.Array:
    """Per-query PRNG seeds, shipped as one uint32 array; the bucket
    executables derive `jax.random.key(seed)` per lane *inside* jit (one
    transfer instead of Q typed-key dispatches, same bits as the
    single-query path creating its key on the host)."""
    return jnp.asarray([q.seed for q in queries], jnp.uint32)


# ---------------------------------------------------------------------------
# vmapped bucket executables (jitted once per bucket signature + pad size)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_chains", "n_iters", "burn_in", "thin", "sampler", "return_state",
        "fused", "interpret",
    ),
    # the stacked carry is built fresh per dispatch (`_stack_carries`), so
    # donating it costs callers nothing and spares the per-slice state copy
    donate_argnames=("carry_q",),
)
def _bn_bucket(
    cbn, groups, ev_vals_q, ev_mask, seeds_q, carry_q, totals_q=None, *,
    n_chains, n_iters, burn_in, thin, sampler, return_state,
    fused=False, interpret=False,
):
    """One vmapped BN microbatch.  `carry_q` is a lane-stacked
    `BNChainState` for a resumed (continuation) bucket — then the seeds are
    dead lanes and chains resume instead of initializing; fresh buckets
    pass carry_q=None.  Either way the per-lane bits equal the single-query
    path with the same carry/seed — fused buckets included (the Pallas
    round kernel vmaps like any other jax computation).

    `totals_q` ((Q,) int32, fresh diagnostics buckets only) carries each
    lane's *total* sweep budget — the accumulator's split point must come
    from the query's whole budget even when this dispatch runs one slice
    of it.  Totals are lane data, so lanes with different budgets share
    the bucket like they always did."""

    def one(ev_vals, seed, carry, diag_total=None):
        return backend_mod.bn_rounds_core(
            cbn, groups, jax.random.key(seed), n_chains=n_chains,
            n_iters=n_iters, burn_in=burn_in, sampler=sampler, thin=thin,
            clamp_vals=ev_vals, clamp_mask=ev_mask,
            carry=carry, return_state=return_state,
            fused=fused, interpret=interpret, diag_total=diag_total,
        )

    if carry_q is None and totals_q is None:
        return jax.vmap(lambda e, s: one(e, s, None))(ev_vals_q, seeds_q)
    if carry_q is None:
        return jax.vmap(
            lambda e, s, t: one(e, s, None, t)
        )(ev_vals_q, seeds_q, totals_q)
    return jax.vmap(one)(ev_vals_q, seeds_q, carry_q)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mrf", "parities", "n_chains", "n_iters", "sampler", "fused",
        "interpret", "eager", "return_state",
    ),
    # see _bn_bucket: the stacked carry is dispatch-local, donate it
    donate_argnames=("carry_q",),
)
def _mrf_bucket(
    mrf, parities, imgs_q, seeds_q, pmask_q, pvals_q, carry_q,
    totals_q=None, *,
    n_chains, n_iters, sampler, fused, interpret, eager, return_state,
):
    def one(img, seed, pm, pv, carry, diag_total=None):
        key = jax.random.key(seed)
        if eager:
            return mrf_mod.mrf_gibbs_loop(
                mrf, img, key, n_chains, n_iters, sampler,
                pin_mask=pm, pin_vals=pv,
                carry=carry, return_state=return_state,
                diag_total=diag_total,
            )
        return backend_mod.mrf_rounds_core(
            mrf, parities, img, key, n_chains=n_chains, n_iters=n_iters,
            sampler=sampler, fused=fused, interpret=interpret,
            pin_mask=pm, pin_vals=pv,
            carry=carry, return_state=return_state,
            diag_total=diag_total,
        )

    if carry_q is None and totals_q is not None:
        if pmask_q is None:
            return jax.vmap(
                lambda i, s, t: one(i, s, None, None, None, t)
            )(imgs_q, seeds_q, totals_q)
        return jax.vmap(
            lambda i, s, pm, pv, t: one(i, s, pm, pv, None, t)
        )(imgs_q, seeds_q, pmask_q, pvals_q, totals_q)
    if pmask_q is None and carry_q is None:
        return jax.vmap(
            lambda i, s: one(i, s, None, None, None)
        )(imgs_q, seeds_q)
    if pmask_q is None:
        return jax.vmap(
            lambda i, s, c: one(i, s, None, None, c)
        )(imgs_q, seeds_q, carry_q)
    if carry_q is None:
        return jax.vmap(
            lambda i, s, pm, pv: one(i, s, pm, pv, None)
        )(imgs_q, seeds_q, pmask_q, pvals_q)
    return jax.vmap(one)(imgs_q, seeds_q, pmask_q, pvals_q, carry_q)


# ---------------------------------------------------------------------------
# bucket execution
# ---------------------------------------------------------------------------


def _stack_carries(padded: list[Query]):
    """Lane-stack the per-query chain states of a resumed bucket (pad lanes
    replicate query 0's state, mirroring the seed/evidence padding)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[q.carry for q in padded]
    )


def _lane_state(states, i: int):
    """Un-stack lane i of a vmapped chain-state pytree."""
    return jax.tree_util.tree_map(lambda x: x[i], states)


def execute_bucket(
    program,
    key: BucketKey,
    queries: list[Query],
    pad_sizes=PAD_SIZES,
    return_state: bool = False,
) -> list[QueryResult]:
    """Run one microbatch through its program and unpack per-query results.

    Pads the query list up to the bucket ladder (replicating query 0 —
    their lanes compute but are discarded), stacks the per-query runtime
    data, and dispatches a single vmapped executable.

    A `resumed` bucket stacks the queries' carried chain states and resumes
    them instead of seeding fresh chains; `return_state=True` attaches each
    lane's post-run chain state to its `QueryResult.carry`, which is how
    the engine slices long queries (continuous batching).  Both are
    bit-preserving: a lane resumed here equals the same query resumed
    standalone, whatever its batch-mates.

    A `diagnostics` bucket additionally threads the streaming quality
    accumulator through every lane and summarizes it into
    `QueryResult.quality` (the chain state is requested internally either
    way, but only attached to `carry` when the caller asked).

    Four wall spans split the call (`repro.obs.tracer`): `batch/prepare`
    (padding, evidence and seed arrays, carry stacking, the executable
    lookup and the fused first-use guard), `batch/launch` (the bucket
    executable's call, until it returns), `batch/fetch` (the host blocked
    on the device, and the copy back) and `batch/unpack` (the per-query
    results: lane states and quality)."""
    n_real = len(queries)
    return _execute_bucket(
        program, key, queries, n_real, pad_size(n_real, pad_sizes),
        return_state,
    )


def _lane_quality(states, i: int, cards=None, free_mask=None) -> dict:
    """Summarize lane i's quality accumulator into the brief scalar dict."""
    from repro.diag import accum as diag_accum

    lane = _lane_state(states, i)
    return diag_accum.summarize(
        lane.quality, cards=cards, free_mask=free_mask
    ).brief()


def _execute_bucket(
    program, key: BucketKey, queries: list[Query],
    n_real: int, n_pad: int, return_state: bool,
) -> list[QueryResult]:
    # diagnostics needs the post-run chain state (the accumulator lives
    # there) even when the caller doesn't want the carry back
    run_state = return_state or key.diagnostics
    with tracer.span("batch/prepare", cat="batch"):
        padded = list(queries) + [queries[0]] * (n_pad - n_real)
        prepare = _prepare_bn if key.kind == "bn" else _prepare_mrf
        bucket, a, kw, unpack = prepare(program, key, padded, run_state)
        if profile_mod.enabled():
            profile_mod.capture_bucket(
                program, key, n_pad, bucket, a, kw, model=queries[0].model,
            )
    with tracer.span("batch/launch", cat="batch"):
        out = bucket(*a, **kw)
    with tracer.span("batch/fetch", cat="batch"):
        if key.kind == "bn":
            host = (np.asarray(out[0]), np.asarray(out[1]))
            states = out[2] if run_state else None
        else:
            labels, states = out if run_state else (out, None)
            host = np.asarray(labels)
    with tracer.span("batch/unpack", cat="batch"):
        return unpack(host, states, queries, n_real, return_state)


def _totals(key: BucketKey, padded: list[Query]):
    """Each lane's total sweep budget, for a fresh diagnostics bucket: the
    accumulator splits at its query's *total* budget — a fresh query's
    n_iters is that total (the engine rewrites n_iters only on continuation
    re-enqueues)."""
    if key.diagnostics and not key.resumed:
        return jnp.asarray([q.n_iters for q in padded], jnp.int32)
    return None


def _prepare_bn(program, key: BucketKey, padded: list[Query],
                run_state: bool):
    """(executable, args, static kwargs, unpack) of a BN bucket."""
    seeds_q = _seed_array(padded)
    carry_q = _stack_carries(padded) if key.resumed else None
    n = program.ir.n_nodes
    ev_mask = np.zeros(n, bool)
    ev_mask[list(key.clamp_nodes)] = True
    ev_vals = np.zeros((len(padded), n), np.int64)
    for i, q in enumerate(padded):
        for node, val in (q.evidence or {}).items():
            ev_vals[i, int(node)] = int(val)
    groups = program.clamped_executable(key.clamp_nodes, key.backend)
    if key.fused:
        # same first-use guarantee the single-program path gets
        program.ensure_fused_cross_check(key.sampler)
    a = (
        program.cbn, groups, jnp.asarray(ev_vals, jnp.int32),
        jnp.asarray(ev_mask), seeds_q, carry_q, _totals(key, padded),
    )
    kw = dict(
        n_chains=key.n_chains, n_iters=key.n_iters, burn_in=key.burn_in,
        thin=key.thin, sampler=key.sampler, return_state=run_state,
        fused=key.fused, interpret=compat.pallas_interpret(),
    )

    def unpack(host, states, queries, n_real, return_state):
        marg, vals = host
        cards = np.asarray(program.cbn.cards)
        return [
            QueryResult(
                qid=q.qid, model=q.model, kind="bn", marginals=marg[i],
                final_state=vals[i], arrival_s=q.arrival_s,
                batch_size=n_real,
                carry=_lane_state(states, i) if return_state else None,
                quality=_lane_quality(states, i, cards=cards,
                                      free_mask=~ev_mask)
                if key.diagnostics else None,
            )
            for i, q in enumerate(queries)
        ]

    return _bn_bucket, a, kw, unpack


def _prepare_mrf(program, key: BucketKey, padded: list[Query],
                 run_state: bool):
    """(executable, args, static kwargs, unpack) of an MRF bucket."""
    seeds_q = _seed_array(padded)
    carry_q = _stack_carries(padded) if key.resumed else None
    mrf = program.mrf
    imgs = jnp.asarray(
        np.stack([np.asarray(q.image, np.int32) for q in padded])
    )
    pmask_q = pvals_q = None
    if key.has_pins:
        masks, vals = [], []
        for q in padded:
            m, v = backend_mod.pin_arrays(mrf, q.evidence or {})
            masks.append(m)
            vals.append(v)
        pmask_q, pvals_q = jnp.stack(masks), jnp.stack(vals)
    if key.fused:
        # same first-use guarantee the single-program path gets
        program.ensure_fused_cross_check(key.sampler)
    if key.backend == "schedule":
        ex = program.schedule_executable()
        parities, eager = ex.parities, False
    else:
        parities, eager = (0, 1), True
    a = (mrf, parities, imgs, seeds_q, pmask_q, pvals_q, carry_q,
         _totals(key, padded))
    kw = dict(
        n_chains=key.n_chains, n_iters=key.n_iters, sampler=key.sampler,
        fused=key.fused, interpret=compat.pallas_interpret(),
        eager=eager, return_state=run_state,
    )

    def mrf_free(i):
        if pmask_q is None:
            return None
        return ~np.asarray(pmask_q[i]).reshape(-1)

    def unpack(labels, states, queries, n_real, return_state):
        return [
            QueryResult(
                qid=q.qid, model=q.model, kind="mrf", marginals=None,
                final_state=labels[i], arrival_s=q.arrival_s,
                batch_size=n_real,
                carry=_lane_state(states, i) if return_state else None,
                quality=_lane_quality(states, i, free_mask=mrf_free(i))
                if key.diagnostics else None,
            )
            for i, q in enumerate(queries)
        ]

    return _mrf_bucket, a, kw, unpack
