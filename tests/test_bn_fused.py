"""Fused Pallas BN color-round kernel: bit-exactness matrix against the
unfused engines (samplers x backends x carry-state slice boundaries x
runtime evidence clamps, all under interpret mode), the loud-failure
guarantee for unsupported samplers, the first-use fused cross-check, the
fused serving route, and the chain-state buffer-donation satellite."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import (
    FUSED_BN_SAMPLERS,
    BackendMismatch,
    canonicalize,
    clear_program_cache,
    compile_graph,
    cross_check_fused,
    lower_schedule,
)
from repro.core import bayesnet as bnet
from repro.core.draws import SAMPLERS
from repro.core.graphs import bn_repository_replica, random_bayesnet
from repro.kernels import bn_gibbs
from repro.runtime import Engine, EngineConfig, Query, bucket_key, \
    execute_bucket, zipf_trace


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_program_cache()
    yield
    clear_program_cache()


# ---------------------------------------------------------------------------
# Kernel-level: fused_gibbs_sweep == gibbs_sweep, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampler", FUSED_BN_SAMPLERS)
@pytest.mark.parametrize("workload", ["survey", "alarm"])
def test_fused_sweep_bit_exact(workload, sampler):
    """The tentpole invariant at its smallest scope: one fused sweep (all
    rounds in one pallas_call, values VMEM-resident) equals the unfused
    sweep's bits — same key, same gather tensors."""
    cbn = bnet.compile_bayesnet(bn_repository_replica(workload))
    fr = bn_gibbs.build_fused_rounds(cbn.groups)
    vals, _ = bnet.init_chain_values(cbn, jax.random.key(0), 3)
    key = jax.random.key(11)
    ref = bnet.gibbs_sweep(cbn, vals, key, sampler)
    fus = bn_gibbs.fused_gibbs_sweep(cbn, fr, vals, key, sampler,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


def test_fused_sweep_wide_cards_bit_exact():
    """Heterogeneous cardinalities exercise the NEG_INF card mask and the
    per-node rejection-bin placement."""
    bn = random_bayesnet(14, max_parents=3, cards=(2, 6), seed=9)
    cbn = bnet.compile_bayesnet(bn)
    fr = bn_gibbs.build_fused_rounds(cbn.groups)
    vals, _ = bnet.init_chain_values(cbn, jax.random.key(1), 4)
    for sampler in FUSED_BN_SAMPLERS:
        key = jax.random.key(23)
        ref = bnet.gibbs_sweep(cbn, vals, key, sampler)
        fus = bn_gibbs.fused_gibbs_sweep(cbn, fr, vals, key, sampler,
                                         interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


def test_fused_sweep_two_lane_tiles_bit_exact():
    """A colour round wider than 128 nodes lays its nodes on two lane tiles
    and the 300-node value block on three: every cross-tile gather (scope
    values, the labels back through the inverse map) picks its tile by the
    index's high bits, as on pigs."""
    bn = random_bayesnet(300, max_parents=2, cards=3, seed=5)
    cbn = bnet.compile_bayesnet(bn)
    fr = bn_gibbs.build_fused_rounds(cbn.groups)
    assert fr.c_max > bn_gibbs.LANES
    assert fr.cards.shape[-1] == 2 * bn_gibbs.LANES
    vals, _ = bnet.init_chain_values(cbn, jax.random.key(2), 2)
    key = jax.random.key(29)
    ref = bnet.gibbs_sweep(cbn, vals, key, "lut_ky")
    fus = bn_gibbs.fused_gibbs_sweep(cbn, fr, vals, key, "lut_ky",
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


# ---------------------------------------------------------------------------
# Program-level matrix: fused vs both unfused backends, clamps, slices
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampler", FUSED_BN_SAMPLERS)
@pytest.mark.parametrize("workload", ["survey", "alarm"])
def test_bn_fused_run_bit_exact(workload, sampler):
    prog = compile_graph(bn_repository_replica(workload), evidence={0: 0})
    kwargs = dict(n_chains=3, n_iters=8, burn_in=2, sampler=sampler)
    marg_e, vals_e = prog.run(jax.random.key(9), backend="eager", **kwargs)
    marg_s, vals_s = prog.run(jax.random.key(9), backend="schedule",
                              **kwargs)
    marg_f, vals_f = prog.run(jax.random.key(9), backend="schedule",
                              fused=True, **kwargs)
    for other_v, other_m in ((vals_e, marg_e), (vals_s, marg_s)):
        np.testing.assert_array_equal(np.asarray(vals_f), np.asarray(other_v))
        np.testing.assert_array_equal(np.asarray(marg_f), np.asarray(other_m))
    assert sampler in prog._fused_checked  # first-use cross-check ran


@pytest.mark.parametrize("sampler", FUSED_BN_SAMPLERS)
def test_bn_fused_clamped_and_sliced_bit_exact(sampler):
    """The full serving shape at once: runtime evidence clamps + a slice
    boundary mid-burn-in + thinning mid-stride, fused == unfused == the
    uninterrupted run, marginals included."""
    bn = random_bayesnet(10, max_parents=2, cards=(2, 3), seed=3)
    prog = compile_graph(canonicalize(bn, evidence_mode="runtime"))
    kw = dict(n_chains=3, burn_in=4, thin=2, sampler=sampler,
              evidence={1: 0, 5: 1})
    m_ref, v_ref = prog.run(jax.random.key(1), n_iters=9, **kw)
    m_f, v_f = prog.run(jax.random.key(1), n_iters=9, fused=True, **kw)
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_ref))
    # slice the fused run at 3 + 6 (burn-in still in progress at the cut)
    _, _, st = prog.run(jax.random.key(1), n_iters=3, return_state=True,
                        fused=True, **kw)
    m_s, v_s = prog.run(None, n_iters=6, carry_state=st, fused=True, **kw)
    np.testing.assert_array_equal(np.asarray(v_s), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_ref))


def test_fused_unsupported_sampler_raises():
    """fused=True on a sampler the kernel does not implement must raise —
    at run(), and in the loop itself — never silently fall back to the
    unfused path (the caller asked for an execution route, not a hint)."""
    prog = compile_graph(bn_repository_replica("survey"))
    for sampler in set(SAMPLERS) - set(FUSED_BN_SAMPLERS):
        with pytest.raises(ValueError, match="fused BN rounds"):
            prog.run(jax.random.key(0), n_chains=2, n_iters=2,
                     backend="schedule", fused=True, sampler=sampler)
        with pytest.raises(ValueError, match="fused BN rounds"):
            bnet.gibbs_run_loop(
                prog.cbn, prog.cbn.groups,
                jnp.zeros((2, prog.ir.n_nodes), jnp.int32),
                jax.random.key(0), 2, 0, sampler, fused=True,
            )
    with pytest.raises(ValueError):  # fused still needs the schedule backend
        prog.run(jax.random.key(0), backend="eager", fused=True)


def test_fused_cross_check_catches_divergence():
    """The first-use guard really guards: an executable whose rounds were
    corrupted (reversed order => different key-to-round pairing) must be
    flagged as a backend mismatch before fused execution ever serves."""
    prog = compile_graph(bn_repository_replica("alarm"), evidence={0: 0})
    ex = lower_schedule(prog)
    ex.round_groups = list(reversed(ex.round_groups))
    with pytest.raises(BackendMismatch, match="fused"):
        cross_check_fused(prog, ex)


# ---------------------------------------------------------------------------
# Serving: fused buckets, engine route, donation
# ---------------------------------------------------------------------------


def test_fused_bucket_bit_exact_and_eligibility():
    bn = random_bayesnet(9, max_parents=2, cards=(2, 3), seed=5)
    graph = canonicalize(bn, evidence_mode="runtime")
    prog = compile_graph(graph, pipeline="runtime")
    mk = lambda qid, seed, sampler="lut_ky": Query(
        qid=qid, model="m", evidence={1: 0}, n_chains=2, n_iters=6,
        burn_in=2, seed=seed, sampler=sampler,
    )
    qs = [mk(0, 11), mk(1, 22)]
    key_u = bucket_key(qs[0], graph, "schedule")
    key_f = bucket_key(qs[0], graph, "schedule", fused=True)
    assert not key_u.fused and key_f.fused
    ref = execute_bucket(prog, key_u, qs)
    fus = execute_bucket(prog, key_f, qs)
    for r, f in zip(ref, fus):
        np.testing.assert_array_equal(r.final_state, f.final_state)
        np.testing.assert_array_equal(r.marginals, f.marginals)
    # ineligible signatures demote to the unfused route instead of failing
    # mixed traffic (the run() API raises; the bucket router serves)
    assert not bucket_key(mk(2, 3, "cdf"), graph, "schedule",
                          fused=True).fused
    assert not bucket_key(mk(3, 4), graph, "eager", fused=True).fused


def test_engine_fused_matches_unfused():
    """An engine with fused=True serves byte-identical posteriors — the
    knob is pure service time, never an answer change."""
    out = {}
    for fused in (False, True):
        clear_program_cache()
        models, queries = zipf_trace(10, quick=True, seed=0)
        eng = Engine(models, EngineConfig(fused=fused, slice_iters=8))
        eng.submit(queries)
        out[fused] = eng.run()
    assert out[False].keys() == out[True].keys()
    for qid in out[False]:
        a, b = out[False][qid], out[True][qid]
        np.testing.assert_array_equal(a.final_state, b.final_state)
        if a.marginals is not None:
            np.testing.assert_array_equal(a.marginals, b.marginals)


def test_engine_fused_requires_schedule_backend():
    models, _ = zipf_trace(2, quick=True, seed=0)
    with pytest.raises(ValueError, match="schedule"):
        Engine(models, EngineConfig(backend="eager", fused=True))


def test_carry_donation_no_copy():
    """Donation satellite: resuming from a carried chain state consumes it
    in place (no per-slice copy).  On platforms with buffer donation the
    donated leaves are deleted; either way the resumed bits must equal the
    uninterrupted run's."""
    bn = random_bayesnet(8, max_parents=2, cards=(2, 3), seed=1)
    prog = compile_graph(canonicalize(bn, evidence_mode="runtime"))
    kw = dict(n_chains=2, burn_in=0, sampler="lut_ky")
    m_ref, v_ref = prog.run(jax.random.key(4), n_iters=7, **kw)
    _, _, st = prog.run(jax.random.key(4), n_iters=3, return_state=True,
                        **kw)
    donated_vals = st.vals
    m2, v2 = prog.run(None, n_iters=4, carry_state=st, **kw)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(m_ref))
    # CPU/TPU/GPU all support donation in the supported jax range; the
    # (B, n) vals leaf aliases the output, so the input must be gone
    assert donated_vals.is_deleted()


def test_stacked_bucket_carry_survives_donation():
    """The bucket executables donate the *stacked* carry, which is built
    fresh per dispatch — the per-query chain states must stay live so a
    continuation can be replayed into a different bucket."""
    bn = random_bayesnet(8, max_parents=2, cards=(2, 3), seed=2)
    graph = canonicalize(bn, evidence_mode="runtime")
    prog = compile_graph(graph, pipeline="runtime")
    q = Query(qid=0, model="m", evidence={1: 0}, n_chains=2, n_iters=8,
              burn_in=2, seed=7)
    skey = bucket_key(q, graph, "schedule", slice_iters=4)
    r = execute_bucket(prog, skey, [q], return_state=True)[0]
    cont = dataclasses.replace(q, carry=r.carry, n_iters=4)
    rkey = bucket_key(cont, graph, "schedule", slice_iters=4)
    a = execute_bucket(prog, rkey, [cont])[0]
    # the same carry again, in a two-query bucket: still usable, same bits
    b = execute_bucket(prog, rkey, [cont, cont])[0]
    np.testing.assert_array_equal(a.final_state, b.final_state)
