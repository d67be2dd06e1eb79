"""repro.runtime.executor subsystem: chain-state carry-over bit-exactness
(sliced == uninterrupted for every sampler x backend x fused), resumed
slices batched into foreign buckets, the multi-worker pool, measured-time
calibration, token-bucket admission + bounded queues, and the engine-level
continuous-batching guarantees."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compile import canonicalize, clear_program_cache, compile_graph
from repro.compile import ir as compile_ir
from repro.core import mrf as mrf_mod
from repro.core.draws import SAMPLERS
from repro.core.graphs import GridMRF, bn_repository_replica, random_bayesnet
from repro.runtime import (
    AdmissionConfig,
    AdmissionController,
    Calibrator,
    Engine,
    EngineConfig,
    Executor,
    ExecutorConfig,
    Query,
    RuntimeMetrics,
    WorkerPool,
    bucket_key,
    bursty_trace,
    execute_bucket,
    sig_of,
    zipf_trace,
)
from repro.runtime.admission import ADMIT, DEFER, SHED
from repro.runtime.metrics import BatchRecord, percentile


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_program_cache()
    yield
    clear_program_cache()


# ---------------------------------------------------------------------------
# Chain-state carry-over: sliced == uninterrupted, asserted bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampler", SAMPLERS)
@pytest.mark.parametrize("backend", ["eager", "schedule"])
def test_bn_sliced_run_bit_exact(sampler, backend):
    """The tentpole guarantee: a BN run sliced at an arbitrary boundary —
    burn-in still in progress, thinning mid-stride — equals the
    uninterrupted run bit for bit, marginals included."""
    bn = random_bayesnet(10, max_parents=2, cards=(2, 3), seed=3)
    prog = compile_graph(canonicalize(bn, evidence_mode="runtime"))
    kw = dict(n_chains=3, burn_in=4, thin=2, sampler=sampler,
              backend=backend, evidence={1: 0, 5: 1})
    m_full, v_full = prog.run(jax.random.key(1), n_iters=11, **kw)
    m1, v1, st = prog.run(
        jax.random.key(1), n_iters=3, return_state=True, **kw
    )
    m2, v2, st2 = prog.run(
        None, n_iters=5, carry_state=st, return_state=True, **kw
    )
    m3, v3 = prog.run(None, n_iters=3, carry_state=st2, **kw)
    np.testing.assert_array_equal(np.asarray(v_full), np.asarray(v3))
    np.testing.assert_array_equal(np.asarray(m_full), np.asarray(m3))


@pytest.mark.parametrize("sampler", SAMPLERS)
@pytest.mark.parametrize("backend,fused", [
    ("eager", False), ("schedule", False), ("schedule", True),
])
def test_mrf_sliced_run_bit_exact(sampler, backend, fused):
    """Same guarantee on the grid path, fused Pallas rounds included."""
    if fused and sampler != "lut_ky":
        pytest.skip("fused rounds implement the lut_ky datapath only")
    mrf = GridMRF(8, 8, 3, theta=1.1, h=1.5)
    prog = compile_graph(compile_ir.from_mrf(mrf))
    _, noisy = mrf_mod.make_denoising_problem(8, 8, 3, 0.25, seed=0)
    kw = dict(n_chains=2, sampler=sampler, evidence=jnp.asarray(noisy),
              backend=backend, fused=fused, pins={3: 1})
    full = prog.run(jax.random.key(5), n_iters=8, **kw)
    _, st = prog.run(jax.random.key(5), n_iters=3, return_state=True, **kw)
    resumed = prog.run(None, n_iters=5, carry_state=st, **kw)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(resumed))


def test_carry_state_validation():
    bn_prog = compile_graph(canonicalize(random_bayesnet(6, seed=0),
                                         evidence_mode="runtime"))
    mrf_prog = compile_graph(compile_ir.from_mrf(GridMRF(4, 4, 2)))
    img = jnp.zeros((4, 4), jnp.int32)
    _, _, bn_state = bn_prog.run(
        jax.random.key(0), n_chains=2, n_iters=2, burn_in=0,
        return_state=True,
    )
    with pytest.raises(TypeError):  # MRF state into a BN program
        _, mrf_state = mrf_prog.run(
            jax.random.key(0), n_chains=2, n_iters=2, evidence=img,
            return_state=True,
        )
        bn_prog.run(None, n_iters=2, burn_in=0, carry_state=mrf_state)
    with pytest.raises(TypeError):  # BN state into an MRF program
        mrf_prog.run(None, n_iters=2, evidence=img, carry_state=bn_state)
    with pytest.raises(ValueError):  # fresh run with no key
        bn_prog.run(None, n_iters=2)


def test_resumed_slice_in_foreign_bucket_bit_exact():
    """Satellite gate: a resumed slice batched with a *different* set of
    companions (it landed in another bucket than its first slice) still
    produces the uninterrupted run's bits — vmap lanes are independent and
    the carry is the whole chain state."""
    bn = random_bayesnet(9, max_parents=2, cards=(2, 3), seed=5)
    graph = canonicalize(bn, evidence_mode="runtime")
    prog = compile_graph(graph, pipeline="runtime")
    mk = lambda qid, seed: Query(
        qid=qid, model="m", evidence={1: 0, 4: 1}, n_chains=2,
        n_iters=10, burn_in=2, seed=seed,
    )
    qa, qb = mk(0, 11), mk(1, 22)
    # uninterrupted reference for A, alone in its bucket
    ref = execute_bucket(
        prog, bucket_key(qa, graph, "schedule"), [qa]
    )[0]
    # slice A and B separately (different buckets: A alone, B alone)
    sliced_key = bucket_key(qa, graph, "schedule", slice_iters=6)
    ra = execute_bucket(prog, sliced_key, [qa], return_state=True)[0]
    rb = execute_bucket(prog, sliced_key, [qb], return_state=True)[0]
    conta = dataclasses.replace(qa, carry=ra.carry, n_iters=4)
    contb = dataclasses.replace(qb, carry=rb.carry, n_iters=4)
    # resume A *batched with B* — a bucket neither slice ever saw
    rkey = bucket_key(conta, graph, "schedule", slice_iters=6)
    assert rkey.resumed and rkey.n_iters == 4
    out = execute_bucket(prog, rkey, [conta, contb])
    np.testing.assert_array_equal(out[0].final_state, ref.final_state)
    np.testing.assert_array_equal(out[0].marginals, ref.marginals)
    # and B equals ITS standalone resume, companions notwithstanding
    solo_b = execute_bucket(prog, rkey, [contb])[0]
    np.testing.assert_array_equal(out[1].final_state, solo_b.final_state)


# ---------------------------------------------------------------------------
# WorkerPool + Executor
# ---------------------------------------------------------------------------


def test_worker_pool_overlaps_and_is_deterministic():
    pool = WorkerPool(3)
    w0, s0 = pool.assign(0.0)
    assert w0 == (0,) and s0 == 0.0
    pool.commit(w0, s0, 5.0)
    w1, s1 = pool.assign(1.0)
    assert w1 == (1,) and s1 == 1.0  # overlaps with worker 0's dispatch
    pool.commit(w1, s1, 4.0)
    w2, s2 = pool.assign(1.0)
    assert w2 == (2,)
    pool.commit(w2, 1.0, 2.0)
    # all busy: earliest-free wins, queued behind its finish
    w3, s3 = pool.assign(1.5)
    assert w3 == (2,) and s3 == 2.0
    assert pool.busy_s == [5.0, 3.0, 1.0]


def test_worker_pool_slice_assignment():
    pool = WorkerPool(4)
    workers, start = pool.assign(0.0, width=2)
    assert workers == (0, 1) and start == 0.0
    pool.commit(workers, 0.0, 3.0)
    workers, start = pool.assign(0.0, width=2)
    assert workers == (2, 3)  # the free slice, not the busy one
    pool.commit(workers, 0.0, 1.0)
    workers, start = pool.assign(0.0, width=4)
    assert workers == (0, 1, 2, 3) and start == 3.0  # waits for the slowest


def test_executor_config_validation():
    with pytest.raises(ValueError):
        ExecutorConfig(n_workers=0)
    with pytest.raises(ValueError):  # sharded route needs a real slice
        ExecutorConfig(n_workers=2, shard_width=1, shard_min_sites=16)
    with pytest.raises(ValueError):  # slice can't exceed the pool
        ExecutorConfig(n_workers=2, shard_width=4, shard_min_sites=16)


def test_executor_routing_rules():
    cal = Calibrator()
    ex = Executor(
        ExecutorConfig(n_workers=4, shard_width=2, shard_min_sites=64),
        cal, (8,),
    )
    mrf_prog = compile_graph(compile_ir.from_mrf(GridMRF(8, 8, 2)))
    bn_graph = canonicalize(random_bayesnet(6, seed=1),
                            evidence_mode="runtime")
    bn_prog = compile_graph(bn_graph)
    img = np.zeros((8, 8), np.int32)
    q = Query(qid=0, model="g", image=img, n_chains=2, n_iters=2)
    mrf_key = bucket_key(q, compile_ir.from_mrf(GridMRF(8, 8, 2)), "schedule")
    assert ex.route(mrf_prog, mrf_key) == "sharded"  # 64 sites >= 64
    pinned = dataclasses.replace(q, evidence={0: 1})
    pkey = bucket_key(pinned, compile_ir.from_mrf(GridMRF(8, 8, 2)),
                      "schedule")
    assert ex.route(mrf_prog, pkey) == "vmap"  # pins never shard
    bq = Query(qid=1, model="b", n_chains=2, n_iters=2)
    assert ex.route(bn_prog, bucket_key(bq, bn_graph, "schedule")) == "vmap"
    # resumed buckets never shard (carry-over is a vmap-route concept)
    rq = dataclasses.replace(q, carry=object())
    rkey = bucket_key(rq, compile_ir.from_mrf(GridMRF(8, 8, 2)), "schedule")
    assert ex.route(mrf_prog, rkey) == "vmap"
    # too-small grids stay on one device
    small = Executor(
        ExecutorConfig(n_workers=4, shard_width=2, shard_min_sites=1000),
        cal, (8,),
    )
    assert small.route(mrf_prog, mrf_key) == "vmap"


def test_executor_sharded_dispatch_occupies_the_slice():
    """A sharded-routed dispatch books every worker in its mesh slice and
    bills compute/width + comm (on a one-device host the math falls back
    to the vmap executable, but the clock must model the slice)."""
    cal = Calibrator()
    ex = Executor(
        ExecutorConfig(n_workers=4, shard_width=2, shard_min_sites=64),
        cal, (4,),
    )
    prog = compile_graph(compile_ir.from_mrf(GridMRF(8, 8, 2)))
    img = np.zeros((8, 8), np.int32)
    qs = [Query(qid=i, model="g", image=img, n_chains=2, n_iters=2, seed=i)
          for i in range(2)]
    key = bucket_key(qs[0], compile_ir.from_mrf(GridMRF(8, 8, 2)),
                     "schedule")
    batch, rec = ex.dispatch(prog, key, qs, 0.0)
    assert rec.route == "sharded" and rec.n_workers == 2
    assert ex.pool.busy_until[0] == ex.pool.busy_until[1] == rec.finish_s
    assert ex.pool.busy_until[2] == 0.0
    assert len(batch) == 2
    # the sharded line model is cheaper per sweep than the serial one
    sig = sig_of(key, "sharded")
    assert cal.line_s(prog, sig, 2, shard_width=2) < \
        cal.line_s(prog, sig, 2, shard_width=1)
    # a batch whose queries continue past this slice must NOT shard: the
    # sharded path cannot return the chain state the continuations need
    long_qs = [dataclasses.replace(q, n_iters=8) for q in qs]
    sliced_key = bucket_key(
        long_qs[0], compile_ir.from_mrf(GridMRF(8, 8, 2)), "schedule",
        slice_iters=2,
    )
    _, rec2 = ex.dispatch(prog, sliced_key, long_qs, 10.0,
                          return_state=True)
    assert rec2.route == "vmap" and rec2.n_workers == 1


def test_sharded_route_without_its_mesh_raises_on_tpu(monkeypatch):
    """Off CPU, a bucket routed sharded over more devices than the process
    has must fail loudly — never run on one chip while booked as a slice
    (the CPU branch above models the slice's timing only)."""
    from repro.runtime import executor as executor_mod

    ex = Executor(
        ExecutorConfig(n_workers=4, shard_width=4, shard_min_sites=64),
        Calibrator(), (4,),
    )
    graph = compile_ir.from_mrf(GridMRF(8, 8, 2))
    prog = compile_graph(graph)
    qs = [Query(qid=0, model="g", image=np.zeros((8, 8), np.int32),
                n_chains=2, n_iters=2)]
    key = bucket_key(qs[0], graph, "schedule")
    assert ex.route(prog, key) == "sharded" and ex._shard_mesh() is None
    monkeypatch.setattr(executor_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="routed sharded over 4"):
        ex.execute(prog, key, qs, "sharded")


# ---------------------------------------------------------------------------
# Calibrator
# ---------------------------------------------------------------------------


def test_calibrator_cold_fallback_and_measured_override():
    cal = Calibrator()
    prog = compile_graph(canonicalize(random_bayesnet(6, seed=2),
                                      evidence_mode="runtime"))
    q = Query(qid=0, model="m", n_chains=4, n_iters=8)
    sig = sig_of(bucket_key(q, prog.ir, "schedule"))
    cold, src = cal.predict(prog, sig, 4)
    assert src == "line" and cold == cal.line_s(prog, sig, 4)
    cal.record(sig, 4, 0.125)
    warm, src = cal.predict(prog, sig, 4)
    assert src == "measured" and warm == 0.125
    # pad scaling: within one chain wave the prediction is flat; past the
    # wave boundary it scales by the wave ratio
    same_wave, _ = cal.predict(prog, sig, 8)
    assert same_wave == 0.125
    big = dataclasses.replace(sig, n_chains=256)
    cal.record(big, 1, 0.1)
    two_waves, _ = cal.predict(prog, big, 2)
    assert two_waves == pytest.approx(0.2)


def test_engine_calibrate_freezes_measurements_and_stays_deterministic():
    models, queries = zipf_trace(16, quick=True, seed=3,
                                 mean_interarrival_s=1e-4)
    keep = {"survey", "cancer"}
    models = {k: v for k, v in models.items() if k in keep}
    queries = [q for q in queries if q.model in keep]
    eng = Engine(models, EngineConfig(pad_sizes=(4,), max_batch=4))
    eng.submit(queries)
    cal = eng.calibrate(queries)
    assert len(cal.measured) > 0
    for _, seconds in cal.measured.values():
        assert seconds > 0
    res1 = eng.run()
    s1 = eng.metrics.summary()
    assert all(b.service_src == "measured"
               for b in eng.metrics.batch_records)
    # replay with the SAME frozen table: identical sim metrics
    eng2 = Engine(models, EngineConfig(pad_sizes=(4,), max_batch=4),
                  calibrator=cal)
    eng2.submit(queries)
    res2 = eng2.run()
    s2 = eng2.metrics.summary()
    for k in s1:
        if k not in ("wall_s", "calib_median_err"):
            assert s1[k] == s2[k], k
    for qid in res1:
        assert res1[qid].finish_s == res2[qid].finish_s


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_admission_config_validation():
    with pytest.raises(ValueError):
        AdmissionConfig(policy="drop")
    with pytest.raises(ValueError):
        AdmissionConfig(rate_qps=0)
    with pytest.raises(ValueError):
        AdmissionConfig(queue_limit=0)


def test_token_bucket_admits_defers_and_sheds():
    ctl = AdmissionController(AdmissionConfig(rate_qps=10.0, burst=2,
                                              max_defer_s=1.0))
    assert ctl.decide(0.0, 0.0)[0] == ADMIT
    assert ctl.decide(0.0, 0.0)[0] == ADMIT  # burst depth 2
    decision, retry = ctl.decide(0.0, 0.0)
    assert decision == DEFER and retry == pytest.approx(0.1)
    # the deferred query re-arrives exactly when its token exists: admitted
    # (the 1e-9 tolerance — without it this would spin forever)
    assert ctl.decide(retry, 0.0)[0] == ADMIT
    assert ctl.defers == 1 and ctl.shed_tokens == 0
    # past the defer budget: shed
    decision, _ = ctl.decide(retry, retry - 1.0)
    assert decision == SHED and ctl.shed_tokens == 1


def test_token_bucket_shed_policy_and_open_admission():
    ctl = AdmissionController(AdmissionConfig(rate_qps=1.0, burst=1,
                                              policy="shed"))
    assert ctl.decide(0.0, 0.0)[0] == ADMIT
    assert ctl.decide(0.0, 0.0)[0] == SHED  # no second chances
    open_ctl = AdmissionController(None)
    for i in range(100):
        assert open_ctl.decide(0.0, 0.0)[0] == ADMIT


def test_queue_bounds():
    ctl = AdmissionController(AdmissionConfig(queue_limit=3))
    assert not ctl.queue_full(2)
    assert ctl.queue_full(3)
    ctl.record_shed(7, by_queue=True)
    assert ctl.sheds == 1 and ctl.shed_queue == 1
    assert AdmissionController(None).queue_full(10 ** 9) is False


def test_engine_bounded_queues_and_shed_accounting():
    """Saturating bursty arrivals against a bounded engine: every pending
    queue stays within the limit, sheds are reported, and served + shed
    covers every submitted query."""
    models, queries = bursty_trace(30, quick=True, seed=2)
    keep = {"survey", "grid"}
    models = {k: v for k, v in models.items() if k in keep}
    queries = [q for q in queries if q.model in keep]
    cfg = EngineConfig(
        pad_sizes=(4,), max_batch=4,
        admission=AdmissionConfig(rate_qps=2000.0, burst=4, queue_limit=3,
                                  policy="shed"),
    )
    eng = Engine(models, cfg)
    eng.submit(queries)
    res = eng.run()
    s = eng.metrics.summary()
    assert s["sheds"] > 0  # the burst actually saturated the bucket
    assert len(res) + s["sheds"] == len(queries)
    assert set(eng.shed_qids).isdisjoint(res)
    assert s["max_queue_depth"] <= 3
    assert s["shed_rate"] == pytest.approx(s["sheds"] / len(queries))
    # determinism under backpressure: replay from a cold program cache
    # reproduces every counter
    clear_program_cache()
    eng2 = Engine(models, cfg)
    models2, queries2 = bursty_trace(30, quick=True, seed=2)
    eng2.submit([q for q in queries2 if q.model in keep])
    res2 = eng2.run()
    s2 = eng2.metrics.summary()
    for k in s:
        if k not in ("wall_s", "calib_median_err"):
            assert s[k] == s2[k], k
    assert sorted(res2) == sorted(res)


# ---------------------------------------------------------------------------
# Engine: multi-worker overlap + continuous batching
# ---------------------------------------------------------------------------


def _zoo(seed=7, n=24):
    models, queries = zipf_trace(n, quick=True, seed=seed,
                                 mean_interarrival_s=5e-5)
    keep = {"survey", "cancer", "grid"}
    models = {k: v for k, v in models.items() if k in keep}
    return models, [q for q in queries if q.model in keep]


def test_multi_worker_qps_beats_serial_and_preserves_bits():
    m1, q1 = _zoo()
    e1 = Engine(m1, EngineConfig(pad_sizes=(4,), max_batch=4, n_workers=1))
    e1.submit(q1)
    r1 = e1.run()
    m4, q4 = _zoo()
    e4 = Engine(m4, EngineConfig(pad_sizes=(4,), max_batch=4, n_workers=4))
    e4.submit(q4)
    r4 = e4.run()
    s1, s4 = e1.metrics.summary(), e4.metrics.summary()
    assert s4["throughput_qps"] > s1["throughput_qps"]
    assert s4["latency_p95_s"] <= s1["latency_p95_s"]
    # worker count changes the clock, never the posterior
    for qid in r1:
        np.testing.assert_array_equal(r1[qid].final_state,
                                      r4[qid].final_state)
    assert len(s4["worker_util"]) == 4
    assert sum(e4.metrics.worker_busy_s) > 0


def test_engine_sliced_serving_bit_exact_with_unsliced():
    m_a, q_a = _zoo(seed=9)
    e_a = Engine(m_a, EngineConfig(pad_sizes=(4,), max_batch=4))
    e_a.submit(q_a)
    r_a = e_a.run()
    m_b, q_b = _zoo(seed=9)
    e_b = Engine(m_b, EngineConfig(pad_sizes=(4,), max_batch=4,
                                   slice_iters=5))
    e_b.submit(q_b)
    r_b = e_b.run()
    assert sorted(r_a) == sorted(r_b)
    assert e_b.metrics.summary()["n_batches"] > \
        e_a.metrics.summary()["n_batches"]
    for qid in r_a:
        np.testing.assert_array_equal(r_a[qid].final_state,
                                      r_b[qid].final_state)
        if r_a[qid].marginals is not None:
            np.testing.assert_array_equal(r_a[qid].marginals,
                                          r_b[qid].marginals)


def test_slicing_interleaves_short_queries_between_long_slices():
    """The continuous-batching win itself: a short query that arrives while
    a long query is mid-flight finishes earlier when the long query is
    sliced, because its slices yield the (single) worker."""
    bn = bn_repository_replica("survey")
    long_q = Query(qid=0, model="m", evidence={0: 1}, n_chains=2,
                   n_iters=24, burn_in=0, seed=1, arrival_s=0.0)
    short_q = Query(qid=1, model="m", evidence={0: 1}, n_chains=2,
                    n_iters=4, burn_in=0, seed=2, arrival_s=1e-5)

    def serve(slice_iters):
        eng = Engine({"m": bn}, EngineConfig(
            pad_sizes=(2,), max_batch=2, window_s=1e-6,
            slice_iters=slice_iters,
        ))
        eng.submit([dataclasses.replace(long_q),
                    dataclasses.replace(short_q)])
        return eng.run()

    unsliced = serve(None)
    sliced = serve(4)
    assert sliced[1].finish_s < unsliced[1].finish_s
    # and the long query still gets its exact bits
    np.testing.assert_array_equal(unsliced[0].final_state,
                                  sliced[0].final_state)


def test_continuations_respect_queue_bound_without_starving():
    """A continuation that re-arrives to a full bucket (queue_limit below
    max_batch, so the bucket cannot fill-flush its way clear) waits for the
    bucket's flush horizon instead of shedding or spinning — every query
    still completes, and the bound holds throughout."""
    bn = bn_repository_replica("survey")
    queries = [
        Query(qid=i, model="m", evidence={0: 1}, n_chains=2,
              n_iters=12, burn_in=0, seed=i, arrival_s=1e-6 * i)
        for i in range(6)
    ]
    eng = Engine({"m": bn}, EngineConfig(
        pad_sizes=(4,), max_batch=4, window_s=5e-4, slice_iters=4,
        admission=AdmissionConfig(queue_limit=2),
    ))
    eng.submit(queries)
    res = eng.run()
    s = eng.metrics.summary()
    # sheds may hit fresh arrivals (the bound is real), but every *served*
    # query ran all its slices and every continuation survived
    assert len(res) + s["sheds"] == len(queries)
    assert s["max_queue_depth"] <= 2
    ref = Engine({"m": bn}, EngineConfig(pad_sizes=(4,), max_batch=4))
    ref.submit([dataclasses.replace(q) for q in queries])
    whole = ref.run()
    for qid in res:
        np.testing.assert_array_equal(res[qid].final_state,
                                      whole[qid].final_state)


def test_lone_overflow_continuation_terminates():
    """Regression: a single continuation meeting a full bucket while the
    heap is otherwise empty and a worker is free must not stall the event
    loop (a heap-parked retry used to suppress the `not heap` drain rule
    and ulp-step the clock toward the window expiry — an effective hang)."""
    bn = bn_repository_replica("survey")
    queries = [
        Query(qid=0, model="m", evidence={0: 1}, n_chains=2, n_iters=8,
              burn_in=0, seed=1, arrival_s=0.0),
        Query(qid=1, model="m", evidence={0: 1}, n_chains=2, n_iters=8,
              burn_in=0, seed=2, arrival_s=0.0),
        Query(qid=2, model="m", evidence={0: 1}, n_chains=2, n_iters=8,
              burn_in=0, seed=3, arrival_s=3e-4),
    ]
    eng = Engine({"m": bn}, EngineConfig(
        pad_sizes=(4,), max_batch=4, window_s=2e-4, slice_iters=4,
        n_workers=2, admission=AdmissionConfig(queue_limit=2),
    ))
    eng.submit(queries)
    res = eng.run()
    s = eng.metrics.summary()
    assert len(res) + s["sheds"] == 3
    assert s["max_queue_depth"] <= 2


# ---------------------------------------------------------------------------
# Metrics hardening
# ---------------------------------------------------------------------------


def test_percentiles_refuse_tiny_samples():
    assert percentile([], 50) is None
    assert percentile([1.0], 95) is None
    assert percentile([1.0, 3.0], 50) == 2.0


def test_summary_reports_na_on_empty_and_singleton_runs():
    m = RuntimeMetrics()
    s = m.summary()  # empty run: no crash, no invented latencies
    assert s["latency_p50_s"] is None and s["latency_p95_s"] is None
    assert s["latency_mean_s"] is None and s["throughput_qps"] == 0.0
    # zero dispatched batches: no mean batch size either (satellite fix —
    # this used to divide by a clamped denominator and report 0.0)
    assert s["mean_batch"] is None
    assert "n/a" in m.table()
    from repro.runtime.batcher import QueryResult

    m.record_queries([QueryResult(
        qid=0, model="m", kind="bn", marginals=None,
        final_state=np.zeros(1), arrival_s=0.0, start_s=1.0, finish_s=2.0,
    )])
    m.record_batch(BatchRecord(model="m", kind="bn", n_real=1, n_padded=1,
                               service_s=1.0, clamp_lowerings=0))
    s = m.summary()  # singleton: a mean exists, percentiles do not
    assert s["latency_p50_s"] is None and s["latency_p95_s"] is None
    # seconds end to end: the summary never pre-converts to ms (the old
    # double conversion reported ms-of-ms in table())
    assert s["latency_mean_s"] == pytest.approx(2.0)
    assert s["n_queries"] == 1


def test_summary_surfaces_workers_and_backpressure():
    m = RuntimeMetrics()
    m.worker_busy_s = (1.0, 3.0)
    m.sheds, m.shed_queue, m.defers, m.max_queue_depth = 2, 1, 5, 7
    s = m.summary()
    assert s["n_workers"] == 2 and len(s["worker_util"]) == 2
    assert s["sheds"] == 2 and s["defers"] == 5
    assert s["max_queue_depth"] == 7
    assert "| 2 | 5 | 7 |" in m.table()


# ---------------------------------------------------------------------------
# Multi-device sharded serving (advisory CI job)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_sharded_route_8dev():
    """The executor's sharded route really executes through run_sharded
    when the host has enough devices (subprocess with 8 simulated host
    devices, mirroring test_distributed_pm)."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from repro.runtime import Engine, EngineConfig, zipf_trace

        models, queries = zipf_trace(20, quick=True, seed=4,
                                     mean_interarrival_s=1e-4)
        models = {k: v for k, v in models.items() if k == "grid"}
        queries = [q for q in queries if q.model == "grid"]
        for q in queries:
            q.evidence = None  # pins never shard; exercise the route
        eng = Engine(models, EngineConfig(
            pad_sizes=(4,), max_batch=4, n_workers=8, shard_width=4,
            shard_min_sites=64,
        ))
        eng.submit(queries)
        res = eng.run()
        s = eng.metrics.summary()
        assert len(res) == len(queries)
        assert s["sharded_batches"] > 0, s
        assert any(b.route == "sharded" and b.n_workers == 4
                   for b in eng.metrics.batch_records)
        print("SHARDED_SERVING_OK")
        """
    )
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_SERVING_OK" in res.stdout


@pytest.mark.slow
def test_engine_batched_sliced_sharded_fused_8dev():
    """The tentpole acceptance: one bucket simultaneously batched, sliced,
    sharded, AND fused — every dispatch (continuations included) runs the
    one-shard_map-body fused datapath, the quality accumulator rides the
    carry, the shard_map executable attributes in the profile join, and
    the results are bit-exact with a single-device vmap engine."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from repro import obs
        from repro.compile import clear_program_cache
        from repro.core import mrf as mrf_mod
        from repro.core.graphs import GridMRF
        from repro.obs import export
        from repro.obs import profile as profile_mod
        from repro.runtime import Engine, EngineConfig, Query

        mrf = GridMRF(8, 8, 3, theta=1.1, h=1.5)
        imgs = [np.asarray(
                    mrf_mod.make_denoising_problem(8, 8, 3, 0.25, seed=s)[1])
                for s in range(3)]

        def queries():
            return [Query(qid=i, model="g", image=imgs[i % 3], n_chains=2,
                          n_iters=8, seed=i, arrival_s=1e-5 * i)
                    for i in range(6)]

        tr = obs.enable()
        reg = profile_mod.enable()
        eng = Engine({"g": mrf}, EngineConfig(
            pad_sizes=(4,), max_batch=4, n_workers=8, shard_width=4,
            shard_min_sites=64, fused=True, diagnostics=True, slice_iters=3,
        ))
        eng.submit(queries())
        res = eng.run()
        recs = eng.metrics.batch_records
        assert len(res) == 6
        # 8 sweeps in slices of 3: every query resumed twice, and every
        # dispatch — fresh or resumed — kept the fused sharded route
        assert len(recs) > 2
        assert all(r.route == "sharded" and r.n_workers == 4 for r in recs)
        assert all(res[q].quality is not None for q in res)

        # the shard_map executable was captured under the dispatch
        # signature: zero unattributed, collective bytes on a sharded row
        events = export.events_as_dicts(list(tr.events))
        joined = profile_mod.join_dispatches(reg.profiles, events)
        assert joined["unattributed"] == [], joined["unattributed"]
        assert joined["n_sharded"] == len(recs)
        assert any(p.meta.get("route") == "sharded"
                   and p.collective_bytes > 0
                   for p in reg.profiles.values())

        # bit-exact with the single-device vmap engine, unsliced
        obs.disable()
        profile_mod.disable()
        clear_program_cache()
        ref = Engine({"g": mrf}, EngineConfig(pad_sizes=(4,), max_batch=4,
                                              fused=True, diagnostics=True))
        ref.submit(queries())
        whole = ref.run()
        for qid in res:
            np.testing.assert_array_equal(res[qid].final_state,
                                          whole[qid].final_state)
            qa, qb = res[qid].quality, whole[qid].quality
            assert qa.keys() == qb.keys()
            for k in qa:
                x, y = qa[k], qb[k]
                assert x == y or (x != x and y != y), (k, x, y)
        print("SHARDED_FUSED_ENGINE_OK")
        """
    )
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_FUSED_ENGINE_OK" in res.stdout
