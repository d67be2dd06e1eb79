"""repro.obs.profile + repro.obs.timeseries: the roofline-profiler gates.

  * typed series semantics: counter monotonicity, gauge last-value,
    histogram bucketing + conservative quantiles (including the
    < 2-sample refusal shared with ``runtime.metrics.percentile``);
  * **series byte-determinism** — two cold-cache same-seed engine passes
    produce byte-identical ``to_jsonl()`` output;
  * bucket/program signature stability and distinctness across the
    static fields that shape a jit specialization;
  * capture + join: a profiled engine pass leaves **zero unattributed
    dispatches** and every row carries measured walls and a roofline
    bottleneck;
  * the static-cost drift gate: injected baseline rows pass clean,
    perturbed rows trip ``obs-cost-drift``, jax-version mismatch and
    row-free baselines skip with a note;
  * ``validate_profile`` flags structural holes;
  * the tracer ``dropped`` counter surfaces in the metrics summary.
"""

import json

import pytest

from benchmarks import check_regression
from repro import obs
from repro.analysis import Report
from repro.compile import clear_program_cache
from repro.obs import export
from repro.obs import profile as profile_mod
from repro.obs import timeseries
from repro.runtime import Engine, EngineConfig, zipf_trace
from repro.runtime.batcher import BucketKey


@pytest.fixture(autouse=True)
def _profiling_off():
    """Tests must not leak tracer/profiler state or warm program caches."""
    obs.disable()
    profile_mod.disable()
    clear_program_cache()
    yield
    obs.disable()
    profile_mod.disable()
    clear_program_cache()


def _engine_pass(n=24, seed=3, **cfg):
    models, queries = zipf_trace(n, quick=True, seed=seed,
                                 mean_interarrival_s=5e-5)
    eng = Engine(models, EngineConfig(pad_sizes=(8,), max_batch=8, **cfg))
    eng.submit(queries)
    results = eng.run()
    return eng, results


# ---------------------------------------------------------------------------
# timeseries
# ---------------------------------------------------------------------------


def test_exp_boundaries():
    b = timeseries.exp_boundaries(1e-4, 2.0, 5)
    assert b == (1e-4, 2e-4, 4e-4, 8e-4, 16e-4)
    with pytest.raises(ValueError):
        timeseries.exp_boundaries(0.0, 2.0, 5)
    with pytest.raises(ValueError):
        timeseries.exp_boundaries(1.0, 1.0, 5)


def test_counter_is_cumulative_and_gauge_is_instant():
    reg = timeseries.SeriesRegistry()
    c = reg.counter("q")
    c.inc(0.1)
    c.inc(0.2, 4)
    assert c.total == 5
    assert [v for _, _, v in c.samples] == [1, 5]
    g = reg.gauge("depth")
    g.sample(0.3, 7)
    g.sample(0.4, 2)
    assert g.last == 2
    # same name, different type: refused, not silently rebound
    with pytest.raises(TypeError):
        reg.gauge("q")


def test_histogram_quantiles_are_conservative():
    reg = timeseries.SeriesRegistry()
    h = reg.histogram("lat", boundaries=(1.0, 2.0, 4.0))
    assert h.quantile(50) is None  # zero samples: no distribution
    h.observe(0.0, 0.5)
    assert h.quantile(50) is None  # one sample: still refused
    h.observe(0.1, 1.5)
    h.observe(0.2, 3.0)
    h.observe(0.3, 100.0)  # overflow bucket
    assert h.count == 4 and h.bucket_counts == [1, 1, 1, 1]
    assert h.quantile(0) == 1.0     # bucket upper bound, not the value
    assert h.quantile(40) == 2.0    # rank 2 of 4
    assert h.quantile(50) == 4.0    # nearest-rank, same as metrics.percentile
    assert h.quantile(100) == 100.0  # overflow reports the observed max
    assert h.vmin == 0.5 and h.vmax == 100.0
    with pytest.raises(ValueError):
        reg.histogram("bad", boundaries=(2.0, 1.0))


def test_registry_jsonl_interleaves_by_emission_order():
    reg = timeseries.SeriesRegistry()
    reg.counter("b").inc(0.1)
    reg.gauge("a").sample(0.2, 9)
    reg.counter("b").inc(0.3)
    lines = [json.loads(x) for x in reg.to_jsonl().splitlines()]
    assert [r["series"] for r in lines] == ["b", "a", "b"]
    assert [r["seq"] for r in lines] == [1, 2, 3]
    assert lines[1] == {"kind": "gauge", "seq": 2, "series": "a",
                        "t": 0.2, "value": 9}
    snap = reg.snapshot()
    assert snap["b"]["total"] == 2 and snap["a"]["last"] == 9


def test_series_jsonl_byte_deterministic_across_runs():
    eng1, _ = _engine_pass()
    blob1 = eng1.metrics.series.to_jsonl()
    clear_program_cache()
    eng2, _ = _engine_pass()
    blob2 = eng2.metrics.series.to_jsonl()
    assert blob1 and blob1 == blob2
    names = {json.loads(x)["series"] for x in blob1.splitlines()}
    assert {"queue_depth", "pad_efficiency", "bucket_service_s",
            "query_latency_s", "worker_stall_s"} <= names


def test_metrics_summary_surfaces_histogram_p99():
    eng, results = _engine_pass()
    s = eng.metrics.summary()
    assert s["latency_p99_s"] is not None
    assert s["latency_p99_s"] >= s["latency_p50_s"]
    assert "p99" in eng.metrics.table() and "dropped" in eng.metrics.table()


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------


def _key(**over):
    base = dict(
        program_key="a" * 64, kind="bn", clamp_nodes=(1, 3), has_pins=False,
        n_chains=8, n_iters=40, burn_in=10, thin=1, sampler="lut_ky",
        backend="schedule",
    )
    base.update(over)
    return BucketKey(**base)


def test_bucket_signature_stable_and_distinct():
    sig = profile_mod.bucket_signature(_key(), 8)
    assert sig == profile_mod.bucket_signature(_key(), 8)  # pure function
    seen = {sig}
    for variant in (
        dict(program_key="b" * 64), dict(clamp_nodes=()), dict(n_chains=16),
        dict(n_iters=41), dict(burn_in=11), dict(thin=2),
        dict(sampler="gumbel"), dict(fused=True), dict(resumed=True),
        dict(diagnostics=True),
    ):
        s = profile_mod.bucket_signature(_key(**variant), 8)
        assert s not in seen, variant
        seen.add(s)
    assert profile_mod.bucket_signature(_key(), 16) not in seen  # pad width


def test_bucket_signature_sharded_route_qualified():
    """A sharded dispatch runs a different jit specialization (the
    shard_map body over a mesh slice), so the route and slice width extend
    the signature; the vmap format is untouched by the new arguments."""
    base = profile_mod.bucket_signature(_key(), 8)
    assert profile_mod.bucket_signature(
        _key(), 8, route="vmap", shard_width=1
    ) == base
    sh4 = profile_mod.bucket_signature(
        _key(), 8, route="sharded", shard_width=4
    )
    assert sh4 != base and sh4.endswith("|sharded|sh4")
    assert profile_mod.bucket_signature(
        _key(), 8, route="sharded", shard_width=2
    ) != sh4


# ---------------------------------------------------------------------------
# capture + join
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_profiled_pass_joins_every_dispatch():
    tr = obs.enable()
    reg = profile_mod.enable()
    eng, results = _engine_pass()
    events = export.events_as_dicts(list(tr.events))
    assert results and reg.profiles
    for prof in reg.profiles.values():
        assert prof.hbm_bytes > 0
        assert prof.bottleneck in profile_mod.BOTTLENECKS
        assert prof.roofline_s > 0
        det = prof.as_dict(deterministic=True)
        assert "capture_s" not in det  # wall term excluded from exports
        assert "capture_s" in prof.as_dict(deterministic=False)
    joined = profile_mod.join_dispatches(reg.profiles, events)
    assert joined["unattributed"] == []
    assert joined["n_dispatches"] == len(eng.metrics.batch_records)
    assert joined["rows"]
    for row in joined["rows"]:
        assert row["n_dispatches"] > 0
        assert row["measured_mean_s"] > 0
        assert 0 < row["peak_frac"] <= 1.0
    rec = {"schema": 1, "buckets": reg.rows(deterministic=False),
           "joined": joined, "peaks": {}}
    assert profile_mod.validate_profile(rec) == []


@pytest.mark.slow
def test_capture_cache_hits_by_signature():
    obs.enable()
    reg = profile_mod.enable()
    _engine_pass()
    n_first = len(reg.profiles)
    assert n_first > 0
    # same workload again in the same process: every bucket is a cache hit
    _engine_pass()
    assert len(reg.profiles) == n_first
    assert reg.hits > 0


def test_validate_profile_flags_holes():
    bad = {
        "schema": 1,
        "buckets": [{"sig": "s", "flops": -1.0, "hbm_bytes": 0.0,
                     "collective_bytes": 0.0, "bottleneck": "nonsense"}],
        "joined": {"unattributed": [{"sig": "x", "n_dispatches": 3}]},
    }
    problems = profile_mod.validate_profile(bad)
    assert any("unattributed" in p for p in problems)
    assert any("bottleneck" in p or "nonsense" in p for p in problems)
    assert profile_mod.validate_profile({"schema": 1, "buckets": [],
                                         "joined": {}}) != []


def test_join_attributes_sharded_dispatches():
    """Sharded-route dispatches join like any other bucket: a profile
    captured under the route-qualified signature attributes them (comm
    rows included), and a missing profile is an unattributed finding —
    never a silent skip (the old ``n_sharded_skipped`` behavior)."""
    sig = profile_mod.bucket_signature(
        _key(fused=True), 8, route="sharded", shard_width=4
    )
    prof = {
        "sig": sig, "flops": 2.0e6, "hbm_bytes": 1.0e6,
        "collective_bytes": 4096.0, "roofline_s": 1e-4,
        "collective_by_op": {"collective-permute": 4096.0},
        "bottleneck": "collective",
    }
    mk = lambda s: {
        "name": "dispatch",
        "args": {"route": "sharded", "profile_sig": s, "model": "g",
                 "service_s": 2e-3},
        "wargs": {"measured_s": 1e-3},
    }
    joined = profile_mod.join_dispatches(
        {sig: prof}, [mk(sig), mk(sig), mk("bucket|nope|sharded|sh4")]
    )
    assert joined["n_dispatches"] == 3 and joined["n_sharded"] == 3
    (row,) = joined["rows"]
    assert row["sig"] == sig and row["n_dispatches"] == 2
    assert row["peak_frac"] == pytest.approx(0.1)
    (comm,) = joined["comm"]
    assert comm["mechanism"] == "ppermute_halo"
    assert comm["total_bytes"] == pytest.approx(2 * 4096.0)
    (un,) = joined["unattributed"]
    assert un["n_dispatches"] == 1


def test_trace_dropped_surfaces_in_summary():
    obs.enable(capacity=16)  # force ring-buffer overflow
    eng, _ = _engine_pass()
    s = eng.metrics.summary()
    assert s["trace_dropped"] > 0
    assert f"{s['trace_dropped']}" in eng.metrics.table()


# ---------------------------------------------------------------------------
# static-cost drift gate
# ---------------------------------------------------------------------------


def _cost_rows():
    return [
        {"sig": "run|aaaa|bn|lut_ky|ch8|it32|bi8|th1|fused0",
         "flops": 0.0, "hbm_bytes": 2.5e6, "collective_bytes": 0.0},
        {"sig": "run|bbbb|mrf|lut_ky|ch8|it32|bi8|th1|fused1",
         "flops": 1.0e9, "hbm_bytes": 5.4e8, "collective_bytes": 1024.0},
    ]


def _cost_baseline():
    import jax

    return {"schema": 2, "quick": True, "jax": jax.__version__,
            "profile": _cost_rows()}


def test_check_static_cost_clean_rerun_passes():
    rep = Report(meta={"cost_rows": []})
    check_regression.check_static_cost(
        _cost_baseline(), rep, sweep_rows=_cost_rows())
    assert rep.exit_code == 0
    assert rep.meta["cost_compared"] == 2
    assert rep.meta["cost_missing"] == [] and rep.meta["cost_new"] == []


def test_check_static_cost_trips_on_injected_drift():
    for metric, bad in (("flops", 2.0e9), ("hbm_bytes", 1.0),
                        ("collective_bytes", 4096.0)):
        rows = _cost_rows()
        rows[1][metric] = bad
        rep = Report(meta={"cost_rows": []})
        check_regression.check_static_cost(
            _cost_baseline(), rep, sweep_rows=rows)
        assert rep.exit_code == 1, metric
        assert rep.findings[0].rule == "obs-cost-drift"
        assert metric in rep.findings[0].message


def test_check_static_cost_within_tolerance_passes():
    rows = _cost_rows()
    rows[1]["hbm_bytes"] *= 1.05  # inside the 10% default band
    rep = Report(meta={"cost_rows": []})
    check_regression.check_static_cost(
        _cost_baseline(), rep, sweep_rows=rows)
    assert rep.exit_code == 0


def test_check_static_cost_skips_across_jax_versions():
    base = _cost_baseline()
    base["jax"] = "0.0.1"
    rep = Report(meta={"cost_rows": []})
    check_regression.check_static_cost(base, rep, sweep_rows=_cost_rows())
    assert rep.exit_code == 0
    assert "not comparable" in rep.meta["cost_note"]


def test_check_static_cost_skips_rowless_baseline():
    rep = Report(meta={"cost_rows": []})
    check_regression.check_static_cost({"schema": 2}, rep, sweep_rows=[])
    assert rep.exit_code == 0
    assert "no profile rows" in rep.meta["cost_note"]


def test_check_static_cost_reports_missing_and_new_sigs():
    rows = _cost_rows()
    renamed = [dict(rows[0], sig="run|cccc|new"), rows[1]]
    rep = Report(meta={"cost_rows": []})
    check_regression.check_static_cost(
        _cost_baseline(), rep, sweep_rows=renamed)
    assert rep.exit_code == 0  # renames are meta, never silent failures
    assert rep.meta["cost_compared"] == 1
    assert rep.meta["cost_missing"] == [_cost_rows()[0]["sig"]]
    assert rep.meta["cost_new"] == ["run|cccc|new"]


def test_roofline_peaks_keyed_by_device_kind():
    """One sourced peak row per chip kind; an unknown kind raises instead
    of borrowing another chip's numbers."""
    from repro.launch import roofline

    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9) and v5e.source
    assert roofline.TARGET_KIND in roofline.PEAKS
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v4")
    roof = roofline.Roofline(flops=197e12, hbm_bytes=0.0,
                             collective_bytes=0.0, n_chips=1,
                             device_kind="TPU v5 lite")
    assert roof.t_compute == 1.0 and roof.bottleneck == "compute"
    with pytest.raises(ValueError):
        roofline.Roofline(flops=1.0, hbm_bytes=0.0, collective_bytes=0.0,
                          n_chips=1, device_kind="cpu").t_compute
    with pytest.raises(ValueError):
        profile_mod.ProfileRegistry(device_kind="cpu")
