"""repro.diag: streaming quality accumulators (Welford/R-hat/ESS math,
carry-over bit-identity, zero perturbation of the draw streams), oracle
audits (VE tractability declaration, KY-quantization attribution,
chi-square GOF of fused KY draws against the quantized target pmf), the
quality CLI's threshold/exit-code contract, and the perf+quality
regression gate."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import RULES, Finding, Report
from repro.compile import clear_program_cache, compile_graph
from repro.core.graphs import DiscreteBayesNet, bn_repository_replica
from repro.diag import accum as diag_accum
from repro.diag import oracle as diag_oracle
from repro.diag.__main__ import main as diag_main
from repro.diag.__main__ import quality_sweep
from repro.runtime import Engine, EngineConfig, Query

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import check_regression


# ---------------------------------------------------------------------------
# accumulator math
# ---------------------------------------------------------------------------


def _onehot(vals, n_values):
    return (np.asarray(vals)[..., None]
            == np.arange(n_values)).astype(np.int32)


def test_welford_matches_numpy_moments():
    rng = np.random.default_rng(0)
    n_chains, n_sites, n_values, total = 4, 3, 5, 40
    draws = rng.integers(0, n_values, size=(total, n_chains, n_sites))
    q = diag_accum.make_accum(n_chains, n_sites, n_values, total)
    for t in range(total):
        q = diag_accum.update(
            q, jnp.asarray(_onehot(draws[t], n_values)), jnp.asarray(True)
        )
    oh = _onehot(draws, n_values)  # (total, chains, sites, values)
    # the two split halves each hold their own exact moments
    half = total // 2
    for s, (lo, hi) in enumerate(((0, half), (half, total))):
        np.testing.assert_allclose(
            np.asarray(q.mean)[s], oh[lo:hi].mean(0), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(q.m2)[s], oh[lo:hi].var(0) * (hi - lo),
            rtol=1e-5, atol=1e-4,
        )
    snap = diag_accum.summarize(q)
    # merged marginal = the plain empirical marginal over all kept draws
    np.testing.assert_allclose(
        snap.p_hat, oh.mean(axis=(0, 1)), rtol=1e-6
    )


def test_rhat_converged_near_one_and_split_chains_diverge():
    rng = np.random.default_rng(1)
    n_chains, n_sites, n_values, total = 8, 2, 3, 200
    # converged: every chain draws iid from the same distribution
    draws = rng.integers(0, n_values, size=(total, n_chains, n_sites))
    q = diag_accum.make_accum(n_chains, n_sites, n_values, total)
    for t in range(total):
        q = diag_accum.update(
            q, jnp.asarray(_onehot(draws[t], n_values)), jnp.asarray(True)
        )
    b = diag_accum.summarize(q).brief()
    assert b["rhat_max"] is not None and b["rhat_max"] < 1.05
    assert b["ess_min"] > 0

    # stuck-apart: half the chains pinned at value 0, half at value 1 —
    # zero within-chain variance, huge between-chain variance
    vals = np.zeros((n_chains, n_sites), np.int64)
    vals[n_chains // 2:] = 1
    q2 = diag_accum.make_accum(n_chains, n_sites, n_values, total)
    oh2 = jnp.asarray(_onehot(vals, n_values))
    for _ in range(total):
        q2 = diag_accum.update(q2, oh2, jnp.asarray(True))
    b2 = diag_accum.summarize(q2).brief()
    assert b2["rhat_max"] > 1.1  # the gate must catch this (inf counts)
    # every chain constant -> batch-means variance is 0/0: ESS undefined,
    # reported None (never a fabricated number)
    assert b2["ess_min"] is None

    # half the chains stuck, half mixing: the stuck half contributes 0
    # ESS, so the total sits well below the all-mixing value
    q3 = diag_accum.make_accum(n_chains, n_sites, n_values, total)
    for t in range(total):
        mixed = draws[t].copy()
        mixed[n_chains // 2:] = 0  # stuck half
        q3 = diag_accum.update(
            q3, jnp.asarray(_onehot(mixed, n_values)), jnp.asarray(True)
        )
    b3 = diag_accum.summarize(q3).brief()
    assert b3["ess_min"] is not None
    assert b3["ess_min"] < 0.75 * b["ess_min"]


def test_accum_overflow_flag():
    q = diag_accum.make_accum(2, 2, 2, 100)
    assert not diag_accum.summarize(q).brief()["overflow_risk"]
    q = dataclasses.replace(
        q, counts=jnp.full_like(q.counts, 2**30 + 1)
    )
    assert diag_accum.summarize(q).brief()["overflow_risk"]


# ---------------------------------------------------------------------------
# in-loop wiring: bit-identity guarantees
# ---------------------------------------------------------------------------


def test_diagnostics_leave_draws_bit_identical():
    prog = compile_graph(bn_repository_replica("survey"))
    kw = dict(n_chains=8, n_iters=40, burn_in=10)
    m0, v0 = prog.run(key=jax.random.key(3), **kw)
    m1, v1, snap = prog.run(key=jax.random.key(3), diagnostics=True, **kw)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    # and the snapshot's merged marginal is itself coherent: a proper
    # distribution over each node's support
    np.testing.assert_allclose(snap.p_hat.sum(-1), 1.0, rtol=1e-5)


def test_fused_and_unfused_snapshots_bit_identical():
    prog = compile_graph(bn_repository_replica("survey"))
    kw = dict(n_chains=8, n_iters=30, burn_in=6, diagnostics=True)
    _, _, s_unfused = prog.run(key=jax.random.key(5), **kw)
    _, _, s_fused = prog.run(key=jax.random.key(5), fused=True, **kw)
    assert s_unfused.to_dict() == s_fused.to_dict()


def test_sliced_equals_unsliced_snapshot():
    """Quality accumulators must be carry-over safe: the same budget cut
    into slices yields the bit-identical snapshot (split point fixed from
    the total budget at accumulator creation)."""
    from repro.core import bayesnet as bnet

    cbn = bnet.compile_bayesnet(bn_repository_replica("survey"))
    kw = dict(n_chains=8, burn_in=10, thin=1, diag_total=40)
    _, _, whole = bnet.run_gibbs(cbn, jax.random.key(7), n_iters=40,
                                 return_state=True, **kw)
    # same budget in two slices: the accumulator declares the *total*
    # kept budget up front, so the carry resumes mid-stream exactly
    _, _, st = bnet.run_gibbs(cbn, jax.random.key(7), n_iters=15,
                              return_state=True, **kw)
    _, _, sliced = bnet.run_gibbs(cbn, None, n_iters=25, carry=st,
                                  return_state=True, **kw)
    for f in ("counts", "mean", "m2", "bm_mean", "bm_m2", "cur_sum",
              "cur_n", "bm_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(whole.quality, f)),
            np.asarray(getattr(sliced.quality, f)), err_msg=f,
        )
    assert (diag_accum.summarize(whole.quality).to_dict()
            == diag_accum.summarize(sliced.quality).to_dict())

    # engine-level: sliced serving produces the same quality brief
    clear_program_cache()
    bn = bn_repository_replica("survey")
    queries = [Query(qid=i, model="survey", n_chains=8, n_iters=40,
                     burn_in=10, seed=i) for i in range(3)]
    e1 = Engine({"survey": bn}, EngineConfig(
        pad_sizes=(4,), max_batch=4, diagnostics=True))
    e1.submit([dataclasses.replace(q) for q in queries])
    r1 = e1.run()
    clear_program_cache()
    e2 = Engine({"survey": bn}, EngineConfig(
        pad_sizes=(4,), max_batch=4, diagnostics=True, slice_iters=15))
    e2.submit([dataclasses.replace(q) for q in queries])
    r2 = e2.run()
    for qid in r1:
        assert r1[qid].quality is not None
        assert r1[qid].quality == r2[qid].quality


def test_engine_quality_briefs_and_metrics_rollup():
    clear_program_cache()
    bn = bn_repository_replica("survey")
    eng = Engine({"survey": bn}, EngineConfig(
        pad_sizes=(4,), max_batch=4, diagnostics=True))
    eng.submit([Query(qid=i, model="survey", n_chains=8, n_iters=30,
                      burn_in=5, seed=i) for i in range(3)])
    res = eng.run()
    for r in res.values():
        assert set(r.quality) >= {"rhat_max", "ess_min", "kept"}
        assert r.quality["kept"] == 25
    s = eng.metrics.summary()
    assert s["quality_queries"] == 3
    assert s["rhat_max"] is not None and s["ess_min"] is not None
    assert "rhat max" in eng.metrics.table()


def test_resume_without_quality_carry_raises():
    prog = compile_graph(bn_repository_replica("survey"))
    _, _, st = prog.run(key=jax.random.key(1), n_chains=4, n_iters=10,
                        burn_in=2, return_state=True)
    with pytest.raises(ValueError, match="diagnostics"):
        prog.run(key=None, n_chains=4, n_iters=10, burn_in=2,
                 carry_state=st, diagnostics=True)


# ---------------------------------------------------------------------------
# oracle audits
# ---------------------------------------------------------------------------


def test_oracle_audit_ok_and_declared_na():
    from repro.core import exact

    bn = bn_repository_replica("survey")
    truth = exact.all_marginals(bn, {})
    p_hat = np.zeros((bn.n_nodes, int(max(bn.cards))))
    for i, row in enumerate(truth):
        p_hat[i, : len(row)] = row
    audit = diag_oracle.oracle_audit(bn, p_hat)
    assert audit["status"] == "ok"
    assert audit["tv_max"] < 1e-12

    # the same model under a starvation limit is *declared* n/a
    na = diag_oracle.oracle_audit(bn, p_hat, limit=1)
    assert na["status"] == "n/a"
    assert na["ve_cost"] > 1 and "limit" in na["reason"]


def test_ky_quantization_floor_ordering():
    bn = bn_repository_replica("alarm")
    lut = diag_oracle.ky_quantization_tv(bn, "lut_ky")["tv_max"]
    exact15 = diag_oracle.ky_quantization_tv(bn, "exact_ky")["tv_max"]
    # int8 LUT weights quantize far coarser than the 15-bit exact grid
    assert 0 <= exact15 < 1e-3 < lut < 0.05
    with pytest.raises(ValueError, match="KY concept"):
        diag_oracle.quantized_pmf(np.zeros(3), "cdf")


def test_chi_square_fused_ky_draws_match_quantized_pmf():
    """GOF capstone: draws from the fused KY datapath are distributed per
    the *quantized* pmf `diag.oracle.quantized_pmf` predicts.  A 1-node
    BN makes the Gibbs conditional the prior itself, so after one sweep
    each chain holds one iid KY draw; chi-square against the quantized
    target must accept at alpha=0.001 (df=3, crit 16.27) for both KY
    samplers, fused and unfused."""
    pmf = np.array([0.05, 0.15, 0.3, 0.5])
    bn = DiscreteBayesNet(
        cards=np.array([4]), parents=[[]], cpts=[pmf], name="one_node",
    )
    prog = compile_graph(bn)
    n = 4096
    for sampler in ("lut_ky", "exact_ky"):
        expected = n * diag_oracle.quantized_pmf(np.log(pmf), sampler)
        for fused in (False, True):
            marg, _ = prog.run(
                key=jax.random.key(11), n_chains=n, n_iters=1, burn_in=0,
                sampler=sampler, fused=fused,
            )
            counts = np.asarray(marg)[0] * n
            chi2 = float(((counts - expected) ** 2 / expected).sum())
            assert chi2 < 16.27, (sampler, fused, chi2)


# ---------------------------------------------------------------------------
# CLI: thresholds are the contract, exit codes are the API
# ---------------------------------------------------------------------------

_TINY = ["--models", "survey", "--variants", "unfused",
         "--n-chains", "16", "--n-iters", "80", "--burn-in", "20"]


def test_diag_cli_passes_with_sane_thresholds(tmp_path, capsys):
    out = tmp_path / "snap.json"
    rc = diag_main(_TINY + ["--rhat-threshold", "5", "--tv-threshold", "1",
                            "--ess-floor", "0", "--out", str(out)])
    assert rc == 0
    snap = json.loads(out.read_text())
    assert snap["n_errors"] == 0
    (row,) = snap["meta"]["rows"]
    assert (row["model"], row["variant"]) == ("survey", "unfused")
    assert row["oracle"] == "ok" and row["kept"] == 60
    assert "survey/unfused" in snap["meta"]["snapshots"]
    assert "| survey | unfused |" in capsys.readouterr().out


def test_diag_cli_exits_nonzero_on_injected_breach():
    # an impossible R-hat threshold forces a diag-threshold-breach
    rc = diag_main(_TINY + ["--rhat-threshold", "0.5", "--tv-threshold", "1",
                            "--ess-floor", "0"])
    assert rc == 1
    # an impossible ESS floor trips the other arm of the same rule
    rc = diag_main(_TINY + ["--rhat-threshold", "5", "--tv-threshold", "1",
                            "--ess-floor", "1e9"])
    assert rc == 1


def test_diag_cli_declares_oracle_na_as_warning():
    rep = quality_sweep(("survey",), ("unfused",), n_chains=16, n_iters=80,
                        burn_in=20, rhat_threshold=5.0, tv_threshold=1.0,
                        ess_floor=0.0, ve_limit=1)
    assert [f.rule for f in rep.warnings] == ["diag-oracle-unavailable"]
    assert rep.exit_code == 0  # n/a is declared, not failed
    assert rep.meta["rows"][0]["oracle"] == "n/a"


def test_diag_rules_registered():
    for rule, sev in (("diag-threshold-breach", "error"),
                      ("diag-oracle-unavailable", "warning"),
                      ("diag-accum-overflow", "error"),
                      ("diag-perf-regression", "error"),
                      ("diag-quality-regression", "error")):
        assert RULES[rule][0] == sev
        Finding(rule, "x", "y")  # constructible


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


def _fake_sweep_report(rows):
    return Report(meta={"rows": rows})


def test_check_regression_quality_tolerances(monkeypatch):
    baseline = {"quality": [
        {"model": "survey", "variant": "unfused",
         "rhat_max": 1.01, "ess_min": 1000.0, "tv_max": 0.010},
    ]}
    cur = {"model": "survey", "variant": "unfused",
           "rhat_max": 1.02, "ess_min": 900.0, "tv_max": 0.012}
    monkeypatch.setattr(
        "repro.diag.__main__.quality_sweep",
        lambda *a, **k: _fake_sweep_report([dict(cur)]),
    )
    rep = Report(meta={"quality_rows": []})
    check_regression.check_quality(baseline, rep)
    assert rep.exit_code == 0 and rep.meta["quality_compared"] == 1

    # each metric's tolerance trips independently
    for key, bad in (("rhat_max", 1.30), ("tv_max", 0.05),
                     ("ess_min", 100.0)):
        monkeypatch.setattr(
            "repro.diag.__main__.quality_sweep",
            lambda *a, **k: _fake_sweep_report([{**cur, key: bad}]),
        )
        rep = Report(meta={"quality_rows": []})
        check_regression.check_quality(baseline, rep)
        assert rep.exit_code == 1, key
        assert rep.findings[0].rule == "diag-quality-regression"
        assert key in rep.findings[0].message


def test_check_regression_schema1_baseline_skips_quality():
    rep = Report(meta={"quality_rows": []})
    check_regression.check_quality({"schema": 1}, rep)
    assert rep.exit_code == 0
    assert "no quality rows" in rep.meta["quality_note"]


def test_check_regression_perf_rows(monkeypatch):
    base = {"quick": True, "suites": {
        "coloring": [{"name": "a", "us_per_call": 10_000.0, "derived": ""}],
        "compile": [{"name": "b", "us_per_call": 100.0, "derived": ""}],
    }}
    monkeypatch.setattr(
        check_regression, "PERF_SUITES", ("coloring", "compile"))
    import benchmarks.run as run_mod
    monkeypatch.setitem(
        run_mod.SUITES, "coloring", lambda **k: ["a,50000.0,"])
    monkeypatch.setitem(
        run_mod.SUITES, "compile", lambda **k: ["b,90000.0,", "new,1.0,"])
    rep = Report(meta={"perf_rows": []})
    check_regression.check_perf(base, rep)
    # "a" regressed past 2x+slack; "b" sat below the noise floor and is
    # skipped; "new" has no baseline row and lands in perf_new
    assert [f.rule for f in rep.findings] == ["diag-perf-regression"]
    assert rep.meta["perf_compared"] == 1
    assert rep.meta["perf_new"] == ["new"]


def test_check_regression_missing_baseline_exit_2(tmp_path):
    rc = check_regression.main(
        ["--baseline", str(tmp_path / "nope.json")])
    assert rc == 2


def test_quality_table_renders_rows():
    from repro.launch.report import quality_table

    txt = quality_table([{
        "model": "survey", "variant": "fused", "n_nodes": 6,
        "n_chains": 64, "kept": 300, "rhat_max": 1.0144, "ess_min": 5819.0,
        "oracle": "ok", "tv_max": 0.0135, "maxabs_max": 0.0135,
        "ky_tv": 8.0e-3, "wall_s": 17.7,
    }, {
        "model": "water", "variant": "unfused", "n_nodes": 32,
        "n_chains": 64, "kept": 300, "rhat_max": 1.06, "ess_min": 7000.0,
        "oracle": "n/a", "tv_max": None, "maxabs_max": None,
        "ky_tv": 1.0e-2, "wall_s": 35.0,
    }])
    assert "| survey | fused |" in txt and "| n/a |" in txt


# ---------------------------------------------------------------------------
# sharded-route quality bit-identity (advisory multi-device CI job)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_quality_snapshot_bit_identical_8dev():
    """Satellite gate: the fused sharded engines thread the *same* quality
    accumulator through the shard_map body, so a sharded run's
    QualitySnapshot equals the single-device run's field for field — no
    demotion, no "diagnostics ran unsharded" asterisk (subprocess with 8
    simulated host devices, mirroring test_distributed_pm)."""
    import subprocess
    import textwrap
    from pathlib import Path

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.compile import compile_graph
        from repro.compile import ir as compile_ir
        from repro.core import compat
        from repro.core.graphs import GridMRF, random_bayesnet

        mesh = compat.make_mesh((2, 4), ("data", "model"))

        def assert_snap_equal(a, b):
            da, db = a.to_dict(), b.to_dict()
            assert da.keys() == db.keys()
            for k in da:
                x, y = da[k], db[k]
                if isinstance(x, str) or isinstance(y, str):
                    assert x == y, k
                elif x is None or y is None:
                    assert x is y, k
                else:
                    xa, ya = np.asarray(x), np.asarray(y)
                    if np.issubdtype(xa.dtype, np.floating):
                        assert np.array_equal(xa, ya, equal_nan=True), k
                    else:
                        assert x == y, k

        mrf = GridMRF(8, 16, 4, theta=1.1)
        prog = compile_graph(compile_ir.from_mrf(mrf))
        ev = jnp.zeros((8, 16), jnp.int32)
        lab1, snap1 = prog.run(jax.random.key(7), evidence=ev, n_chains=4,
                               n_iters=5, fused=True, diagnostics=True)
        lab2, snap2 = prog.run_sharded(jax.random.key(7), mesh, evidence=ev,
                                       n_chains=4, n_iters=5, fused=True,
                                       diagnostics=True)
        assert (np.asarray(lab1) == np.asarray(lab2)).all()
        assert_snap_equal(snap1, snap2)

        bn = random_bayesnet(12, seed=3)
        pbn = compile_graph(compile_ir.from_bayesnet(bn))
        kw = dict(n_chains=4, n_iters=6, burn_in=2, thin=2, fused=True,
                  diagnostics=True)
        m1, v1, sn1 = pbn.run(jax.random.key(11), **kw)
        m2, v2, sn2 = pbn.run_sharded(jax.random.key(11), mesh, **kw)
        assert (np.asarray(v1) == np.asarray(v2)).all()
        assert (np.asarray(m1) == np.asarray(m2)).all()
        assert_snap_equal(sn1, sn2)
        print("SHARDED_QUALITY_OK")
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
    assert "SHARDED_QUALITY_OK" in res.stdout
