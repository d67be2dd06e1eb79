"""repro.analysis: schedule-verifier fault injection (every mutation must be
caught with its structured rule id), kernel VMEM linter + batcher demotion,
repo-convention source lint, and the CLI / pipeline wiring."""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.analysis import Finding, RULES, Report
from repro.analysis import kernel_lint, source_lint
from repro.analysis import verify as verify_mod
from repro.analysis.__main__ import main as analysis_main
from repro.compile import ir as compile_ir
from repro.compile.passes import (
    named_pipeline,
    random_baseline_pipeline,
    run_pipeline,
)
from repro.compile.schedule import CommOp
from repro.core.graphs import (
    GridMRF,
    bn_repository_names,
    bn_repository_replica,
)

SRC_ROOT = pathlib.Path(source_lint.__file__).parents[1]  # .../src/repro


def _bn_ir(name="alarm", evidence=None):
    bn = bn_repository_replica(name)
    if evidence is not None:
        return compile_ir.from_bayesnet(bn, evidence)
    return compile_ir.from_bayesnet(bn, evidence_mode="runtime")


def _compiled(name="alarm", pipeline="default", evidence=None):
    g = _bn_ir(name, evidence)
    ctx = run_pipeline(g, (4, 4), named_pipeline(pipeline))
    return g, ctx


def _rules(findings):
    return {f.rule for f in findings}


def _verify(g, ctx, schedule=None, placement=..., diagnostics=None):
    return verify_mod.verify_schedule_static(
        g,
        schedule if schedule is not None else ctx.schedule,
        placement=ctx.placement if placement is ... else placement,
        diagnostics=diagnostics,
        adj=ctx.adj,
    )


# ---------------------------------------------------------------------------
# clean artifacts verify clean: the whole model zoo x both named pipelines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipeline", ["default", "runtime"])
def test_clean_sweep_zoo(pipeline):
    """Every zoo BN and a pair of MRFs lower cleanly through both named
    pipelines: VerifyPass runs by default, raises nothing, and no
    error-severity finding survives a full re-verify."""
    graphs = [_bn_ir(name) for name in bn_repository_names()]
    graphs += [
        compile_ir.from_mrf(GridMRF(8, 8, 3)),
        compile_ir.from_mrf(GridMRF(16, 16, 2)),
    ]
    for g in graphs:
        ctx = run_pipeline(g, (4, 4), named_pipeline(pipeline))
        assert ctx.diagnostics["verify"]["n_rules"] == len(
            verify_mod.VERIFY_RULES
        )
        findings = _verify(g, ctx, diagnostics=ctx.diagnostics)
        assert [f for f in findings if f.severity == "error"] == []


def test_verify_pass_is_default_in_every_named_pipeline():
    for pipeline in ("default", "runtime"):
        names = [p.name for p in named_pipeline(pipeline)]
        assert names[-1] == "verify"
    assert [p.name for p in random_baseline_pipeline()][-1] == "verify"


def test_verify_program_reports_clean():
    from repro.compile import clear_program_cache, compile_graph

    clear_program_cache()
    try:
        program = compile_graph(bn_repository_replica("survey"))
        report = verify_mod.verify_program(program)
        assert report.exit_code == 0
        assert report.meta["model"] == "survey"
        assert report.meta["n_rules"] == len(verify_mod.VERIFY_RULES)
        assert report.meta["verify_s"] >= 0
    finally:
        clear_program_cache()


# ---------------------------------------------------------------------------
# fault injection: every mutation is caught with its structured rule id
# ---------------------------------------------------------------------------


def test_injected_merged_rounds_race():
    """Merging two DSATUR rounds creates a same-round conflict edge — the
    parallel-Gibbs race the verifier exists to catch."""
    g, ctx = _compiled()
    r0, r1 = ctx.schedule.rounds[0], ctx.schedule.rounds[1]
    merged = dataclasses.replace(r0, nodes=r0.nodes + r1.nodes)
    bad = dataclasses.replace(
        ctx.schedule, rounds=(merged,) + ctx.schedule.rounds[2:]
    )
    assert "race-in-round" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_dropped_comm_op():
    g, ctx = _compiled()
    rounds = list(ctx.schedule.rounds)
    for i, r in enumerate(rounds):
        if r.comm:
            rounds[i] = dataclasses.replace(r, comm=r.comm[1:])
            break
    else:
        pytest.skip("no comm ops on this mesh")
    bad = dataclasses.replace(ctx.schedule, rounds=tuple(rounds))
    assert "comm-missing" in _rules(_verify(g, ctx, schedule=bad))


def _tamper_first_comm(schedule, **changes):
    rounds = list(schedule.rounds)
    for i, r in enumerate(rounds):
        if r.comm:
            op = dataclasses.replace(r.comm[0], **changes)
            rounds[i] = dataclasses.replace(r, comm=(op,) + r.comm[1:])
            return dataclasses.replace(schedule, rounds=tuple(rounds))
    pytest.skip("no comm ops on this mesh")


def test_injected_wrong_mechanism():
    g, ctx = _compiled()
    bad = _tamper_first_comm(ctx.schedule, mechanism="ppermute_halo")
    assert "comm-mechanism" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_wrong_bytes():
    g, ctx = _compiled()
    op0 = next(r.comm[0] for r in ctx.schedule.rounds if r.comm)
    bad = _tamper_first_comm(ctx.schedule, n_bytes=op0.n_bytes + 4)
    assert "comm-bytes" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_wrong_hops():
    g, ctx = _compiled()
    op0 = next(r.comm[0] for r in ctx.schedule.rounds if r.comm)
    bad = _tamper_first_comm(ctx.schedule, hops=op0.hops + 1)
    assert "comm-hops" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_spurious_comm_is_warning():
    """A core-0 -> core-0 op matches no cross-core edge: flagged, but as a
    warning (the cost model overcharges; the samples stay correct)."""
    g, ctx = _compiled()
    r0 = ctx.schedule.rounds[0]
    ghost = CommOp("psum_broadcast", 0, 0, 4, 0)
    bad = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, comm=r0.comm + (ghost,)),)
        + ctx.schedule.rounds[1:],
    )
    findings = _verify(g, ctx, schedule=bad)
    spurious = [f for f in findings if f.rule == "comm-spurious"]
    assert spurious and all(f.severity == "warning" for f in spurious)


def test_injected_clamped_node_in_round():
    g, ctx = _compiled(evidence={0: 0})
    assert g.evidence  # node 0 is clamped
    r0 = ctx.schedule.rounds[0]
    bad = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, nodes=r0.nodes + (0,)),)
        + ctx.schedule.rounds[1:],
    )
    assert "clamp-resampled" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_duplicate_node():
    g, ctx = _compiled()
    r0 = ctx.schedule.rounds[0]
    bad = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, nodes=r0.nodes + (r0.nodes[0],)),)
        + ctx.schedule.rounds[1:],
    )
    assert "node-dup" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_orphaned_and_unknown_nodes():
    g, ctx = _compiled()
    r0 = ctx.schedule.rounds[0]
    orphaned = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, nodes=r0.nodes[1:]),)
        + ctx.schedule.rounds[1:],
    )
    assert "coverage" in _rules(_verify(g, ctx, schedule=orphaned))
    unknown = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, nodes=r0.nodes + (g.n_nodes + 5,)),)
        + ctx.schedule.rounds[1:],
    )
    assert "coverage" in _rules(_verify(g, ctx, schedule=unknown))


def test_injected_off_mesh_placement():
    g, ctx = _compiled()
    arr = np.asarray(ctx.placement.placement).copy()
    arr[0] = ctx.schedule.n_cores  # one past the last core
    bad = dataclasses.replace(ctx.placement, placement=arr)
    assert "placement-range" in _rules(_verify(g, ctx, placement=bad))


def test_injected_core_load_tamper():
    g, ctx = _compiled()
    r0 = ctx.schedule.rounds[0]
    load = list(r0.core_load)
    load[0] += 1
    bad = dataclasses.replace(
        ctx.schedule,
        rounds=(dataclasses.replace(r0, core_load=tuple(load)),)
        + ctx.schedule.rounds[1:],
    )
    assert "placement-load" in _rules(_verify(g, ctx, schedule=bad))


def test_injected_cost_diagnostics_tamper():
    g, ctx = _compiled()
    diag = dict(ctx.diagnostics)
    diag["schedule_cost"] = dict(
        diag["schedule_cost"], total_cycles=diag["schedule_cost"]["total_cycles"] + 1
    )
    assert "cost-model" in _rules(_verify(g, ctx, diagnostics=diag))
    diag2 = dict(ctx.diagnostics, critical_core_load=10**6)
    assert "cost-model" in _rules(_verify(g, ctx, diagnostics=diag2))


def test_injected_full_parity_pins():
    """`from_mrf` rejects full-parity pins at construction; the verifier is
    the second line of defense for IRs that arrive by other routes."""
    mrf = GridMRF(4, 4, 3)
    g = compile_ir.from_mrf(mrf)
    ctx = run_pipeline(g, (2, 2), named_pipeline("default"))
    parity0 = tuple(
        (r * 4 + c, 0) for r in range(4) for c in range(4) if (r + c) % 2 == 0
    )
    pinned = dataclasses.replace(g, evidence=parity0)
    findings = verify_mod.verify_schedule_static(
        pinned, ctx.schedule, adj=ctx.adj
    )
    assert "pin-full-parity" in _rules(findings)


# ---------------------------------------------------------------------------
# the error type: explicit raise, -O survival, AssertionError back-compat
# ---------------------------------------------------------------------------


def test_raise_on_errors_is_structured_assertion_error():
    f = Finding(rule="race-in-round", loc="t", message="injected")
    with pytest.raises(verify_mod.ScheduleVerificationError) as ei:
        verify_mod.raise_on_errors([f])
    assert isinstance(ei.value, AssertionError)  # legacy pytest.raises sites
    assert ei.value.findings == (f,)
    assert "race-in-round" in str(ei.value)
    verify_mod.raise_on_errors([])  # no errors -> no raise


def test_coloring_violation_raises_under_python_O():
    """The checks that used to be `assert verify_coloring(...)` must still
    fire when assertions are stripped."""
    code = textwrap.dedent(
        """
        import numpy as np
        from repro.analysis import verify
        assert True is not None or True  # stripped under -O, proving the mode
        try:
            verify.require_proper_coloring(
                [{1}, {0}], np.zeros(2, np.int64), loc="sabotage"
            )
        except verify.ScheduleVerificationError as e:
            print("CAUGHT", e.findings[0].rule)
        """
    )
    env = dict(os.environ, PYTHONPATH=str(SRC_ROOT.parent))
    out = subprocess.run(
        [sys.executable, "-O", "-c", code],
        capture_output=True, text=True, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "CAUGHT race-in-round" in out.stdout


# ---------------------------------------------------------------------------
# kernel resource linter + batcher demotion
# ---------------------------------------------------------------------------


def test_ky_lanes_constant_pinned_to_kernel():
    from repro.kernels import ky_sampler

    assert kernel_lint.KY_LANES == ky_sampler.LANES


def test_bn_footprint_scales_with_chains_mrf_does_not():
    pigs = _bn_ir("pigs")
    wide = kernel_lint.bn_fused_footprint(pigs, 256)
    narrow = kernel_lint.bn_fused_footprint(pigs, 32)
    assert wide.total_bytes > narrow.total_bytes
    assert wide.total_bytes > kernel_lint.vmem_budget()  # the demotion story
    # the planar layout puts pigs' serving width well inside the budget
    assert narrow.total_bytes <= kernel_lint.vmem_budget()
    assert wide.breakdown["scope_vals"] == 8 * narrow.breakdown["scope_vals"]
    mrf = compile_ir.from_mrf(GridMRF(64, 64, 4))
    a = kernel_lint.mrf_fused_footprint(mrf, 32)
    b = kernel_lint.mrf_fused_footprint(mrf, 1)
    # chains vmap the grid: per-step residency is one tile either way
    assert a.total_bytes == b.total_bytes
    assert a.total_bytes <= kernel_lint.vmem_budget()


def test_penguin_fits_the_fused_budget():
    """The served Penguin bucket (179 x 122, 256 labels, 8 chains) routes
    fused: the planar estimate counts the kernel's tile rows, W padded to
    128 lanes, one energy and one weight plane per label — no 128-lane
    row per site."""
    from repro.kernels import mrf_tiles

    mrf = GridMRF(179, 122, 256, theta=1.0, h=0.04, data_cost="quadratic",
                  pairwise="truncated_quadratic", truncation=200,
                  name="penguin")
    fp = kernel_lint.mrf_fused_footprint(compile_ir.from_mrf(mrf), 8)
    rows = mrf_tiles.tile_rows(179, 122, 256)
    assert fp.breakdown["label_planes"] == 513 * rows * 128 * 4
    assert fp.total_bytes <= kernel_lint.vmem_budget()
    assert kernel_lint.fused_fits(compile_ir.from_mrf(mrf), 8)


def test_footprint_findings_severity():
    pigs = _bn_ir("pigs")
    fp = kernel_lint.bn_fused_footprint(pigs, 256)
    demoted = fp.findings()
    assert [f.rule for f in demoted] == ["vmem-budget"]
    assert demoted[0].severity == "warning"  # batcher guard makes it advisory
    forced = fp.findings(demotable=False)
    assert forced[0].severity == "error"
    # just over the pressure threshold, under the budget -> warning only
    pressured = fp.findings(budget=int(fp.total_bytes / 0.8))
    assert [f.rule for f in pressured] == ["vmem-pressure"]
    assert fp.findings(budget=fp.total_bytes * 10) == []


def test_batcher_demotes_oversized_fused_bucket():
    """The acceptance story: a deliberately oversized fused bucket is
    demoted by the static estimate inside `bucket_key`, not OOMed."""
    from repro.runtime import batcher

    g = _bn_ir("pigs")
    wide = batcher.Query(qid=0, model="pigs", n_chains=256)
    key = batcher.bucket_key(wide, g, "schedule", fused=True)
    assert key.fused is False  # ~23.7 MiB estimate vs the 16 MiB budget
    narrow = batcher.Query(qid=1, model="pigs", n_chains=32)
    assert batcher.bucket_key(narrow, g, "schedule", fused=True).fused is True
    # shrink the budget and the same narrow bucket demotes too
    prev = kernel_lint.set_vmem_budget(1 << 16)
    try:
        key = batcher.bucket_key(narrow, g, "schedule", fused=True)
        assert key.fused is False
    finally:
        kernel_lint.set_vmem_budget(prev)
    assert batcher.bucket_key(narrow, g, "schedule", fused=True).fused is True


def test_sharded_footprints_budget_the_per_shard_envelope():
    """Satellite: `shard_width > 1` budgets what each device of the
    shard_map body actually allocates — the local row slab plus halo rows
    on the grid, the owned node slice on the BN — not the whole model."""
    mrf = compile_ir.from_mrf(GridMRF(64, 64, 4))
    whole = kernel_lint.mrf_fused_footprint(mrf, 8)
    sh = kernel_lint.mrf_fused_footprint(mrf, 8, shard_width=4)
    assert sh.shard_width == 4
    assert "halo_rows" in sh.breakdown and "halo_rows" not in whole.breakdown
    # the 16-row local slab (64 rows / 4 shards) undercuts the 32-row
    # block_h tile even after paying the two halo rows
    assert sh.total_bytes < whole.total_bytes
    over = sh.findings(budget=1)
    assert over and "@sh4" in over[0].loc  # findings name the slice width
    pigs = _bn_ir("pigs")
    bn_whole = kernel_lint.bn_fused_footprint(pigs, 256)
    bn_sh = kernel_lint.bn_fused_footprint(pigs, 256, shard_width=4)
    assert bn_sh.total_bytes < bn_whole.total_bytes
    # the pigs-class demotion story inverts on a slice: the whole envelope
    # busts the budget, the per-device owned node slice fits
    assert bn_whole.total_bytes > kernel_lint.vmem_budget()
    assert bn_sh.total_bytes <= kernel_lint.vmem_budget()


def test_fused_fits_judges_sharded_buckets_per_shard():
    """The demotion oracle keys on the slice width: a bucket too wide for
    one core's VMEM stays fused when it will run the shard_map body."""
    from repro.runtime import batcher

    g = _bn_ir("pigs")
    assert not kernel_lint.fused_fits(g, 256)
    assert kernel_lint.fused_fits(g, 256, shard_width=4)
    assert kernel_lint.fused_fits(g, 32)
    wide = batcher.Query(qid=0, model="pigs", n_chains=256)
    assert batcher.bucket_key(wide, g, "schedule", fused=True).fused is False
    assert batcher.bucket_key(
        wide, g, "schedule", fused=True, shard_width=4
    ).fused is True


# ---------------------------------------------------------------------------
# repo-convention source lint
# ---------------------------------------------------------------------------


def _lint_snippet(tmp_path, rel, code):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return source_lint.lint_file(p, root=tmp_path)


def test_lint_wallclock_in_sim_and_pragma(tmp_path):
    code = """
        import time

        def tick():
            return time.perf_counter()
        """
    found = _lint_snippet(tmp_path, "repro/runtime/engine.py", code)
    assert [f.rule for f in found] == ["wallclock-in-sim"]
    allowed = code.replace(
        "time.perf_counter()",
        "time.perf_counter()  # lint: allow[wallclock-in-sim]",
    )
    assert _lint_snippet(tmp_path, "repro/runtime/engine.py", allowed) == []
    # same call outside the sim scope is fine
    assert _lint_snippet(tmp_path, "repro/launch/bench.py", code) == []


def test_lint_compat_import(tmp_path):
    code = """
        from jax.experimental import pallas as pl
        """
    found = _lint_snippet(tmp_path, "repro/kernels/new_kernel.py", code)
    assert [f.rule for f in found] == ["compat-import"]
    # compat.py itself is the one allowed importer
    assert _lint_snippet(tmp_path, "repro/core/compat.py", code) == []


def test_lint_pyrandom_in_jit(tmp_path):
    code = """
        import functools
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            return x + np.random.rand()

        @functools.partial(jax.jit, static_argnames=("n",))
        def also_bad(x, n):
            return x + np.random.rand()

        def fine(x):
            return x + np.random.rand()
        """
    found = _lint_snippet(tmp_path, "repro/core/newmod.py", code)
    assert [f.rule for f in found] == ["pyrandom-in-jit"] * 2


def test_lint_bare_assert_scope(tmp_path):
    code = """
        def check(x):
            assert x > 0
        """
    found = _lint_snippet(tmp_path, "repro/compile/newpass.py", code)
    assert [f.rule for f in found] == ["bare-assert"]
    # tests/benchmark-style modules outside the pipeline scope are exempt
    assert _lint_snippet(tmp_path, "repro/runtime/helpers.py", code) == []


def test_lint_syntax_error_is_a_finding(tmp_path):
    found = _lint_snippet(tmp_path, "repro/compile/broken.py", "def f(:\n")
    assert len(found) == 1 and found[0].severity == "error"


def test_repo_lints_clean():
    """The shipped tree obeys its own conventions (pragmas included)."""
    findings = source_lint.lint_repo(SRC_ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# finding model + report spine
# ---------------------------------------------------------------------------


def test_finding_model():
    with pytest.raises(ValueError):
        Finding(rule="no-such-rule", loc="x", message="m")
    f = Finding(rule="race-in-round", loc="m:round 0", message="boom")
    assert f.severity == RULES["race-in-round"][0] == "error"
    assert "error[race-in-round]" in f.render()
    r = Report(findings=[f])
    assert r.exit_code == 1 and len(r.errors) == 1
    d = json.loads(r.to_json())
    assert d["schema"] == 1 and d["n_errors"] == 1
    assert d["findings"][0]["rule"] == "race-in-round"
    assert Report().exit_code == 0


# ---------------------------------------------------------------------------
# CLI: exit codes, JSON artifact, verification table
# ---------------------------------------------------------------------------


def test_cli_clean_repo_exits_zero(capsys, tmp_path):
    out = tmp_path / "findings.json"
    rc = analysis_main([
        "--skip-verify", "--skip-kernels", "--format", "json",
        "--out", str(out),
    ])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["n_errors"] == 0
    assert data["meta"]["analyzers"] == ["source_lint"]


def test_cli_injected_violation_exits_nonzero(tmp_path):
    bad = tmp_path / "repro" / "compile" / "sabotage.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(x):\n    assert x\n")
    out = tmp_path / "findings.json"
    rc = analysis_main([
        "--skip-verify", "--skip-kernels", "--root", str(tmp_path),
        "--format", "json", "--out", str(out),
    ])
    assert rc == 1
    data = json.loads(out.read_text())
    assert data["n_errors"] == 1
    assert data["findings"][0]["rule"] == "bare-assert"


def test_cli_verify_sweep_and_table(capsys):
    rc = analysis_main(["--skip-lint", "--skip-kernels", "--models", "survey"])
    assert rc == 0
    text = capsys.readouterr().out
    assert "| survey | bn | default |" in text
    assert "clean" in text


def test_verification_table_renders():
    from repro.launch.report import verification_table

    rows = [{
        "model": "alarm", "kind": "bn", "pipeline": "runtime",
        "n_nodes": 37, "n_rounds": 5, "n_rules": 14, "n_findings": 0,
        "verify_s": 0.0004,
    }]
    table = verification_table(rows)
    assert "| alarm | bn | runtime | 37 | 5 | 14 | clean |" in table
