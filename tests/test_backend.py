"""Schedule-direct execution backend: bit-exactness with the eager engines
on BN and MRF workloads for every sampler, legality re-verification at
lowering, the fused Pallas round path, and backend argument plumbing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import (
    BNScheduleExec,
    MRFScheduleExec,
    ScheduleLoweringError,
    clear_program_cache,
    compile_graph,
    cross_check,
    lower_schedule,
)
from repro.compile.backend import BackendMismatch
from repro.compile.schedule import Round, Schedule, verify_schedule
from repro.core import mrf as mrf_mod
from repro.core.draws import SAMPLERS
from repro.core.graphs import GridMRF, bn_repository_replica


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_program_cache()
    yield
    clear_program_cache()


# ---------------------------------------------------------------------------
# Bit-exactness: schedule backend == eager backend, every sampler
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", ["survey", "alarm"])
@pytest.mark.parametrize("sampler", SAMPLERS)
def test_bn_schedule_bit_exact(workload, sampler):
    prog = compile_graph(bn_repository_replica(workload), evidence={0: 0})
    kwargs = dict(n_chains=4, n_iters=12, burn_in=3, sampler=sampler)
    marg_e, vals_e = prog.run(jax.random.key(9), backend="eager", **kwargs)
    marg_s, vals_s = prog.run(jax.random.key(9), backend="schedule", **kwargs)
    np.testing.assert_array_equal(np.asarray(vals_e), np.asarray(vals_s))
    np.testing.assert_array_equal(np.asarray(marg_e), np.asarray(marg_s))


@pytest.mark.parametrize("sampler", SAMPLERS)
def test_mrf_schedule_bit_exact(sampler):
    mrf = GridMRF(8, 8, 3, theta=1.2, h=2.0)
    _, noisy = mrf_mod.make_denoising_problem(8, 8, 3, 0.25, seed=1)
    ev = jnp.asarray(noisy)
    prog = compile_graph(mrf)
    kwargs = dict(n_chains=2, n_iters=8, sampler=sampler, evidence=ev)
    lab_e = prog.run(jax.random.key(5), backend="eager", **kwargs)
    lab_s = prog.run(jax.random.key(5), backend="schedule", **kwargs)
    np.testing.assert_array_equal(np.asarray(lab_e), np.asarray(lab_s))


def test_mrf_fused_rounds_bit_exact():
    """The Pallas round path derives its random words exactly as
    draw_from_logits does, so fused lut_ky == eager lut_ky, bit for bit."""
    mrf = GridMRF(8, 8, 4, theta=1.0, h=1.5)
    _, noisy = mrf_mod.make_denoising_problem(8, 8, 4, 0.3, seed=2)
    ev = jnp.asarray(noisy)
    prog = compile_graph(mrf)
    lab_e = prog.run(jax.random.key(3), n_chains=2, n_iters=5, evidence=ev,
                     backend="eager")
    lab_f = prog.run(
        jax.random.key(3), n_chains=2, n_iters=5, evidence=ev,
        backend="schedule", fused=True,
    )
    np.testing.assert_array_equal(np.asarray(lab_e), np.asarray(lab_f))


def test_fused_requires_schedule_backend_and_fused_samplers():
    mrf_prog = compile_graph(GridMRF(4, 4, 2))
    ev = jnp.zeros((4, 4), jnp.int32)
    with pytest.raises(ValueError):  # fused needs the schedule backend
        mrf_prog.run(jax.random.key(0), evidence=ev, fused=True,
                     backend="eager")
    with pytest.raises(ValueError):
        mrf_prog.run(
            jax.random.key(0), evidence=ev, backend="schedule", fused=True,
            sampler="cdf",
        )
    # BN fused rounds exist since the bn_gibbs kernel landed: lut_ky runs,
    # samplers outside the kernel's datapath still fail loudly
    bn_prog = compile_graph(bn_repository_replica("survey"))
    bn_prog.run(jax.random.key(0), n_chains=2, n_iters=2,
                backend="schedule", fused=True)
    with pytest.raises(ValueError):
        bn_prog.run(jax.random.key(0), backend="schedule", fused=True,
                    sampler="gumbel")


def test_unknown_backend_rejected():
    prog = compile_graph(bn_repository_replica("survey"))
    with pytest.raises(ValueError):
        prog.run(jax.random.key(0), backend="pallas")


# ---------------------------------------------------------------------------
# Lowering: legality re-verification + structure checks
# ---------------------------------------------------------------------------


def test_lowering_reverifies_legality():
    """A corrupted schedule (node scheduled twice) must fail at lowering,
    before any round-ordered execution happens."""
    prog = compile_graph(bn_repository_replica("survey"))
    r0 = prog.schedule.rounds[0]
    dup = dataclasses.replace(
        prog.schedule.rounds[1],
        nodes=prog.schedule.rounds[1].nodes + (r0.nodes[0],),
    )
    bad_sched = Schedule(
        rounds=(r0, dup) + prog.schedule.rounds[2:],
        mesh_shape=prog.schedule.mesh_shape,
    )
    bad_prog = dataclasses.replace(prog, schedule=bad_sched)
    with pytest.raises(AssertionError):
        lower_schedule(bad_prog)


def test_legality_holds_after_round_ordered_execution():
    """Executing via the schedule does not mutate it: the rounds the backend
    ran from still verify as a legal partition afterwards."""
    for model, ev in ((bn_repository_replica("alarm"), {0: 1}),
                      (GridMRF(8, 8, 2), None)):
        prog = compile_graph(model, evidence=ev)
        if prog.kind == "bn":
            prog.run(jax.random.key(0), n_chains=2, n_iters=4,
                     backend="schedule")
        else:
            prog.run(jax.random.key(0), n_chains=2, n_iters=4,
                     evidence=jnp.zeros((8, 8), jnp.int32),
                     backend="schedule")
        verify_schedule(prog.ir, prog.schedule)


def test_bn_lowering_builds_round_ordered_groups():
    prog = compile_graph(bn_repository_replica("alarm"), evidence={3: 0})
    ex = lower_schedule(prog)
    assert isinstance(ex, BNScheduleExec)
    assert len(ex.round_groups) == len(prog.schedule.rounds)
    for g, r in zip(ex.round_groups, prog.schedule.rounds):
        assert tuple(int(v) for v in np.asarray(g.nodes)) == r.nodes


def test_mrf_lowering_extracts_checkerboard_parities():
    prog = compile_graph(GridMRF(6, 6, 3))
    ex = lower_schedule(prog)
    assert isinstance(ex, MRFScheduleExec)
    assert sorted(ex.parities) == [0, 1]
    for parity, r in zip(ex.parities, prog.schedule.rounds):
        for v in r.nodes:
            assert (v // 6 + v % 6) % 2 == parity


def test_mrf_partial_parity_round_rejected():
    """A legal schedule that splits one parity class into two rounds has no
    lowering in the whole-parity grid path: it must fail loudly at lowering,
    not execute a different plan than was compiled."""
    prog = compile_graph(GridMRF(4, 4, 2))
    r0, r1 = prog.schedule.rounds
    half = len(r0.nodes) // 2
    split = (
        dataclasses.replace(r0, nodes=r0.nodes[:half]),
        dataclasses.replace(r0, color=2, nodes=r0.nodes[half:]),
        r1,
    )
    bad_prog = dataclasses.replace(
        prog, schedule=Schedule(rounds=split, mesh_shape=(4, 4))
    )
    with pytest.raises(ScheduleLoweringError):
        lower_schedule(bad_prog)


def test_mrf_mixed_parity_round_rejected():
    prog = compile_graph(GridMRF(4, 4, 2))
    r0, r1 = prog.schedule.rounds
    merged = Round(
        color=0, nodes=tuple(sorted(r0.nodes + r1.nodes)), comm=(),
        core_load=r0.core_load,
    )
    bad_prog = dataclasses.replace(
        prog,
        schedule=Schedule(rounds=(merged,), mesh_shape=(4, 4)),
    )
    with pytest.raises((ScheduleLoweringError, AssertionError)):
        lower_schedule(bad_prog)


# ---------------------------------------------------------------------------
# Cross-check: the compile-time bit-exactness guarantee
# ---------------------------------------------------------------------------


def test_cross_check_passes_and_is_cached():
    prog = compile_graph(
        bn_repository_replica("survey"), evidence={1: 0}, cross_check=True,
    )
    ex = prog.schedule_executable()
    assert prog.schedule_executable() is ex  # lowered + checked once


def test_cross_check_catches_divergent_lowering():
    """An executable whose rounds differ from the schedule's (here: reversed
    round order) must be flagged as a backend mismatch."""
    prog = compile_graph(bn_repository_replica("alarm"), evidence={0: 0})
    ex = lower_schedule(prog)
    ex.round_groups = list(reversed(ex.round_groups))
    with pytest.raises(BackendMismatch):
        cross_check(prog, ex)


# ---------------------------------------------------------------------------
# compat: the interpret switch and the compile-cache placement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,interpret", [
    ("tpu", False), ("cpu", True), ("gpu", None),
])
def test_pallas_interpret_follows_the_backend(monkeypatch, backend,
                                              interpret):
    """Kernels compile on TPU, interpret on CPU, and refuse anything else
    rather than silently interpreting on an accelerator."""
    from repro.core import compat

    monkeypatch.setattr(compat.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            compat.pallas_interpret()
    else:
        assert compat.pallas_interpret() is interpret


def test_compile_cache_dir_from_env_else_checkout(monkeypatch, tmp_path):
    from repro.core import compat

    set_dirs = []
    monkeypatch.setattr(
        compat.jax.config, "update",
        lambda name, value: set_dirs.append((name, value)),
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compat.setup_compile_cache() == str(tmp_path)
    assert set_dirs == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(compat.CHECKOUT / ".jax_cache")
    assert compat.setup_compile_cache() == want
    assert set_dirs == [("jax_compilation_cache_dir", want)]
    assert (compat.CHECKOUT / "src" / "repro").is_dir()
