"""Distributed (shard_map) chromatic Gibbs — runs in a subprocess with 8
simulated host devices so the main test process keeps a single device."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import compat

    mesh = compat.make_mesh((2, 4), ("data", "model"))

    from repro.core.graphs import GridMRF, random_bayesnet
    from repro.core import mrf as mrf_mod
    from repro.core import bayesnet as bnet
    from repro.core.distributed import bn_gibbs_sharded, mrf_gibbs_sharded
    from repro.core.exact import ve_marginal

    # MRF: halo-exchange Gibbs must denoise as well as single-device
    clean, noisy = mrf_mod.make_denoising_problem(32, 32, 3, 0.25, seed=1)
    m = GridMRF(32, 32, 3, theta=1.2, h=2.0)
    lab = mrf_gibbs_sharded(m, jnp.asarray(noisy), jax.random.key(0), mesh,
                            n_chains=4, n_iters=30)
    assert lab.shape == (4, 32, 32)
    err = (np.asarray(lab[0]) != clean).mean()
    base = (noisy != clean).mean()
    assert err < base / 2, (err, base)

    # determinism given the key
    lab2 = mrf_gibbs_sharded(m, jnp.asarray(noisy), jax.random.key(0), mesh,
                             n_chains=4, n_iters=30)
    assert (np.asarray(lab) == np.asarray(lab2)).all()

    # BN: sharded chromatic Gibbs converges to exact marginals
    bn = random_bayesnet(12, max_parents=3, cards=(2, 3), seed=3)
    ev = {1: 0}
    cbn = bnet.compile_bayesnet(bn, evidence=ev)
    marg, vals = bn_gibbs_sharded(cbn, jax.random.key(1), mesh,
                                  n_chains=32, n_iters=400, burn_in=100)
    marg = np.asarray(marg)
    tv = max(0.5 * np.abs(marg[q][:bn.cards[q]] - ve_marginal(bn, q, ev)).sum()
             for q in range(12) if q not in ev)
    assert tv < 0.05, tv
    vals = np.asarray(vals)
    assert (vals[:, 1] == 0).all()  # evidence respected on every shard
    print("DISTRIBUTED_PM_OK")
    """
)


@pytest.mark.slow
def test_distributed_pm_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DISTRIBUTED_PM_OK" in res.stdout


_BOUNDARY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp

    from repro.compile import compile_graph
    from repro.compile import ir as compile_ir
    from repro.core import compat
    from repro.core.graphs import GridMRF, random_bayesnet

    mesh = compat.make_mesh((2, 4), ("data", "model"))

    # MRF (lut_ky, the fused grid sampler): a query sliced across the
    # shard-route boundary — sharded first leg, vmap second — equals the
    # unsliced sharded run bit for bit, because both legs execute the one
    # fused Pallas datapath and the carry is the whole chain state
    mrf = GridMRF(8, 16, 4, theta=1.1)
    prog = compile_graph(compile_ir.from_mrf(mrf))
    ev = jnp.zeros((8, 16), jnp.int32)
    key = jax.random.key(7)
    full = prog.run_sharded(key, mesh, evidence=ev, n_chains=4, n_iters=5,
                            fused=True)
    _, st = prog.run_sharded(key, mesh, evidence=ev, n_chains=4, n_iters=2,
                             fused=True, return_state=True)
    resumed = prog.run(None, evidence=ev, n_chains=4, n_iters=3, fused=True,
                       carry_state=st)
    assert (np.asarray(full) == np.asarray(resumed)).all()
    # and the reverse crossing: vmap first leg, sharded second
    _, st2 = prog.run(key, evidence=ev, n_chains=4, n_iters=2, fused=True,
                      return_state=True)
    resumed2 = prog.run_sharded(None, mesh, evidence=ev, n_chains=4,
                                n_iters=3, fused=True, carry_state=st2)
    assert (np.asarray(full) == np.asarray(resumed2)).all()
    print("MRF_BOUNDARY_OK")

    # BN: both fused samplers cross the boundary bit-exactly, marginals
    # (burn-in and thinning mid-stride) included
    bn = random_bayesnet(12, seed=3)
    pbn = compile_graph(compile_ir.from_bayesnet(bn))
    for sampler in ("lut_ky", "exact_ky"):
        base = dict(n_chains=4, burn_in=2, thin=2, sampler=sampler,
                    fused=True)
        kb = jax.random.key(11)
        m_full, v_full = pbn.run_sharded(kb, mesh, n_iters=6, **base)
        _, _, st = pbn.run_sharded(kb, mesh, n_iters=3, return_state=True,
                                   **base)
        m2, v2 = pbn.run(None, n_iters=3, carry_state=st, **base)
        assert (np.asarray(v_full) == np.asarray(v2)).all()
        assert (np.asarray(m_full) == np.asarray(m2)).all()
        print(f"BN_BOUNDARY_{sampler}_OK")
    print("SHARD_BOUNDARY_OK")
    """
)


@pytest.mark.slow
def test_fused_shard_route_boundary_8dev():
    """Satellite gate: chain state carried across the sharded/vmap route
    boundary reproduces the unsliced sharded run's bits, for every fused
    sampler (grid lut_ky; BN lut_ky and exact_ky)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _BOUNDARY_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARD_BOUNDARY_OK" in res.stdout


_TQ_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp

    from repro.compile import compile_graph
    from repro.compile import ir as compile_ir
    from repro.core import compat
    from repro.core.distributed import mrf_gibbs_sharded
    from repro.core.graphs import GridMRF

    mesh = compat.make_mesh((2, 4), ("data", "model"))
    # 20 rows over 4 shards: 5-row slabs, each padded to an 8-row tile
    # whose first padded row is the neighbor shard's halo
    mrf = GridMRF(20, 16, 8, theta=0.8, h=0.1, data_cost="quadratic",
                  pairwise="truncated_quadratic", truncation=9.0)
    prog = compile_graph(compile_ir.from_mrf(mrf))
    rng = np.random.default_rng(0)
    ev = rng.integers(0, 8, (20, 16))
    ev = jnp.asarray(np.where(rng.random((20, 16)) < 0.2, -1, ev), jnp.int32)
    key = jax.random.key(7)
    run = dict(evidence=ev, n_chains=4, n_iters=2)
    sharded = prog.run_sharded(key, mesh, fused=True, **run)
    single = prog.run(key, fused=True, **run)
    unfused = prog.run(key, **run)
    assert (np.asarray(sharded) == np.asarray(single)).all()
    assert (np.asarray(single) == np.asarray(unfused)).all()
    print("TQ_SHARDED_FUSED_OK")
    # the legacy sharded engine computes Potts only, and says so
    try:
        mrf_gibbs_sharded(mrf, ev, key, mesh, n_chains=4, n_iters=1)
    except ValueError as e:
        assert "pairwise" in str(e), e
        print("LEGACY_REFUSES_TQ_OK")
    """
)


@pytest.mark.slow
def test_fused_sharded_truncated_quadratic_8dev():
    """A truncated-quadratic grid with masked pixels: the sharded fused
    route (row slabs padded to whole tiles) equals one device's fused
    route and the unfused route bit for bit, and the legacy Potts-only
    sharded engine refuses the model."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _TQ_SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TQ_SHARDED_FUSED_OK" in res.stdout
    assert "LEGACY_REFUSES_TQ_OK" in res.stdout
