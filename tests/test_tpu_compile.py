"""The Pallas kernels of the serving path compile for a TPU v5e.

No chip is attached: the TPU compiler compiles for a *described* v5e:2x2
topology, at the sizes the serving path runs, and each executable must hold
a Mosaic kernel (`tpu_custom_call`).  Interpret-mode tests cannot catch what
these do: tiling-rule violations, primitives Mosaic does not lower, layouts
it cannot relay out.

The topology is described inside a fixture — never at import — because
only one process at a time may load the TPU library: a worker that
describes it at collection would make the other workers fail.  The
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.interp import build_exp_weight_lut
from repro.obs.profile import MOSAIC_CALL


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_ky_sample_kernel_compiles(one_chip):
    from repro.kernels.ky_sampler import ky_sample_kernel

    text = _compiled_text(
        lambda w, words: ky_sample_kernel(w, words, n_bins=10),
        _on(one_chip, (1024, 128), jnp.int32),
        _on(one_chip, (1024, 4), jnp.uint32),
    )
    assert MOSAIC_CALL in text


def test_interp_kernel_compiles(one_chip):
    from repro.kernels.interp_lut import interp_kernel

    _, spec = build_exp_weight_lut()
    text = _compiled_text(
        lambda x, tab: interp_kernel(x, tab, spec=spec),
        _on(one_chip, (1024, 128), jnp.float32),
        _on(one_chip, (1, 128), jnp.float32),
    )
    assert MOSAIC_CALL in text


@pytest.mark.parametrize("height,width,n_labels,block_h", [
    (64, 64, 4, 32),  # penguin
    (48, 48, 8, 24),  # art
])
def test_mrf_half_step_kernel_compiles(one_chip, height, width, n_labels,
                                       block_h):
    from repro.kernels.mrf_gibbs import mrf_half_step_kernel

    _, spec = build_exp_weight_lut()
    text = _compiled_text(
        lambda lab, ev, words, tab: mrf_half_step_kernel(
            lab, ev, words, tab, parity=1, theta=1.2, h=2.0,
            n_labels=n_labels, spec=spec, block_h=block_h,
        ),
        _on(one_chip, (height, width), jnp.int32),
        _on(one_chip, (height, width), jnp.int32),
        _on(one_chip, (height, width * 4), jnp.uint32),
        _on(one_chip, (1, 16), jnp.float32),
    )
    assert MOSAIC_CALL in text


@pytest.mark.parametrize("model,sampler", [
    ("alarm", "lut_ky"),
    ("alarm", "exact_ky"),
    ("hepar2", "lut_ky"),  # a 3072-entry CPT: the widest arena gather
    ("pigs", "lut_ky"),  # 204-node rounds: two 128-lane node tiles
])
def test_fused_gibbs_sweep_compiles(one_chip, model, sampler):
    from repro.core import bayesnet as bnet
    from repro.core.graphs import bn_repository_replica
    from repro.kernels import bn_gibbs

    cbn = bnet.compile_bayesnet(bn_repository_replica(model))
    fr = bn_gibbs.build_fused_rounds(cbn.groups)
    key = jax.random.key(0)
    text = _compiled_text(
        lambda vals, k: bn_gibbs.fused_gibbs_sweep(cbn, fr, vals, k, sampler),
        _on(one_chip, (32, cbn.n_nodes), jnp.int32),
        _on(one_chip, key.shape, key.dtype),
    )
    assert MOSAIC_CALL in text


def test_fused_bn_bucket_names_its_kernel(one_chip):
    """The serving path's fused BN bucket executable (`_bn_bucket`, vmapped
    over query lanes) holds the Mosaic kernel as a custom call named
    `bn_gibbs_kernel` (the `pallas_call`'s name) — a name a device trace
    reads the same after any change to the surrounding HLO."""
    from repro.compile import compile_graph, ir as ir_mod
    from repro.core.graphs import bn_repository_replica
    from repro.kernels import bn_gibbs
    from repro.runtime import batcher

    graph = ir_mod.canonicalize(bn_repository_replica("alarm"),
                                evidence_mode="runtime")
    program = compile_graph(graph)
    query = batcher.Query(qid=0, model="alarm", evidence={0: 1},
                          n_chains=32, n_iters=4, burn_in=1)
    key = batcher.bucket_key(query, graph, "schedule", fused=True)
    assert key.fused
    groups = program.clamped_executable(key.clamp_nodes, key.backend)
    n = program.ir.n_nodes
    text = batcher._bn_bucket.lower(
        program.cbn, groups, _on(one_chip, (2, n), jnp.int32),
        _on(one_chip, (n,), jnp.bool_), _on(one_chip, (2,), jnp.uint32),
        None, None, n_chains=32, n_iters=4, burn_in=1, thin=1,
        sampler="lut_ky", return_state=True, fused=True, interpret=False,
    ).compile().as_text()
    name = bn_gibbs.KERNEL_NAME
    assert name == "bn_gibbs_kernel"
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = [^\n]* custom-call\(",
                       text, re.M)
    assert any(c.startswith(name) for c in calls), calls
    assert MOSAIC_CALL in text


@pytest.mark.parametrize("model", ["hepar2", "pigs"])
def test_bn_footprint_bounds_mosaic_on_served_buckets(one_chip, monkeypatch,
                                                      model):
    """`kernel_lint.bn_fused_footprint` is an upper bound of what Mosaic
    allocates: the benchmark's served fused bucket (`bnlearn.long`: 4 query
    lanes x 32 chains, the traffic's observed set) compiles with the kernel's
    scoped VMEM limit set to the estimate.  pigs' rounds span two 128-lane
    node tiles; hepar2's gather the widest scopes."""
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import generator, harness, models as bench_models
    from repro.analysis import kernel_lint
    from repro.compile import compile_graph, ir as ir_mod
    from repro.core import compat
    from repro.runtime import batcher

    config = json.loads(
        (root / "bench/configs/bnlearn-replicas.json").read_text())
    traffic = json.loads((root / "bench/traffic/long.json").read_text())
    plain = bench_models.build(config)
    bn = harness.program_models(config, {model: plain[model]})[model]
    graph = ir_mod.canonicalize(bn, evidence_mode="runtime")
    program = compile_graph(graph)
    observed = generator.observed_nodes(traffic, model, plain[model])
    n_chains, lanes = traffic["query"]["n_chains"], 4
    query = batcher.Query(qid=0, model=model, n_chains=n_chains,
                          evidence={int(j): 0 for j in observed})
    key = batcher.bucket_key(query, graph, "schedule", fused=True)
    assert key.fused  # pigs at 32 chains fits the 16 MiB budget
    limit = kernel_lint.bn_fused_footprint(graph, n_chains).total_bytes

    params, seen = compat.tpu_compiler_params, []

    def limited(**kw):
        seen.append(limit)
        return params(vmem_limit_bytes=limit, **kw)

    monkeypatch.setattr(compat, "tpu_compiler_params", limited)
    groups = program.clamped_executable(key.clamp_nodes, key.backend)
    n = graph.n_nodes
    text = batcher._bn_bucket.lower(
        program.cbn, groups, _on(one_chip, (lanes, n), jnp.int32),
        _on(one_chip, (n,), jnp.bool_), _on(one_chip, (lanes,), jnp.uint32),
        None, None, n_chains=n_chains, n_iters=2, burn_in=1, thin=1,
        sampler="lut_ky", return_state=True, fused=True, interpret=False,
    ).compile().as_text()
    assert seen  # the kernel was traced under the estimate's limit
    assert MOSAIC_CALL in text


def _penguin_graph():
    """The served `penguin.inpaint` model and its structure-only IR."""
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import harness, models as bench_models
    from repro.compile import ir as ir_mod

    config = json.loads(
        (root / "bench/configs/middlebury-penguin.json").read_text())
    mrf = harness.program_models(
        config, bench_models.build(config))["penguin"]
    return mrf, ir_mod.canonicalize(mrf, evidence_mode="runtime")


def _penguin_bucket(one_chip, monkeypatch, limit) -> str:
    """Compile the served `penguin.inpaint` bucket (4 query lanes x 8
    chains, one 50-sweep slice, 256 labels on 179 x 122) with the MRF
    kernel's scoped VMEM limit set to `limit`."""
    from repro.core import compat
    from repro.runtime import batcher

    mrf, graph = _penguin_graph()
    n_chains, lanes = 8, 4
    query = batcher.Query(qid=0, model="penguin", n_chains=n_chains)
    key = batcher.bucket_key(query, graph, "schedule", fused=True)
    assert key.fused  # Penguin at 8 chains fits the 16 MiB budget
    params, seen = compat.tpu_compiler_params, []

    def limited(**kw):
        seen.append(limit)
        return params(vmem_limit_bytes=int(limit), **kw)

    monkeypatch.setattr(compat, "tpu_compiler_params", limited)
    jax.clear_caches()  # trace the kernel anew, under this limit
    shape = (lanes, mrf.height, mrf.width)
    text = batcher._mrf_bucket.lower(
        mrf, (0, 1), _on(one_chip, shape, jnp.int32),
        _on(one_chip, (lanes,), jnp.uint32), None, None, None, None,
        n_chains=n_chains, n_iters=50, sampler="lut_ky", fused=True,
        interpret=False, eager=False, return_state=True,
    ).compile().as_text()
    assert seen  # the kernel was traced under the limit
    return text


def test_mrf_footprint_bounds_mosaic_on_the_served_penguin_bucket(
        one_chip, monkeypatch):
    """`kernel_lint.mrf_fused_footprint` bounds what Mosaic allocates for
    the served Penguin bucket, and is no loose bound: the bucket compiles
    with the kernel's scoped VMEM limit at the estimate, and not at 0.4
    of it (the per-label scratch alone is three quarters of the
    estimate)."""
    from repro.analysis import kernel_lint
    from repro.kernels.mrf_gibbs import KERNEL_NAME

    limit = kernel_lint.mrf_fused_footprint(_penguin_graph()[1],
                                            8).total_bytes
    text = _penguin_bucket(one_chip, monkeypatch, limit)
    assert MOSAIC_CALL in text
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = [^\n]* custom-call\(",
                       text, re.M)
    assert any(c.startswith(KERNEL_NAME) for c in calls), calls
    with pytest.raises(Exception, match="(?i)vmem|memory|scoped"):
        _penguin_bucket(one_chip, monkeypatch, 0.4 * limit)


def test_mrf_fused_sharded_compiles(topo):
    """The one-shard_map-body MRF program on a 2x2 mesh of described chips:
    halo ppermutes between Mosaic half-steps over each device's row slab."""
    from repro.core.distributed import mrf_fused_sharded_program
    from repro.core.graphs import GridMRF

    mesh = jax.sharding.Mesh(
        [topo.devices[:2], topo.devices[2:]], ("data", "model")
    )
    mrf = GridMRF(64, 64, 4, theta=1.2, h=2.0, name="penguin")
    program = mrf_fused_sharded_program(
        mrf, mesh, n_chains=8, n_iters=4, parities=(0, 1), interpret=False,
    )
    key = jax.random.key(0)
    text = program.lower(
        _on(NamedSharding(mesh, P("data", "model", None)), (8, 64, 64),
            jnp.int32),
        _on(NamedSharding(mesh, P()), key.shape, key.dtype),
        None,
        _on(NamedSharding(mesh, P("model", None)), (64, 64), jnp.int32),
    ).compile().as_text()
    assert MOSAIC_CALL in text
    assert "collective-permute" in text
