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
