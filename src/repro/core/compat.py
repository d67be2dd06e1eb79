"""One import point for the JAX APIs that move between releases, and for
the process-wide JAX settings the code depends on.

The repo targets jax 0.9: `jax.shard_map`, `pltpu.CompilerParams`,
`jax.make_mesh(axis_types=...)`.  Call sites go through here so the next
rename lands in one file.  This module also owns the two decisions every
Pallas call site used to make for itself: whether kernels run compiled or
interpreted (`pallas_interpret`), and where the persistent compilation
cache lives (`setup_compile_cache`, called by entry points only).
"""

from __future__ import annotations

import os
import pathlib

import jax

# The checkout root (src/repro/core/compat.py -> three parents up from src).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map`."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def axis_size(axis_name):
    """`jax.lax.axis_size`."""
    return jax.lax.axis_size(axis_name)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axes.  Bare `jax.make_mesh` gives Explicit
    axes, under which the legacy sharded engines' gathers cannot resolve
    their output sharding."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def pallas():
    """The `jax.experimental.pallas` module — the single import point for
    the Pallas API, so version churn (experimental namespace moves, as
    already happened to shard_map) lands here and not in four kernels.
    Kernels bind it at module import: `pl = compat.pallas()`."""
    from jax.experimental import pallas as pl

    return pl


def tpu_compiler_params(**kwargs):
    """`pltpu.CompilerParams`."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def pallas_vmem():
    """The VMEM memory space every kernel's BlockSpecs use."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    False on TPU (Mosaic compiles them), True on CPU (tests and CPU
    rehearsals evaluate the kernel bodies as XLA ops).  Any other backend
    raises: the kernels are written for Mosaic TPU, and running them
    interpreted on an accelerator would hide that the device never ran
    them."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run on TPU (compiled) or CPU (interpreted); "
        f"backend {backend!r} is neither"
    )


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives at the fixed
    `<checkout>/.jax_cache` — a fixed path, because the path is part of
    what a later process must find again.  Called once by the entry points
    (`chip_smoke.py`, `python -m repro.runtime`, `benchmarks/run.py`);
    importing the library or running the tests never turns it on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
