"""Pallas TPU kernel for the LUT linear-interpolation unit (paper C2).

The hardware unit fetches Y[i], Y[i+1] and computes the lerp in one cycle.
On TPU there is no fast per-lane VMEM gather, so the <=32-entry table gather
is unrolled into `size` lane-selects against scalar table entries — constant
work per element, fully fused with the surrounding arithmetic, no HBM access.
This preserves the unit's contract: "nonlinear f() at table cost, one op".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.core.interp import LUTSpec

pl = compat.pallas()

DEFAULT_BLOCK_M = 256


def interp_eval(
    x: jax.Array, tab_row, x0: float, inv_dx: float, size: int
) -> jax.Array:
    """Fused LUT lerp on an in-VMEM value array; tab_row is a (1, L) ref or
    array whose scalar entries are read per unrolled step (size <= 32).
    Shared by this kernel and the fused mrf_gibbs / bn_gibbs kernels.

    The float expression is `core.interp.interp_ref`'s, term for term: the
    grid position multiplies by the same f32 `inv_dx` constant (a multiply
    rounds identically under Mosaic and XLA, a divide need not), which is
    what keeps the fused kernels bit-exact with the unfused draws on TPU."""
    u = jnp.clip((x - x0) * inv_dx, 0.0, float(size - 1))
    idx = jnp.minimum(u.astype(jnp.int32), size - 2)
    frac = u - idx.astype(u.dtype)
    y0 = jnp.zeros_like(x)
    y1 = jnp.zeros_like(x)
    for l in range(size - 1):  # unrolled table walk (size <= 32)
        sel = idx == l
        y0 = jnp.where(sel, tab_row[0, l], y0)
        y1 = jnp.where(sel, tab_row[0, l + 1], y1)
    return y0 + frac * (y1 - y0)


def _interp_kernel(
    x_ref, tab_ref, y_ref, *, x0: float, inv_dx: float, size: int
):
    y_ref[...] = interp_eval(x_ref[...], tab_ref, x0, inv_dx, size)


@functools.partial(
    jax.jit, static_argnames=("spec", "block_m", "interpret")
)
def interp_kernel(
    x: jax.Array,
    table: jax.Array,
    *,
    spec: LUTSpec,
    block_m: int = DEFAULT_BLOCK_M,
    interpret: bool = False,
) -> jax.Array:
    """x (M, N) f32, table (1, size_padded) f32 -> (M, N) f32.

    N must be a multiple of 128 (ops.interp pads); rows are tiled block_m at
    a time with the table block broadcast to every grid step (VMEM-resident,
    the private-RF analogue)."""
    m, n = x.shape
    if n % 128 != 0:  # raised, not asserted: must hold under `python -O`
        raise ValueError(
            f"lane axis {n} not a multiple of 128; pad it (use ops.interp)"
        )
    block_m = min(block_m, m)
    grid = (pl.cdiv(m, block_m),)
    kernel = functools.partial(
        _interp_kernel, x0=spec.x0, inv_dx=spec.inv_dx, size=spec.size
    )
    vmem = compat.pallas_vmem()
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, table.shape[1]), lambda i: (0, 0),
                         memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, table)
