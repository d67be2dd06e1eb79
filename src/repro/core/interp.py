"""LUT-based linear interpolation of nonlinear functions (paper C2, Sec. III-D).

AIA evaluates exp/log/... in one cycle from a 16-entry, 8-bit lookup table
held in the private RF (the CoopMC-validated accuracy/efficiency point).  Here
the same unit becomes (i) a pure-jnp reference (`interp_ref`) and (ii) a
Pallas kernel (kernels/interp_lut.py) whose table lives in VMEM and whose
gather is unrolled into `size` lane-selects — the TPU-idiomatic fused lookup.

Tables are described by `LUTSpec`: uniform grid y = f(x0 + i*dx), inputs are
clamped to the table range (saturating ends, as in the hardware unit).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

# Paper defaults (Sec. III-D "Accuracy Impact"): 16 entries, 8-bit values.
DEFAULT_SIZE = 16
DEFAULT_BITS = 8


@dataclasses.dataclass(frozen=True)
class LUTSpec:
    x0: float
    dx: float
    size: int

    @property
    def x1(self) -> float:
        return self.x0 + self.dx * (self.size - 1)

    @property
    def inv_dx(self) -> float:
        """1 / dx: the lerp multiplies by it (as f32) instead of dividing by
        dx, so XLA and the Pallas kernels round the grid position alike."""
        return 1.0 / self.dx


def build_lut(
    fn: Callable[[np.ndarray], np.ndarray],
    x0: float,
    x1: float,
    size: int = DEFAULT_SIZE,
    dtype=jnp.float32,
) -> tuple[jax.Array, LUTSpec]:
    spec = LUTSpec(x0=float(x0), dx=float(x1 - x0) / (size - 1), size=size)
    xs = np.asarray(x0 + spec.dx * np.arange(size), np.float64)
    return jnp.asarray(fn(xs), dtype), spec


def build_exp_weight_lut(
    bits: int = DEFAULT_BITS, x_min: float = -8.0, size: int = DEFAULT_SIZE
):
    """exp() table emitting integer sampling weights in [0, 2^bits - 1].

    Inputs are max-subtracted log-potentials (<= 0).  exp(x_min) ~ 3e-4 maps
    to weight 0 — bins that improbable are dropped, matching the paper's 8-bit
    quantization with "negligible accuracy loss"."""
    top = float((1 << bits) - 1)
    return build_lut(lambda x: np.rint(np.exp(x) * top), x_min, 0.0, size)


def build_log_lut(size: int = DEFAULT_SIZE, x0: float = 1.0, x1: float = 2.0):
    """log() over one octave; range-reduced callers handle the exponent."""
    return build_lut(np.log, x0, x1, size)


def interp_ref(x: jax.Array, table: jax.Array, spec: LUTSpec) -> jax.Array:
    """Pure-jnp oracle: y = Y[i] + frac * (Y[i+1] - Y[i])   (paper Sec. III-D)."""
    u = jnp.clip((x - spec.x0) * spec.inv_dx, 0.0, spec.size - 1)
    idx = jnp.clip(jnp.floor(u), 0, spec.size - 2).astype(jnp.int32)
    frac = u - idx.astype(u.dtype)
    y0 = jnp.take(table, idx)
    y1 = jnp.take(table, idx + 1)
    return y0 + frac * (y1 - y0)
