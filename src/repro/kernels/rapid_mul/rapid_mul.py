"""Pallas TPU kernel: elementwise RAPID integer multiplier (8/16-bit ops).

The faithful port of the paper's integer unit: leading-one detection via
smear+popcount (the VPU analogue of the 4-bit segmented LOD), fraction
alignment, ternary add (frac1 + frac2 + coefficient in one pass — on TPU
a single fused int add chain), anti-log barrel shift.  Tiled over a 2D
grid of (rows, 128-lane) blocks; the grid pipeline double-buffers the
HBM<->VMEM transfers, standing in for the paper's register pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import float_approx as fa
from repro.core.bitops import ilog2


def _kernel(a_ref, b_ref, lut_ref, o_ref, *, n_bits: int):
    F = n_bits - 1
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    lut = lut_ref[...]

    k1 = ilog2(jnp.maximum(a, 1))
    k2 = ilog2(jnp.maximum(b, 1))
    f1 = (a - (jnp.int32(1) << k1)) << (F - k1)
    f2 = (b - (jnp.int32(1) << k2)) << (F - k2)
    i1 = (f1 >> (F - 4)) & 0xF
    i2 = (f2 >> (F - 4)) & 0xF
    c = fa.lut_take(lut, (i1 * 16 + i2).astype(jnp.int32))

    s = f1 + f2 + c
    one = jnp.int32(1) << F
    carry = (s >= one).astype(jnp.int32)
    mant = jnp.maximum(jnp.where(carry == 1, s, s + one), 0).astype(jnp.uint32)
    shift = k1 + k2 + carry - F
    pos = jnp.maximum(shift, 0).astype(jnp.uint32)
    neg = jnp.maximum(-shift, 0).astype(jnp.uint32)
    res = (mant << pos) >> neg
    hi = ilog2(jnp.maximum(mant.astype(jnp.int32), 1)) + shift
    res = jnp.where(hi >= 32, jnp.uint32(0xFFFFFFFF), res)
    o_ref[...] = jnp.where((a == 0) | (b == 0), jnp.uint32(0), res)


@functools.partial(jax.jit, static_argnames=("n_bits", "block", "interpret"))
def rapid_mul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    n_bits: int = 16,
    block: tuple = (64, 128),
    interpret: bool = False,
) -> jnp.ndarray:
    """Elementwise approximate a*b on (R, 128k)-shaped uint arrays."""
    r, ccols = a.shape
    br, bc = block
    grid = (r // br, ccols // bc)
    return pl.pallas_call(
        functools.partial(_kernel, n_bits=n_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((8, 256), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, ccols), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b, fa.kernel_lut(lut))
