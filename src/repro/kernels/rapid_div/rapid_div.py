"""Pallas TPU kernel: elementwise RAPID integer divider (2N-by-N).

Same LOD/align/ternary-add structure as rapid_mul, with log subtraction
and a borrow branch instead of a carry (paper Eq. 5/7).  The paper's key
point — that Mitchell's transform collapses the long iterative divider
array into one subtractor, bringing divide latency down to multiply
latency — carries over verbatim: this kernel has the *same* op count and
pipeline depth as rapid_mul (on TPU there is no iterative integer divide
unit at all; exact integer division lowers to a multi-op sequence, so the
win is even larger).
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
    F = 2 * n_bits - 1
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

    s = f1 - f2 + c
    one = jnp.int32(1) << F
    borrow = (s < 0).astype(jnp.int32)
    mant = jnp.maximum(jnp.where(borrow == 1, s + 2 * one, s + one), 0)
    shift = k1 - k2 - borrow - F
    pos = jnp.maximum(shift, 0).astype(jnp.uint32)
    neg = jnp.minimum(jnp.maximum(-shift, 0), 31).astype(jnp.uint32)
    res = (mant.astype(jnp.uint32) << pos) >> neg
    res = jnp.where(a == 0, jnp.uint32(0), res)
    sat = jnp.uint32((1 << (2 * n_bits)) - 1)
    o_ref[...] = jnp.where(b == 0, sat, res)


@functools.partial(jax.jit, static_argnames=("n_bits", "block", "interpret"))
def rapid_div_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    n_bits: int = 8,
    block: tuple = (64, 128),
    interpret: bool = False,
) -> jnp.ndarray:
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
        name="rapid_div_pallas",
    )(a, b, fa.kernel_lut(lut))
