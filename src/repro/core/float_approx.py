"""RAPID logarithmic arithmetic on IEEE-754 floats (the TPU-native port).

The FPGA units operate on integer operands: find the leading one (k),
treat the remaining bits as a fraction x, approximate log2 as k + x, add
(subtract) in the log domain + a RAPID error coefficient, anti-log by a
shift.  An IEEE-754 float *is already* the (k, x) pair: the exponent field
is k and the mantissa field is x.  Bit-casting a positive float to an
integer therefore yields exactly Mitchell's log approximation (scaled by
2^23, biased by 127 << 23), so the whole Mitchell+RAPID pipeline becomes:

    bits(a) + bits(b) - BIAS + coeff[idx(a), idx(b)]      (multiply)
    bits(a) - bits(b) + BIAS + coeff[idx(a), idx(b)]      (divide)

where ``idx`` is the 4 MSBs of the mantissa — precisely the paper's
coefficient-selection index — and the mantissa-adder carry into the
exponent field implements the ``x1+x2 >= 1`` anti-log case for free (the
same role the ternary-adder MSB plays on the FPGA).

This path is branch-free integer add + 256-entry gather per element: pure
VPU work on TPU, no MXU, no transcendentals.  It is the building block of
the ``log_matmul`` Pallas kernel and of the elementwise approx ops used in
softmax/normalisation denominators.

Error characteristics are *identical* to the integer units for the same
scheme (the error depends only on the fraction pair), with mantissa
quantisation at 2^-23 instead of the integer fraction width.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mitchell, schemes
from repro.core.mitchell import ErrorScheme

__all__ = [
    "mul_lut",
    "div_lut",
    "mul_lut_device",
    "div_lut_device",
    "lut_take",
    "kernel_lut",
    "log_mul_f32",
    "log_div_f32",
    "log_recip_f32",
    "approx_mul",
    "approx_div",
]

_F32_FRAC = 23
_F32_BIAS = np.int32(127 << 23)
_F32_ABS = np.int32(0x7FFFFFFF)
_F32_SIGN = np.int32(-0x80000000)
_MIN_NORMAL = np.int32(0x00800000)
_INF_BITS = np.int32(0x7F800000)


def _lut_host(kind: str, scheme: ErrorScheme) -> np.ndarray:
    """Memoized read-only (256,) int32 host LUT at the f32 fraction width
    (shared build/cache machinery: ``repro.core.mitchell.lut_host``)."""
    assert scheme.kind == kind
    return mitchell.lut_host(scheme, _F32_FRAC)


def _lut_device(kind: str, scheme: ErrorScheme, dtype: str):
    """Memoized on-device LUT per (scheme, dtype): one upload ever
    (shared machinery: ``repro.core.mitchell.lut_device``)."""
    assert scheme.kind == kind
    return mitchell.lut_device(scheme, _F32_FRAC, dtype)


def _as_scheme(kind: str, scheme: ErrorScheme | str) -> ErrorScheme:
    if isinstance(scheme, str):
        table = schemes.MUL_SCHEMES if kind == "mul" else schemes.DIV_SCHEMES
        return table[scheme]
    return scheme


def mul_lut(scheme: ErrorScheme | str) -> np.ndarray:
    """(256,) int32 coefficient LUT for f32 multiply (host, memoized)."""
    return _lut_host("mul", _as_scheme("mul", scheme))


def div_lut(scheme: ErrorScheme | str) -> np.ndarray:
    """(256,) int32 coefficient LUT for f32 divide (host, memoized)."""
    return _lut_host("div", _as_scheme("div", scheme))


def mul_lut_device(scheme: ErrorScheme | str, dtype: str = "int32"):
    """(256,) on-device multiply LUT, memoized per (scheme, dtype)."""
    return _lut_device("mul", _as_scheme("mul", scheme), dtype)


def div_lut_device(scheme: ErrorScheme | str, dtype: str = "int32"):
    """(256,) on-device divide LUT, memoized per (scheme, dtype)."""
    return _lut_device("div", _as_scheme("div", scheme), dtype)


def lut_take(lut: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``lut[idx]`` for a 256-entry coefficient table.

    A 1-D ``(256,)`` table is a plain XLA gather.  Pallas TPU kernels
    pass the table as an ``(8, 256)`` tile of identical rows instead:
    Mosaic gathers only within one vreg, and only from a source laid
    out as whole sublane tiles, so each 128-lane block of the 2-D
    ``idx`` looks up both 128-entry halves of the table and selects.
    Both forms return the same integers, which keeps kernels bit-exact
    with the jnp path.
    """
    if lut.ndim == 1:
        return jnp.take(lut, idx)
    rows, width = idx.shape
    zeros = jnp.zeros((rows, 128), lut.dtype)
    lo = lut[:1, :128] + zeros
    hi = lut[:1, 128:] + zeros
    blocks = []
    for j in range(0, width, 128):
        ij = idx[:, j:j + 128]
        lane = ij & 127
        blocks.append(jnp.where(
            ij < 128,
            jnp.take_along_axis(lo, lane, axis=1, mode="promise_in_bounds"),
            jnp.take_along_axis(hi, lane, axis=1, mode="promise_in_bounds")))
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def kernel_lut(lut: jnp.ndarray) -> jnp.ndarray:
    """A ``(256,)`` table as the ``(8, 256)`` tile Pallas kernels take."""
    return jnp.broadcast_to(lut.reshape(1, -1), (8, lut.size))


def _log_mul_bits(m1: jnp.ndarray, m2: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """Magnitude-bits multiply in the log domain. m1, m2 >= 0 (int32)."""
    i1 = (m1 >> (_F32_FRAC - 4)) & 0xF
    i2 = (m2 >> (_F32_FRAC - 4)) & 0xF
    c = lut_take(lut, i1 * 16 + i2)
    return m1 + m2 - _F32_BIAS + c


def _log_div_bits(m1: jnp.ndarray, m2: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    i1 = (m1 >> (_F32_FRAC - 4)) & 0xF
    i2 = (m2 >> (_F32_FRAC - 4)) & 0xF
    c = lut_take(lut, i1 * 16 + i2)
    return m1 - m2 + _F32_BIAS + c


def _finish(sum_bits: jnp.ndarray, sign: jnp.ndarray, dead: jnp.ndarray) -> jnp.ndarray:
    """Clamp under/overflow, apply sign, zero the dead lanes, bitcast."""
    sum_bits = jnp.where(sum_bits >= _INF_BITS, _INF_BITS, sum_bits)
    sum_bits = jnp.where(sum_bits < _MIN_NORMAL, 0, sum_bits)  # flush subnormal
    sum_bits = jnp.where(dead, 0, sum_bits)
    return jax.lax.bitcast_convert_type(sum_bits | sign, jnp.float32)


def log_mul_f32(a: jnp.ndarray, b: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """Elementwise RAPID approximate a*b for float32 (broadcasting ok).

    Semantics: flush-to-zero for subnormals, 0*x == 0 (including 0*inf),
    inf propagates, exponent overflow saturates to inf.
    """
    a, b = jnp.broadcast_arrays(a, b)
    ba = jax.lax.bitcast_convert_type(a, jnp.int32)
    bb = jax.lax.bitcast_convert_type(b, jnp.int32)
    sign = (ba ^ bb) & _F32_SIGN
    m1, m2 = ba & _F32_ABS, bb & _F32_ABS
    s = _log_mul_bits(m1, m2, lut)
    # int32 wrap detection: (m1 - BIAS) + m2 overflowed iff both halves were
    # non-negative yet the sum is negative -> real exponent way past inf.
    half = m1 - _F32_BIAS
    wrapped = (half >= 0) & (s < 0)
    s = jnp.where(wrapped | (m1 >= _INF_BITS) | (m2 >= _INF_BITS), _INF_BITS, s)
    dead = (m1 < _MIN_NORMAL) | (m2 < _MIN_NORMAL)  # 0 * x == 0
    return _finish(s, sign, dead)


def log_div_f32(a: jnp.ndarray, b: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """Elementwise RAPID approximate a/b for float32. b==0 -> +-inf."""
    a, b = jnp.broadcast_arrays(a, b)
    ba = jax.lax.bitcast_convert_type(a, jnp.int32)
    bb = jax.lax.bitcast_convert_type(b, jnp.int32)
    sign = (ba ^ bb) & _F32_SIGN
    m1, m2 = ba & _F32_ABS, bb & _F32_ABS
    s = _log_div_bits(m1, m2, lut)
    diff = m1 - m2
    wrapped = (diff >= 0) & (s < 0)  # huge / tiny past inf
    s = jnp.where(wrapped | (m1 >= _INF_BITS), _INF_BITS, s)
    s = jnp.where(m2 < _MIN_NORMAL, _INF_BITS, s)  # x / 0
    dead = m1 < _MIN_NORMAL  # 0 / x == 0
    return _finish(s, sign, dead)


def log_recip_f32(b: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """Approximate 1/b (division with dividend fraction fixed at zero)."""
    return log_div_f32(jnp.ones_like(b), b, lut)


# --------------------------------------------------------------------------
# Public elementwise ops with scheme names + gradient support.
#
# The ops are near-unbiased (paper SS IV-A), so we give them straight-
# through exact gradients: the forward pass carries the approximation, the
# backward pass differentiates the *ideal* product/quotient.  This mirrors
# how quantised training treats non-differentiable rounding, and is what
# makes RAPID usable inside training graphs, not just inference.
# --------------------------------------------------------------------------

@partial(jax.custom_jvp, nondiff_argnums=(2,))
def approx_mul(a: jnp.ndarray, b: jnp.ndarray, scheme: str = "rapid10") -> jnp.ndarray:
    orig = a.dtype
    lut = mul_lut_device(scheme)
    out = log_mul_f32(a.astype(jnp.float32), b.astype(jnp.float32), lut)
    return out.astype(orig)


@approx_mul.defjvp
def _approx_mul_jvp(scheme, primals, tangents):
    a, b = primals
    da, db = tangents
    return approx_mul(a, b, scheme), a * db + b * da


@partial(jax.custom_jvp, nondiff_argnums=(2,))
def approx_div(a: jnp.ndarray, b: jnp.ndarray, scheme: str = "rapid9") -> jnp.ndarray:
    orig = a.dtype
    lut = div_lut_device(scheme)
    out = log_div_f32(a.astype(jnp.float32), b.astype(jnp.float32), lut)
    return out.astype(orig)


@approx_div.defjvp
def _approx_div_jvp(scheme, primals, tangents):
    a, b = primals
    da, db = tangents
    return approx_div(a, b, scheme), (da * b - a * db) / (b * b)
