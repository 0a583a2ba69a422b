"""Pallas TPU kernels: fused RAPID divider passes.

Three kernels, all pure VPU (int32 add/sub + 256-entry coefficient
gather — the same per-element cost as the log_matmul products):

  * ``softmax_div_pallas`` — one grid step holds a [bm, n_pad] slab of
    exp-weights in VMEM, reduces the row-sum, floors it, and applies the
    logarithmic divide to the resident slab.  The denominator and the
    un-divided numerator never exist in HBM.
  * ``rms_div_pallas``     — same shape, denominator is
    sqrt(mean(x^2) + eps) over the real (unpadded) row width.
  * ``div_pallas``         — elementwise a/b on pre-broadcast operands
    (the online-softmax combine, whose denominator comes from a scan).

The kernel bodies call the *same* jnp expressions as the jnp backend
(`ref.softmax_denom` / `ref.rms_denom` / `float_approx.log_div_f32`), so
jnp vs pallas-interpret parity is bit-for-bit by construction; the
grid rows are independent ("parallel" semantics, no K accumulation).

Each row-fused wrapper takes a ``depth`` knob (the ``PipelineSpec``
depth from :mod:`repro.kernels.spec`): depth 1 is the legacy grid
formulation above, depth >= 2 lowers to a software-pipelined body —
grid (1,) with the slab loop inside the kernel, x and out in ANY (HBM)
memory, and ``depth`` VMEM scratch slots per side rotating through
explicit ``make_async_copy`` DMAs.  Slab s+depth-1's fetch and slab
s-depth's writeback are both in flight while slab s computes, the
paper's pipelined-divider schedule.  The per-slab tile expression is
shared verbatim between the two formulations (``_*_tile``), so they
are bit-exact against each other and the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import float_approx as fa
from repro.kernels.fused_div import ref

__all__ = ["softmax_div_pallas", "rms_div_pallas", "div_pallas",
           "div_rowbcast_pallas"]


def _softmax_tile(e, lut, *, floor: float):
    return fa.log_div_f32(e, ref.softmax_denom(e, floor), lut)


def _rms_tile(x, lut, *, n: int, eps: float):
    return fa.log_div_f32(x, ref.rms_denom(x, n, eps), lut)


def _softmax_kernel(e_ref, lut_ref, o_ref, *, floor: float):
    o_ref[...] = _softmax_tile(e_ref[...], lut_ref[...], floor=floor)


def _rms_kernel(x_ref, lut_ref, o_ref, *, n: int, eps: float):
    o_ref[...] = _rms_tile(x_ref[...], lut_ref[...], n=n, eps=eps)


def _div_kernel(a_ref, b_ref, lut_ref, o_ref):
    o_ref[...] = fa.log_div_f32(a_ref[...], b_ref[...], lut_ref[...])


def _div_rowbcast_kernel(a_ref, b_ref, lut_ref, o_ref):
    # b is one denominator per row as a [bm, 1] column block (a 1-D
    # (bm,) block puts bm on the lane axis, where it is misaligned for
    # any bm that is neither %128 nor the whole row count — RPD006),
    # broadcast over the lanes in VMEM: the [M, N] / [M, 1] shape of the
    # online-softmax combine without materialising the broadcast in HBM
    o_ref[...] = fa.log_div_f32(a_ref[...], b_ref[...], lut_ref[...])


def _rowwise_pipelined_kernel(x_hbm, lut_ref, *rest, tile_fn, bm: int,
                              nslabs: int, depth: int, has_b: bool):
    """Software-pipelined slab loop: in-DMA ahead, out-DMA behind.

    Slab s's input slot (s % depth) is also its output slot; before
    computing into it we wait slab s-depth's writeback (same slot), so
    every slot is quiescent when reused.  Warm-up and drain bounds are
    static (nslabs is a trace-time constant), so every DMA is started
    exactly once and waited exactly once.
    """
    refs = list(rest)
    b_ref = refs.pop(0) if has_b else None
    o_hbm, x_scr, o_scr, x_sem, o_sem = refs

    def in_dma(slot, s):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(s * bm, bm), :], x_scr.at[slot], x_sem.at[slot])

    def out_dma(slot, s):
        return pltpu.make_async_copy(
            o_scr.at[slot], o_hbm.at[pl.ds(s * bm, bm), :], o_sem.at[slot])

    for d in range(min(depth - 1, nslabs)):
        in_dma(d % depth, d).start()
    lut = lut_ref[...]

    def step(s, carry):
        slot = jax.lax.rem(s, depth)
        nxt = s + depth - 1

        @pl.when(nxt < nslabs)
        def _prefetch():
            in_dma(jax.lax.rem(nxt, depth), nxt).start()

        in_dma(slot, s).wait()

        @pl.when(s >= depth)
        def _retire():
            out_dma(slot, s - depth).wait()

        x_slab = x_scr[slot]
        if has_b:
            o_scr[slot] = tile_fn(x_slab, b_ref[pl.ds(s * bm, bm), :], lut)
        else:
            o_scr[slot] = tile_fn(x_slab, lut)
        out_dma(slot, s).start()
        return carry

    jax.lax.fori_loop(0, nslabs, step, 0)
    for s in range(max(0, nslabs - depth), nslabs):
        out_dma(s % depth, s).wait()


def _rowwise_pipelined_call(tile_fn, x, lut, bm: int, depth: int,
                            interpret: bool, b=None):
    """pallas_call plumbing for the depth>=2 row-fused formulation."""
    m, npad = x.shape
    nslabs = m // bm
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    in_specs = [any_spec, pl.BlockSpec((8, 256), lambda i: (0, 0))]
    operands = [x, fa.kernel_lut(lut)]
    if b is not None:
        # the whole [M, 1] denominator column stays resident in VMEM
        # (4 bytes/row); slabs are sliced in-kernel
        in_specs.append(pl.BlockSpec((m, 1), lambda i: (0, 0)))
        operands.append(b)
    return pl.pallas_call(
        functools.partial(_rowwise_pipelined_kernel, tile_fn=tile_fn,
                          bm=bm, nslabs=nslabs, depth=depth,
                          has_b=b is not None),
        grid=(1,),
        in_specs=in_specs,
        out_specs=any_spec,
        out_shape=jax.ShapeDtypeStruct((m, npad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((depth, bm, npad), jnp.float32),
            pltpu.VMEM((depth, bm, npad), jnp.float32),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)


def _rowwise_call(kernel, x, lut, bm: int, interpret: bool):
    """Shared pallas_call plumbing for the row-fused kernels.

    x: [M, n_pad] f32 with M % bm == 0 and n_pad % LANE == 0; every grid
    step owns bm full rows (the whole reduction axis stays in VMEM).
    """
    m, npad = x.shape
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, npad), lambda i: (i, 0)),
            pl.BlockSpec((8, 256), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, npad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, fa.kernel_lut(lut))


@functools.partial(jax.jit,
                   static_argnames=("floor", "bm", "depth", "interpret"))
def softmax_div_pallas(e, lut, *, floor: float = ref.SOFTMAX_FLOOR,
                       bm: int = 8, depth: int = 1,
                       interpret: bool = False):
    """e[M, n_pad] -> e / max(rowsum(e), floor) with RAPID divides."""
    if depth >= 2:
        return _rowwise_pipelined_call(
            functools.partial(_softmax_tile, floor=floor),
            e, lut, bm, depth, interpret)
    return _rowwise_call(functools.partial(_softmax_kernel, floor=floor),
                         e, lut, bm, interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "eps", "bm", "depth", "interpret"))
def rms_div_pallas(x, lut, *, n: int, eps: float, bm: int = 8,
                   depth: int = 1, interpret: bool = False):
    """x[M, n_pad] -> x / sqrt(mean(x[:, :n]^2) + eps), RAPID divides."""
    if depth >= 2:
        return _rowwise_pipelined_call(
            functools.partial(_rms_tile, n=n, eps=eps),
            x, lut, bm, depth, interpret)
    return _rowwise_call(functools.partial(_rms_kernel, n=n, eps=eps),
                         x, lut, bm, interpret)


@functools.partial(jax.jit, static_argnames=("bm", "depth", "interpret"))
def div_rowbcast_pallas(a, b, lut, *, bm: int = 8, depth: int = 1,
                        interpret: bool = False):
    """a[M, n_pad] / b[M, 1] with the per-row denominator broadcast in VMEM."""
    m, npad = a.shape
    if depth >= 2:
        return _rowwise_pipelined_call(
            fa.log_div_f32, a, lut, bm, depth, interpret, b=b)
    return pl.pallas_call(
        _div_rowbcast_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, npad), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((8, 256), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, npad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a, b, fa.kernel_lut(lut))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def div_pallas(a, b, lut, *, block=(8, 128), interpret: bool = False):
    """Elementwise RAPID a/b on f32 [rows, cols] tiles (pre-broadcast)."""
    r, c = a.shape
    br, bc = block
    return pl.pallas_call(
        _div_kernel,
        grid=(r // br, c // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((8, 256), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b, fa.kernel_lut(lut))
