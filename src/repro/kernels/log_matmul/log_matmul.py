"""Pallas TPU kernel: RAPID logarithmic approximate matmul.

TPU adaptation of the paper's pipelined multiplier array.  The FPGA
pipeline stages (LOD -> log-add+coefficient -> anti-log shift) become a
single fused VPU expression per element — on IEEE floats the LOD/anti-log
are free (they are the exponent field), so each approximate product is
one int32 add + one 256-entry coefficient gather.  The *pipelining* that
the paper implements with explicit register stages is realised here by the
Pallas grid pipeline: HBM->VMEM DMA for the next (bm x bk)/(bk x bn) tiles
is overlapped with VPU compute on the current tiles (a hardware-managed
2-deep double buffer per operand — the TPU analogue of the paper's 2-/3-/
4-stage configurations; see DESIGN.md SSPipelining).

Two formulations share the kernel arithmetic:

  * ``log_matmul_pallas`` (pipeline depth 1) — grid (M/bm, N/bn, K/bk);
    M, N are parallel, K is sequential and accumulates into the output
    tile (revisited across the K dimension).  HBM->VMEM staging is left
    to Mosaic's hardware-managed grid pipeline.
  * ``log_matmul_pipelined`` (depth >= 2) — grid (M/bm, N/bn) with the
    K loop *inside* the kernel: x and w stay in ANY (HBM) memory and
    ``depth`` VMEM scratch slots per operand rotate through explicit
    ``make_async_copy`` DMAs, so the copy for K block t+depth-1 is in
    flight while block t's log-domain products compute — the explicit
    software pipeline the paper implements with register stages.  The
    accumulation order (zeros + block_0 + block_1 + ...) is identical
    to the grid formulation, so the two are bit-exact against each
    other and against the chunk=1 jnp scan.

VMEM working set: bm*bk + bk*bn tiles (x depth when manually staged)
+ the bm*bn output tile + the 1 KiB coefficient LUT.  MXU is untouched;
arithmetic is pure VPU int32.

Fused epilogue menu: an optional composition of ``{bias, activation,
residual-add, rms-normalize, softmax-combine}`` is applied to the output
tile on its *last* K-grid visit, while it is still resident in VMEM — a
whole block tail ``norm(activation(out + bias) + residual)`` costs no
extra HBM round-trip, with the normalization divides running through
the RAPID approximate divider.  The epilogue expression is
``repro.core.backend.apply_epilogue_tile``, shared *verbatim* with the
jnp scan path, so the two backends agree bit-for-bit on identically-
ordered accumulations; the norm stages additionally require the output
tile to span the full lane-padded row (``bn == n_pad``; ops.py arranges
this), matching the canonical lane-padded denominator semantics of
``repro.kernels.fused_div.ref``.  With ``Epilogue.keep_prenorm`` the
kernel emits a second output holding the value before the norm stage —
the residual stream a pre-norm transformer block carries forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import float_approx as fa
from repro.core.backend import Epilogue, apply_epilogue_tile
from repro.kernels.budget import LANE

F32_BIAS = 127 << 23
F32_ABS = 0x7FFFFFFF
F32_SIGN = -0x80000000
MIN_NORMAL = 0x00800000
INF_BITS = 0x7F800000


def _approx_prod(bx_col: jnp.ndarray, bw_row: jnp.ndarray, lut: jnp.ndarray):
    """One rank-1 slab of approximate products from log-domain bits.

    bx_col: [bm, 1] int32 operand bits; bw_row: [1, bn] int32; returns
    [bm, bn] float32 approximate products.
    """
    s1 = bx_col & F32_SIGN
    s2 = bw_row & F32_SIGN
    m1 = bx_col & F32_ABS
    m2 = bw_row & F32_ABS
    i1 = (m1 >> 19) & 0xF
    i2 = (m2 >> 19) & 0xF
    c = fa.lut_take(lut, (i1 * 16 + i2).astype(jnp.int32))
    s = m1 + m2 - F32_BIAS + c
    s = jnp.where(s < MIN_NORMAL, 0, s)
    s = jnp.where(s >= INF_BITS, INF_BITS, s)
    dead = (m1 < MIN_NORMAL) | (m2 < MIN_NORMAL)
    s = jnp.where(dead, 0, s)
    return jax.lax.bitcast_convert_type(s | (s1 ^ s2), jnp.float32)


def _kernel(x_ref, w_ref, lut_ref, *rest, bk: int, unroll: int, nk: int,
            ep: Epilogue, has_bias: bool, has_residual: bool, n: int):
    """Accumulate one (bm, bn) output tile over the current K block."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    res_ref = refs.pop(0) if has_residual else None
    dlut_ref = refs.pop(0) if ep.wants_norm_lut else None
    if ep.keep_prenorm:
        o_ref, pre_ref = refs
    else:
        (o_ref,) = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bn = o_ref.shape
    o_ref[...] += _accumulate_block(x_ref, w_ref, lut_ref[...], bm, bn, bk,
                                    unroll)

    if has_bias or has_residual or not ep.is_identity:
        # epilogue menu on the tile's final K visit, while it sits in
        # VMEM — the shared canonical expression (apply_epilogue_tile)
        @pl.when(pl.program_id(2) == nk - 1)
        def _epilogue():
            out = apply_epilogue_tile(
                o_ref[...],
                bias_ref[...] if has_bias else None,
                res_ref[...] if has_residual else None,
                ep, n=n,
                div_lut=dlut_ref[...] if dlut_ref is not None else None)
            if ep.keep_prenorm:
                o_ref[...], pre_ref[...] = out
            else:
                o_ref[...] = out


def _accumulate_block(x_ref, w_ref, lut, bm: int, bn: int, bk: int,
                      unroll: int):
    """Zeros + sum of rank-1 slabs over one K block (the canonical
    accumulation order both formulations and the chunk=1 scan share).

    ``x_ref`` / ``w_ref`` are the block's [bm, bk] / [bk, bn] VMEM refs.
    Row ``kk`` of w is a dynamic single-row load; column ``kk`` of x is
    picked out of its aligned lane chunk by a one-hot integer lane sum,
    which returns the operand bits exactly (Mosaic has no dynamic
    lane-offset slice).
    """
    width = min(bk, LANE)
    if bk % width:
        raise ValueError(f"bk={bk} must be below {LANE} or a multiple of it")
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, width), 1)

    def body(t, acc):
        k0 = t * unroll
        start = pl.multiple_of(k0 // width * width, width)
        xb = jax.lax.bitcast_convert_type(x_ref[:, pl.ds(start, width)],
                                          jnp.int32)
        for u in range(unroll):
            kk = k0 + u
            bx = jnp.sum(jnp.where(lane == kk - start, xb, 0), axis=1,
                         keepdims=True)                          # [bm, 1]
            bw = jax.lax.bitcast_convert_type(w_ref[pl.ds(kk, 1), :],
                                              jnp.int32)         # [1, bn]
            acc = acc + _approx_prod(bx, bw, lut)
        return acc

    return jax.lax.fori_loop(0, bk // unroll, body,
                             jnp.zeros((bm, bn), jnp.float32))


def _pipelined_kernel(x_hbm, w_hbm, lut_ref, *rest, bm: int, bn: int,
                      bk: int, unroll: int, nk: int, depth: int,
                      ep: Epilogue, has_bias: bool, has_residual: bool,
                      n: int):
    """One (bm, bn) output tile with the K loop software-pipelined.

    x/w live in ANY (HBM) memory; ``depth`` VMEM slots per operand
    rotate through explicit DMAs so the copy of K block t+depth-1
    overlaps block t's compute.  Each K block is started exactly once
    and waited exactly once, so the DMA semaphores balance per grid
    step; the output tile is written once (no grid revisits).
    """
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    res_ref = refs.pop(0) if has_residual else None
    dlut_ref = refs.pop(0) if ep.wants_norm_lut else None
    x_scr, w_scr, x_sem, w_sem = refs[-4:]
    refs = refs[:-4]
    if ep.keep_prenorm:
        o_ref, pre_ref = refs
    else:
        (o_ref,) = refs

    i = pl.program_id(0)
    j = pl.program_id(1)

    def x_dma(slot, kk):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
            x_scr.at[slot], x_sem.at[slot])

    def w_dma(slot, kk):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)],
            w_scr.at[slot], w_sem.at[slot])

    # warm-up: put the first depth-1 K blocks in flight
    for d in range(depth - 1):
        @pl.when(d < nk)
        def _start(d=d):
            x_dma(d % depth, d).start()
            w_dma(d % depth, d).start()

    lut = lut_ref[...]

    def k_step(kk, acc):
        slot = jax.lax.rem(kk, depth)
        nxt = kk + depth - 1

        @pl.when(nxt < nk)
        def _prefetch():
            x_dma(jax.lax.rem(nxt, depth), nxt).start()
            w_dma(jax.lax.rem(nxt, depth), nxt).start()

        x_dma(slot, kk).wait()
        w_dma(slot, kk).wait()
        return acc + _accumulate_block(x_scr.at[slot], w_scr.at[slot], lut,
                                       bm, bn, bk, unroll)

    acc = jax.lax.fori_loop(
        0, nk, k_step, jnp.zeros((bm, bn), jnp.float32))

    if has_bias or has_residual or not ep.is_identity:
        out = apply_epilogue_tile(
            acc,
            bias_ref[...] if has_bias else None,
            res_ref[...] if has_residual else None,
            ep, n=n,
            div_lut=dlut_ref[...] if dlut_ref is not None else None)
        if ep.keep_prenorm:
            o_ref[...], pre_ref[...] = out
        else:
            o_ref[...] = out
    else:
        o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "unroll", "depth", "epilogue", "n",
                     "interpret"),
)
def log_matmul_pipelined(
    x: jnp.ndarray,
    w: jnp.ndarray,
    lut: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
    div_lut: jnp.ndarray | None = None,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    unroll: int = 8,
    depth: int = 2,
    epilogue: Epilogue = Epilogue(),
    n: int | None = None,
    interpret: bool = False,
):
    """Software-pipelined x[M,K] @ w[K,N_pad]; contract as
    :func:`log_matmul_pallas` plus ``depth`` explicit DMA slots."""
    m, k = x.shape
    _, npad = w.shape
    if n is None:
        n = npad
    if epilogue.norm is not None and bn != npad:
        raise ValueError(
            f"norm epilogue needs whole rows per tile: bn={bn} != N={npad}")
    if epilogue.wants_norm_lut and div_lut is None:
        raise ValueError("epilogue.div_scheme set but no div_lut operand")
    grid = (m // bm, npad // bn)
    nk = k // bk
    has_bias = bias is not None
    has_residual = residual is not None
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    in_specs = [
        any_spec,                                    # x: manual DMA
        any_spec,                                    # w: manual DMA
        pl.BlockSpec((8, 256), lambda i, j: (0, 0)), # mul LUT
    ]
    operands = [x, w, fa.kernel_lut(lut)]
    if has_bias:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j: (j,)))
        operands.append(bias)
    if has_residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
        operands.append(residual)
    if epilogue.wants_norm_lut:
        in_specs.append(pl.BlockSpec((8, 256), lambda i, j: (0, 0)))
        operands.append(fa.kernel_lut(div_lut))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, npad), jnp.float32)
    if epilogue.keep_prenorm:
        out_specs, out_shapes = [out_spec, out_spec], [out_shape, out_shape]
    else:
        out_specs, out_shapes = out_spec, out_shape
    return pl.pallas_call(
        functools.partial(_pipelined_kernel, bm=bm, bn=bn, bk=bk,
                          unroll=unroll, nk=nk, depth=depth, ep=epilogue,
                          has_bias=has_bias, has_residual=has_residual, n=n),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((depth, bm, bk), jnp.float32),
            pltpu.VMEM((depth, bk, bn), jnp.float32),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "unroll", "epilogue", "n", "interpret"),
)
def log_matmul_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    lut: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
    div_lut: jnp.ndarray | None = None,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    unroll: int = 8,
    epilogue: Epilogue = Epilogue(),
    n: int | None = None,
    interpret: bool = False,
):
    """x[M,K] @ w[K,N_pad] with RAPID approximate products. f32 in/out.

    M, N_pad, K must be divisible by the block sizes (ops.py pads);
    ``bias`` ([N_pad]) / ``residual`` ([M, N_pad]) and the ``epilogue``
    spec are fused into the output tile's last K visit.  ``n`` is the
    real (pre-padding) output width the rms norm stage averages over;
    norm epilogues additionally require ``bn == N_pad`` (whole rows per
    tile) and ``div_lut`` when ``epilogue.div_scheme`` is set.  Returns
    the tail, or ``(tail, pre_norm)`` when ``epilogue.keep_prenorm``.
    """
    m, k = x.shape
    _, npad = w.shape
    if n is None:
        n = npad
    if epilogue.norm is not None and bn != npad:
        raise ValueError(
            f"norm epilogue needs whole rows per tile: bn={bn} != N={npad}")
    if epilogue.wants_norm_lut and div_lut is None:
        raise ValueError("epilogue.div_scheme set but no div_lut operand")
    grid = (m // bm, npad // bn, k // bk)
    has_bias = bias is not None
    has_residual = residual is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((8, 256), lambda i, j, kk: (0, 0)),
    ]
    operands = [x, w, fa.kernel_lut(lut)]
    if has_bias:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j, kk: (j,)))
        operands.append(bias)
    if has_residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(residual)
    if epilogue.wants_norm_lut:
        in_specs.append(pl.BlockSpec((8, 256), lambda i, j, kk: (0, 0)))
        operands.append(fa.kernel_lut(div_lut))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, npad), jnp.float32)
    if epilogue.keep_prenorm:
        out_specs, out_shapes = [out_spec, out_spec], [out_shape, out_shape]
    else:
        out_specs, out_shapes = out_spec, out_shape
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, unroll=unroll, nk=grid[2],
                          ep=epilogue, has_bias=has_bias,
                          has_residual=has_residual, n=n),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
