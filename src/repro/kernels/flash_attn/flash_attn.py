"""Pallas TPU kernel: fused flash-decode attention with the RAPID divider.

One grid step owns one (batch, kv-head) row: the query block stays
VMEM-resident while the kernel scans the cache in ``bc``-slot chunks,
keeping running online-softmax stats (m, l, acc) and finishing with the
floored combine divide — through ``float_approx.log_div_f32`` when a
RAPID scheme is set.  This replaces the separate score-matmul + mask +
softmax-stats + value-matmul + combine passes of the jnp decode path
with a single kernel whose intermediates never visit HBM.

The cache chunks are software-pipelined exactly like the other kernel
families: k / v / slot-position chunks live in ANY (HBM) memory and
rotate through ``depth`` VMEM scratch slots via explicit
``make_async_copy`` DMAs, so chunk c+depth-1's fetch overlaps chunk c's
compute.  Depth 1 degenerates to a strictly sequential fetch-compute
loop (the same kernel body; no separate formulation).

Numerics: the score/value contractions are exact (MXU dot_generals, as
``models/layers.py`` keeps activation-activation contractions exact);
the online chunked max can differ from the jnp reference's global max
by reassociation, so parity vs :func:`..ref.decode_attn_ref` is tight
allclose, not bit-exact — except when the whole cache fits one chunk,
where the schedules coincide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import float_approx as fa
from repro.kernels.fused_div.ref import SOFTMAX_FLOOR

__all__ = ["flash_decode_pallas"]


def _flash_kernel(q_ref, posq_ref, k_hbm, v_hbm, sp_hbm, *rest, bc: int,
                  nc: int, depth: int, window: int, floor: float,
                  has_lut: bool):
    refs = list(rest)
    dlut_ref = refs.pop(0) if has_lut else None
    o_ref, k_scr, v_scr, sp_scr, k_sem, v_sem, sp_sem = refs
    r = pl.program_id(0)

    def dmas(slot, c):
        sl = pl.ds(c * bc, bc)
        return (
            pltpu.make_async_copy(k_hbm.at[r, sl, :], k_scr.at[slot],
                                  k_sem.at[slot]),
            pltpu.make_async_copy(v_hbm.at[r, sl, :], v_scr.at[slot],
                                  v_sem.at[slot]),
            pltpu.make_async_copy(sp_hbm.at[r, :, sl], sp_scr.at[slot],
                                  sp_sem.at[slot]),
        )

    for d in range(min(depth - 1, nc)):
        for cp in dmas(d % depth, d):
            cp.start()

    q = q_ref[0]            # [Gp, hdp]
    posq = posq_ref[r]      # whole vector in SMEM; one scalar per row
    gp, hdp = q.shape

    def step(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, depth)
        nxt = c + depth - 1

        @pl.when(nxt < nc)
        def _prefetch():
            for cp in dmas(jax.lax.rem(nxt, depth), nxt):
                cp.start()

        for cp in dmas(slot, c):
            cp.wait()
        kb = k_scr[slot]        # [bc, hdp]
        vb = v_scr[slot]
        spb = sp_scr[slot]      # [1, bc]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = spb <= posq
        if window:
            mask &= spb > posq - window
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(jnp.isfinite(m_new), jnp.exp(s - m_new), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc = acc * corr + pv
        return m_new, l, acc

    m0 = jnp.full((gp, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((gp, 1), jnp.float32)
    a0 = jnp.zeros((gp, hdp), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nc, step, (m0, l0, a0))
    l = jnp.maximum(l, floor)
    if has_lut:
        out = fa.log_div_f32(acc, l, dlut_ref[...])
    else:
        out = acc / l
    o_ref[0] = out


@functools.partial(
    jax.jit,
    static_argnames=("bc", "depth", "window", "floor", "interpret"),
)
def flash_decode_pallas(
    q: jnp.ndarray,       # [R, Gp, hdp] f32, pre-scaled
    k: jnp.ndarray,       # [R, Cp, hdp] f32
    v: jnp.ndarray,       # [R, Cp, hdp] f32
    sp: jnp.ndarray,      # [R, Cp] int32 (INT32_MAX = empty/pad slot)
    posq: jnp.ndarray,    # [R, 1] int32
    div_lut: jnp.ndarray | None = None,
    *,
    bc: int = 128,
    depth: int = 2,
    window: int = 0,
    floor: float = SOFTMAX_FLOOR,
    interpret: bool = False,
):
    """Fused decode attention over pre-padded rows; Cp % bc == 0."""
    r, gp, hdp = q.shape
    cp = k.shape[1]
    nc = cp // bc
    has_lut = div_lut is not None
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    in_specs = [
        pl.BlockSpec((1, gp, hdp), lambda i: (i, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),      # posq: scalar reads
        any_spec,                                    # k: manual DMA
        any_spec,                                    # v: manual DMA
        any_spec,                                    # slot positions
    ]
    # slot positions ride a unit sublane dim so each row's chunk DMA is
    # a whole (1, bc) tile rather than a misaligned single-sublane slice
    operands = [q, posq.reshape(-1), k, v, sp.reshape(r, 1, cp)]
    if has_lut:
        in_specs.append(pl.BlockSpec((8, 256), lambda i: (0, 0)))
        operands.append(fa.kernel_lut(div_lut))
    return pl.pallas_call(
        functools.partial(_flash_kernel, bc=bc, nc=nc, depth=depth,
                          window=window, floor=floor, has_lut=has_lut),
        grid=(r,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gp, hdp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, gp, hdp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((depth, bc, hdp), jnp.float32),
            pltpu.VMEM((depth, bc, hdp), jnp.float32),
            pltpu.VMEM((depth, 1, bc), jnp.int32),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="flash_decode_pallas",
    )(*operands)
