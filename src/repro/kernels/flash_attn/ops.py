"""Public wrapper for the flash-decode kernel (layout + pad + dispatch).

Model-shaped operands ([B, KV, G, hd] queries against [B, C, KV, hd]
caches) are flattened to one row per (batch, kv-head), the cache is
transposed row-major and padded to the chunk grid, and the kernel runs
one grid step per row.  Pad slots carry ``INT32_MAX`` positions, which
the causality mask removes — the same empty-slot convention the ring
caches already use — so padding never perturbs the softmax stats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import float_approx as fa
from repro.kernels import budget
from repro.kernels.flash_attn.flash_attn import flash_decode_pallas
from repro.kernels.flash_attn.ref import SOFTMAX_FLOOR, canon_posq
from repro.kernels.spec import KernelSpec, as_kernel_spec, resolve_spec

__all__ = ["flash_decode_attn"]

_EMPTY_SLOT = jnp.iinfo(jnp.int32).max


def _check_budget(bc: int, gp: int, hdp: int, depth: int) -> None:
    # k/v/sp chunks: `depth` manual VMEM slots each; q and out tiles are
    # grid-staged (PIPELINE_BUFFERS copies); LUT single-buffered
    working = depth * (2 * budget.tile_bytes((bc, hdp))
                       + budget.tile_bytes((bc,)))
    working += 2 * budget.PIPELINE_BUFFERS * budget.tile_bytes((gp, hdp))
    working += budget.tile_bytes(budget.LUT_TILE)
    budget.check_working_set(working)


def flash_decode_attn(
    qf: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    slot_positions: jnp.ndarray,
    pos,
    window: int = 0,
    scheme: str | None = None,
    *,
    floor: float = SOFTMAX_FLOOR,
    spec: KernelSpec | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused single-token attention; same contract as ``decode_attn_ref``.

    qf: [B, KV, G, hd] pre-scaled f32 queries; caches: [B, C, KV, hd];
    slot_positions: [B, C] int32; ``pos`` scalar or [B] / [B, 1].
    ``scheme=None`` is the exact-divide combine (not defaulted from the
    spec: exact softmax is a semantic choice, not a tuning knob).
    ``spec.bk`` overrides the cache chunk size (multiple of 128); left
    unset it resolves via :func:`repro.kernels.spec.resolve_spec` —
    tuning-cache winner, else one lane tile.  ``spec.pipeline.depth``
    sets how many chunk fetches stay in flight.  Depth is schedule-only
    (bit-exact); the chunk size re-chunks the online softmax, keeping
    this family's tight-allclose parity contract vs the reference.
    Returns [B, KV, G, hd] f32.
    """
    ks = as_kernel_spec(spec)
    if interpret is None:
        interpret = ks.interpret
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, kv, g, hd = qf.shape
    c = k_cache.shape[1]
    rows = b * kv
    ks = resolve_spec("flash_attn", (rows, c, g, hd), ks, scheme=scheme)
    bc = ks.bk
    if bc % budget.LANE:
        raise ValueError(f"cache chunk bc={bc} must be a multiple of "
                         f"{budget.LANE} (slot positions ride the lanes)")
    depth = ks.depth
    gp = budget.round_up(g, budget.SUBLANE)
    hdp = budget.round_up(hd, budget.LANE)
    cpad = budget.round_up(c, bc)
    _check_budget(bc, gp, hdp, depth)
    q2 = jnp.pad(qf.astype(jnp.float32).reshape(rows, g, hd),
                 ((0, 0), (0, gp - g), (0, hdp - hd)))
    def cache_rows(cache):
        c2 = cache.transpose(0, 2, 1, 3).reshape(rows, c, hd)
        return jnp.pad(c2.astype(jnp.float32),
                       ((0, 0), (0, cpad - c), (0, hdp - hd)))
    k2 = cache_rows(k_cache)
    v2 = cache_rows(v_cache)
    sp2 = jnp.pad(
        jnp.repeat(slot_positions.astype(jnp.int32), kv, axis=0),
        ((0, 0), (0, cpad - c)), constant_values=_EMPTY_SLOT)
    posq = jnp.broadcast_to(canon_posq(pos).astype(jnp.int32), (b, 1))
    posq2 = jnp.repeat(posq, kv, axis=0)
    dlut = fa.div_lut_device(scheme) if scheme else None
    out = flash_decode_pallas(q2, k2, v2, sp2, posq2, dlut, bc=bc,
                              depth=depth, window=window, floor=float(floor),
                              interpret=interpret)
    return out[:, :g, :hd].reshape(b, kv, g, hd)
