"""jit'd public wrapper for the log_matmul kernel (padding + dispatch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import float_approx as fa
from repro.core.backend import Epilogue, as_epilogue
from repro.kernels import budget
from repro.kernels.log_matmul.log_matmul import (
    log_matmul_pallas,
    log_matmul_pipelined,
)
from repro.kernels.spec import KernelSpec, as_kernel_spec, resolve_spec

__all__ = ["log_matmul"]


def _check_budget(bm: int, bn: int, bk: int, ep: Epilogue,
                  has_bias: bool, has_residual: bool,
                  depth: int = 1) -> None:
    """Fail an oversized block choice (explicit spec blocks included)
    at call time with the same constant the auditor ratchets on.

    At depth 1 the x/w tiles are hardware double-buffered by the Mosaic
    grid pipeline (``PIPELINE_BUFFERS`` copies); at depth >= 2 they are
    manual VMEM scratch slots, ``depth`` copies each, and nothing else
    buffers them.  Output-row tiles stay grid-BlockSpec'd either way.
    """
    xw_buffers = depth if depth >= 2 else budget.PIPELINE_BUFFERS
    working = xw_buffers * (budget.tile_bytes((bm, bk))
                            + budget.tile_bytes((bk, bn)))
    row_tiles = 1 + has_residual + ep.keep_prenorm       # out, res, pre
    working += budget.PIPELINE_BUFFERS * row_tiles * budget.tile_bytes(
        (bm, bn))
    working += budget.tile_bytes(budget.LUT_TILE)        # mul LUT
    if has_bias:
        working += budget.PIPELINE_BUFFERS * budget.tile_bytes((bn,))
    if ep.wants_norm_lut:
        working += budget.tile_bytes(budget.LUT_TILE)
    budget.check_working_set(working)


def log_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scheme: str | None = None,
    *,
    bias: jnp.ndarray | None = None,
    activation: str | None = None,
    residual: jnp.ndarray | None = None,
    epilogue: Epilogue | None = None,
    spec: KernelSpec | None = None,
    blocks=None,
    interpret: bool | None = None,
):
    """RAPID approximate x @ w (f32). Pads every dim to the block grid.

    ``bias`` ([N]) / ``residual`` ([M, N]) and the ``epilogue`` spec
    (``repro.core.backend.Epilogue`` — activation, rms/softmax norm
    stages; ``activation=`` remains the activation-only sugar) are fused
    into the kernel's output-tile epilogue on its last K visit.  Norm
    epilogues force whole lane-padded rows per output tile so the
    canonical padded-row denominator semantics hold.

    Geometry left unset on ``spec`` is resolved through
    :func:`repro.kernels.spec.resolve_spec` — explicit field > committed
    tuning-cache winner (``TUNE_baseline.json``) > budget heuristic —
    and norm epilogues force whole-row output tiles regardless of
    source.  ``spec`` also carries scheme/epilogue defaults and
    interpret mode; explicit keyword arguments override its fields.
    Depth >= 2 (the default, ``budget.PIPELINE_BUFFERS``) dispatches to
    the software-pipelined kernel whose next K-block DMA overlaps the
    current block's compute; depth 1 keeps the legacy grid formulation.
    Both are bit-exact against each other and the chunk=1 jnp scan.
    Returns the tail, or ``(tail, pre_norm)`` when
    ``epilogue.keep_prenorm``.
    """
    if blocks is not None:
        raise TypeError(
            "log_matmul(blocks=...) was removed; pass "
            "spec=KernelSpec(bm=..., bn=..., bk=...) instead")
    ks = as_kernel_spec(spec)
    scheme = scheme or ks.scheme or "rapid10"
    if epilogue is None:
        epilogue = ks.epilogue
    if interpret is None:
        interpret = ks.interpret
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    ep = as_epilogue(epilogue, activation)
    lut = fa.mul_lut_device(scheme)
    m, k = x.shape
    _, n = w.shape
    ks = resolve_spec("log_matmul", (m, n, k), ks, scheme=scheme,
                      epilogue=ep)
    bm, bn, bk = ks.bm, ks.bn, ks.bk
    depth = ks.depth
    _check_budget(bm, bn, bk, ep, bias is not None, residual is not None,
                  depth=depth)
    unroll = 8 if bk % 8 == 0 else 1
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    xp = jnp.pad(x.astype(jnp.float32), ((0, pm), (0, pk)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, pk), (0, pn)))
    bp = None
    if bias is not None:
        bp = jnp.pad(bias.astype(jnp.float32), (0, pn))
    rp = None
    if residual is not None:
        rp = jnp.pad(residual.astype(jnp.float32), ((0, pm), (0, pn)))
    dlut = fa.div_lut_device(ep.div_scheme) if ep.wants_norm_lut else None
    if depth >= 2:
        out = log_matmul_pipelined(
            xp, wp, lut, bp, rp, dlut, bm=bm, bn=bn, bk=bk,
            unroll=min(unroll, bk), depth=depth, epilogue=ep, n=n,
            interpret=interpret)
    else:
        out = log_matmul_pallas(
            xp, wp, lut, bp, rp, dlut, bm=bm, bn=bn, bk=bk,
            unroll=min(unroll, bk), epilogue=ep, n=n, interpret=interpret)
    if ep.keep_prenorm:
        return out[0][:m, :n], out[1][:m, :n]
    return out[:m, :n]
