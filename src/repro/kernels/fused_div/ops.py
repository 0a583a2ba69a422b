"""jit'd public wrappers for the fused divider kernels (pad + dispatch).

The wrappers own shape plumbing only: collapse leading dims to rows, pad
rows to the block grid and lanes to a multiple of 128, dispatch, slice.
Padding values are chosen so the pad lanes stay numerically inert (zeros
in the reduced numerator, ones in elementwise denominators) and the pad
rows cannot trap (0/floor = 0, sqrt(eps) > 0); everything padded is
sliced off before return.

Every wrapper accepts the shared :class:`repro.kernels.spec.KernelSpec`
(``spec=``); geometry left unset resolves through
:func:`repro.kernels.spec.resolve_spec` — explicit ``bm``/depth >
committed tuning-cache winner (``TUNE_baseline.json``) > the slab-row
heuristic.  ``spec.pipeline.depth`` selects the formulation — depth 1
the legacy grid loop, depth >= 2 (the default,
``budget.PIPELINE_BUFFERS``) the software-pipelined slab loop with
explicit async-copy staging.  Both are bit-exact against each other and
the jnp reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import float_approx as fa
from repro.kernels import budget
from repro.kernels.fused_div import ref
from repro.kernels.fused_div.fused_div import (
    div_pallas,
    div_rowbcast_pallas,
    rms_div_pallas,
    softmax_div_pallas,
)
from repro.kernels.spec import KernelSpec, as_kernel_spec, resolve_spec

__all__ = ["fused_softmax_div", "fused_rms_div", "fused_elementwise_div"]


def _check_budget(bm: int, npad: int, depth: int) -> None:
    # in + out slabs: grid double-buffered at depth 1, `depth` manual
    # VMEM scratch slots per side at depth >= 2; LUT single-buffered
    buffers = depth if depth >= 2 else budget.PIPELINE_BUFFERS
    budget.check_working_set(
        2 * buffers * budget.tile_bytes((bm, npad))
        + budget.tile_bytes(budget.LUT_TILE))


def _resolve(spec, interpret):
    ks = as_kernel_spec(spec)
    if interpret is None:
        interpret = ks.interpret
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return ks, interpret


def _as_rows(x: jnp.ndarray, ks: KernelSpec, family: str,
             scheme: str | None):
    """[..., n] -> padded [M_pad, n_pad] f32 + the resolved spec and
    unpad geometry.  ``family`` keys the resolve_spec tuning-cache
    lookup (explicit ``bm``/depth > cache > slab-row heuristic); the
    budget check applies to the winner regardless of source."""
    lead, n = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, n).astype(jnp.float32)
    m = x2.shape[0]
    npad = ref.padded_width(n)
    ks = resolve_spec(family, (m, n), ks, scheme=scheme)
    bm = ks.bm
    _check_budget(bm, npad, ks.depth)
    mp = -(-m // bm) * bm
    xp = jnp.pad(x2, ((0, mp - m), (0, npad - n)))
    return xp, ks, m, n, lead


def fused_softmax_div(e: jnp.ndarray, scheme: str | None = None, *,
                      floor: float = ref.SOFTMAX_FLOOR,
                      spec: KernelSpec | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Softmax combine: e / max(sum(e, -1), floor), fused in one pass."""
    ks, interpret = _resolve(spec, interpret)
    scheme = scheme or ks.scheme or "rapid9"
    lut = fa.div_lut_device(scheme)
    ep, ks, m, n, lead = _as_rows(e, ks, "fused_softmax", scheme)
    out = softmax_div_pallas(ep, lut, floor=float(floor), bm=ks.bm,
                             depth=ks.depth, interpret=interpret)
    return out[:m, :n].reshape(*lead, n).astype(e.dtype)


def fused_rms_div(x: jnp.ndarray, eps: float, scheme: str | None = None, *,
                  spec: KernelSpec | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """RMS normalize: x / sqrt(mean(x^2, -1) + eps), fused in one pass."""
    ks, interpret = _resolve(spec, interpret)
    scheme = scheme or ks.scheme or "rapid9"
    lut = fa.div_lut_device(scheme)
    xp, ks, m, n, lead = _as_rows(x, ks, "fused_rms", scheme)
    out = rms_div_pallas(xp, lut, n=n, eps=float(eps), bm=ks.bm,
                         depth=ks.depth, interpret=interpret)
    return out[:m, :n].reshape(*lead, n).astype(x.dtype)


def fused_elementwise_div(a: jnp.ndarray, b: jnp.ndarray,
                          scheme: str | None = None, *,
                          spec: KernelSpec | None = None,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Elementwise RAPID a/b (broadcasting ok); output dtype follows a.

    The one-denominator-per-row shape (``b`` scalar or trailing dim 1,
    as the online-softmax combine divides ``acc`` by ``l[..., None]``)
    dispatches to a row-broadcast kernel: ``b`` stays a vector and the
    lane broadcast happens in VMEM instead of materialising an a-sized
    denominator tensor in HBM.  The tiled fallback for fully general
    broadcasts has no slab structure to pipeline and always runs the
    grid formulation.
    """
    ks, interpret = _resolve(spec, interpret)
    scheme = scheme or ks.scheme or "rapid9"
    lut = fa.div_lut_device(scheme)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    orig = a.dtype
    out_shape = jnp.broadcast_shapes(a.shape, b.shape)
    rowbcast = (out_shape == a.shape and a.ndim >= 1
                and (b.ndim == 0 or b.shape[-1] == 1))
    if rowbcast:
        ap, ks, m, n, lead = _as_rows(a, ks, "fused_div_rowbcast", scheme)
        # [M_pad, 1] column: the denominator's row count lives on the
        # sublane axis where bm-alignment holds (see _div_rowbcast_kernel)
        bv = jnp.broadcast_to(b, (*a.shape[:-1], 1)).reshape(-1, 1)
        bv = jnp.pad(bv.astype(jnp.float32), ((0, ap.shape[0] - m), (0, 0)),
                     constant_values=1.0)
        out = div_rowbcast_pallas(ap, bv, lut, bm=ks.bm, depth=ks.depth,
                                  interpret=interpret)
        return out[:m, :n].reshape(*lead, n).astype(orig)
    a, b = jnp.broadcast_arrays(a, b)
    shape = a.shape
    br, bc = 8, ref.LANE
    af = a.reshape(-1).astype(jnp.float32)
    bf = b.reshape(-1).astype(jnp.float32)
    pad = (-af.size) % (br * bc)
    af = jnp.pad(af, (0, pad)).reshape(-1, bc)
    bf = jnp.pad(bf, (0, pad), constant_values=1.0).reshape(-1, bc)
    out = div_pallas(af, bf, lut, block=(br, bc), interpret=interpret)
    return out.reshape(-1)[: a.size].reshape(shape).astype(orig)
