"""Shared kernel-call contract: one spec from call site to auditor.

Every kernel-family public wrapper (``log_matmul``, the ``fused_*_div``
trio, ``rapid_mul``/``rapid_div``, ``flash_decode_attn``) accepts the
same :class:`KernelSpec`, and ``core/backend.py``'s dispatchers and the
kernel auditor's capture drivers pass the same object through — block
geometry, pipeline depth, interpret mode, scheme and epilogue are one
hashable value instead of a family-specific kwarg soup.

Both dataclasses are frozen (hashable), so a spec can ride ``jax.jit``
static arguments and ``functools.partial`` keywords unchanged.

Pipeline semantics (:class:`PipelineSpec`):

  * ``depth == 1`` — the legacy grid formulation: one tile per grid
    step, HBM->VMEM staging left to Mosaic's hardware-managed grid
    pipeline (which itself double-buffers grid-varying operands).
  * ``depth >= 2`` — explicit software pipelining: the wrapper lowers
    to the manual async-copy kernel, which keeps operands in ANY
    (HBM) memory and rotates ``depth`` VMEM scratch slots per operand,
    starting the DMA for tile ``t+depth-1`` before computing tile
    ``t`` (the paper's pipelined-unit schedule, one slot per stage).
  * ``depth is None`` — *deferred*: :func:`resolve_spec` fills it from
    the tuning cache, else :data:`repro.kernels.budget.PIPELINE_BUFFERS`
    — so the software-pipelined path stays the production default and
    an explicitly requested depth is distinguishable from the default.

Spec resolution (:func:`resolve_spec`) is the single choke point every
wrapper and ``core/backend.py`` dispatcher goes through.  Precedence,
per field:

  1. an explicitly-set :class:`KernelSpec` field (the caller's choice);
  2. a tuning-cache hit — the committed, device-measured winners in
     ``TUNE_baseline.json`` (``repro.kernels.autotune``), keyed by
     ``(family, shape class, scheme, epilogue kind, platform)``;
  3. the budget-derived heuristic fallback (off-TPU / cache miss) —
     the former ``log_matmul/ops.py::_pick_blocks`` and
     ``fused_div/ops.py::_pick_bm``, now private to this module.

Norm-epilogue matmuls additionally force whole lane-padded rows per
output tile (canonical denominator semantics) and rebalance ``bm``/
``bk`` to keep the VMEM working set bounded; that is a *hard geometry
constraint*, applied after resolution to every source — explicit,
cached, or heuristic — exactly as the wrapper always did.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Sequence, Tuple, Union

from repro.kernels import budget

__all__ = [
    "PipelineSpec",
    "KernelSpec",
    "as_kernel_spec",
    "resolve_spec",
    "epilogue_kind",
    "RESOLVED_FAMILIES",
]

#: kernel families :func:`resolve_spec` knows how to resolve, with the
#: ``shapes`` tuple each expects (all python ints, pre-padding):
#:   log_matmul          (m, n, k)
#:   fused_softmax       (rows, n)
#:   fused_rms           (rows, n)
#:   fused_div_rowbcast  (rows, n)
#:   flash_attn          (rows, cache_slots, group, head_dim)
RESOLVED_FAMILIES = (
    "log_matmul", "fused_softmax", "fused_rms", "fused_div_rowbcast",
    "flash_attn",
)

_ROW_FAMILIES = ("fused_softmax", "fused_rms", "fused_div_rowbcast")


@dataclass(frozen=True)
class PipelineSpec:
    """How deep the software pipeline stages HBM->VMEM tile copies."""

    #: number of VMEM scratch slots per pipelined operand; 1 disables
    #: the manual pipeline (hardware grid double-buffering only); None
    #: defers to resolve_spec (tuning cache, else PIPELINE_BUFFERS)
    depth: Optional[int] = None

    def __post_init__(self):
        if self.depth is not None and not 1 <= int(self.depth) <= 8:
            raise ValueError(
                f"pipeline depth {self.depth} outside [1, 8] "
                "(deeper than 8 slots has no VMEM headroom)")


@dataclass(frozen=True)
class KernelSpec:
    """Uniform kernel-call spec shared by every kernel family.

    ``bm``/``bn``/``bk`` are rows / lanes / contraction depth per tile
    (``bk`` doubles as the cache chunk size for ``flash_decode_attn``).
    A ``None`` field defers to :func:`resolve_spec` — tuning cache hit,
    else the family's budget-derived heuristic; families without a K
    dimension (the fused dividers, the integer units) ignore ``bk``.
    ``interpret=None`` keeps the per-wrapper CPU autodetect.
    """

    bm: Optional[int] = None
    bn: Optional[int] = None
    bk: Optional[int] = None
    pipeline: PipelineSpec = field(default_factory=PipelineSpec)
    interpret: Optional[bool] = None
    scheme: Optional[str] = None
    epilogue: Optional[object] = None  # repro.core.backend.Epilogue

    @property
    def depth(self) -> int:
        """Concrete pipeline depth (deferred -> PIPELINE_BUFFERS)."""
        d = self.pipeline.depth
        return budget.PIPELINE_BUFFERS if d is None else int(d)

    def with_depth(self, depth: Optional[int]) -> "KernelSpec":
        return replace(self, pipeline=PipelineSpec(depth=depth))


def as_kernel_spec(spec: Union["KernelSpec", None]) -> KernelSpec:
    """Canonicalize a wrapper's ``spec=`` argument (None -> defaults).

    The one-release positional ``blocks=(bm, bn, bk)`` tuple shim is
    gone: tuples/lists raise ``TypeError`` naming the replacement.
    """
    if spec is None:
        return KernelSpec()
    if isinstance(spec, KernelSpec):
        return spec
    if isinstance(spec, (tuple, list)):
        raise TypeError(
            "positional blocks tuples were removed; pass "
            "spec=KernelSpec(bm=..., bn=..., bk=...) instead")
    raise TypeError(f"spec must be a KernelSpec or None, got {spec!r}")


def epilogue_kind(epilogue: Optional[object]) -> str:
    """Canonical epilogue label for tuning-cache keys.

    Collapses an ``Epilogue`` spec (duck-typed: this module must not
    import ``core.backend``) to the coarse classes that change kernel
    geometry: ``plain`` (identity), ``act`` (elementwise-only stages),
    ``rms`` / ``softmax`` (norm stages, whole-row output tiles), with
    ``+pre`` appended when the pre-norm value is kept (an extra row
    slab in VMEM).
    """
    if epilogue is None:
        return "plain"
    norm = getattr(epilogue, "norm", None)
    if norm is None:
        act = getattr(epilogue, "activation", None)
        return "plain" if act is None else "act"
    kind = str(norm)
    if getattr(epilogue, "keep_prenorm", False):
        kind += "+pre"
    return kind


# --------------------------------------------------------------------------
# heuristic fallbacks (formerly log_matmul/ops.py::_pick_blocks and
# fused_div/ops.py::_pick_bm — private to the resolve_spec choke point)
# --------------------------------------------------------------------------

def _default_matmul_blocks(m: int, n: int, k: int) -> Tuple[int, int, int]:
    """Hardware-aligned matmul blocks that fit the VMEM budget.

    Every block is clamped to the problem size *rounded up to the
    minimum tile* (``budget.SUBLANE`` x ``budget.LANE`` for f32):
    degenerate dims smaller than a tile used to leak through as
    unaligned block shapes, and a K dim between 128 and 512 that was
    not a multiple of the unroll factor silently dropped its tail
    elements (``bk // unroll`` truncated — the smoke-mode shapes
    exposed this).  Keeping bk a multiple of 128 keeps it a multiple of
    any unroll <= 8.  All caps come from :mod:`repro.kernels.budget` —
    the same constants the static kernel auditor (RPD005/RPD006)
    enforces over the captured BlockSpecs.
    """
    bm = min(budget.MAX_BM, budget.round_up(m, budget.SUBLANE))
    bn = min(budget.MAX_BN, budget.round_up(n, budget.LANE))
    bk = min(budget.MAX_BK, budget.round_up(k, budget.LANE))
    return bm, bn, bk


def _default_row_bm(m: int, npad: int) -> int:
    """Rows per fused-divider slab: >= the f32 sublane tile, capped so
    the in/out slabs stay under ``budget.ROW_SLAB_BYTES`` each — the
    same constants the static kernel auditor (RPD005) enforces."""
    rows = budget.round_up(m, budget.SUBLANE)
    return max(budget.SUBLANE,
               min(budget.MAX_BM, budget.slab_rows(npad), rows))


def _rebalance_norm_matmul(bm: int, bn: int, bk: int, n: int
                           ) -> Tuple[int, int, int]:
    """Whole lane-padded rows per output tile (canonical denominator
    semantics); rebalance bm/bk so the VMEM working set stays bounded
    when N is a real model width — <= ROW_SLAB_BYTES per bm-row slab
    (out / pre / residual), <= W_SLAB_BYTES for w."""
    bn = budget.round_up(n, budget.LANE)
    bm = max(budget.SUBLANE, min(bm, budget.slab_rows(bn)))
    bk = max(budget.LANE, min(bk, budget.slab_depth(bn)))
    return bm, bn, bk


# --------------------------------------------------------------------------
# the spec-resolution choke point
# --------------------------------------------------------------------------

def resolve_spec(
    family: str,
    shapes: Sequence[int],
    spec: Optional[KernelSpec] = None,
    *,
    scheme: Optional[str] = None,
    epilogue: Optional[object] = None,
    platform: Optional[str] = None,
) -> KernelSpec:
    """Resolve a (possibly partial) KernelSpec to concrete geometry.

    ``family`` is one of :data:`RESOLVED_FAMILIES`; ``shapes`` the
    family's problem-shape tuple (see there); ``scheme`` / ``epilogue``
    the call's arithmetic scheme and (for ``log_matmul``) epilogue spec
    — both part of the tuning-cache key; ``platform`` defaults to
    ``jax.default_backend()``.

    Per-field precedence: explicit spec field > tuning-cache hit >
    heuristic fallback (off-TPU / cache miss).  Fields a family does
    not use are left untouched.  Norm-epilogue matmul geometry (whole
    padded rows, slab-clamped bm/bk) is enforced *after* resolution on
    every source, preserving the wrapper's historic hard constraint.
    Idempotent: resolving an already-resolved spec is a no-op.
    """
    if family not in RESOLVED_FAMILIES:
        raise KeyError(
            f"unknown kernel family {family!r}; have {RESOLVED_FAMILIES}")
    ks = as_kernel_spec(spec)
    norm = getattr(epilogue, "norm", None)

    needs_bm = family != "flash_attn"
    needs_bn = needs_bk = family == "log_matmul"
    if family == "flash_attn":
        needs_bk = True
    depth_unset = ks.pipeline.depth is None
    unset = ((needs_bm and ks.bm is None)
             or (needs_bn and ks.bn is None)
             or (needs_bk and ks.bk is None)
             or depth_unset)

    bm, bn, bk, depth = ks.bm, ks.bn, ks.bk, ks.pipeline.depth
    if unset:
        hit = _cache_lookup(family, shapes, scheme=scheme,
                            epilogue=epilogue, platform=platform)
        if hit is not None:
            bm = bm if bm is not None else hit.get("bm")
            bn = bn if bn is not None else hit.get("bn")
            bk = bk if bk is not None else hit.get("bk")
            depth = depth if depth is not None else hit.get("depth")

    if family == "log_matmul":
        m, n, k = (int(s) for s in shapes)
        hbm, hbn, hbk = _default_matmul_blocks(m, n, k)
        bm = int(bm) if bm is not None else hbm
        bn = int(bn) if bn is not None else hbn
        bk = int(bk) if bk is not None else hbk
        if norm is not None:
            bm, bn, bk = _rebalance_norm_matmul(bm, bn, bk, n)
    elif family in _ROW_FAMILIES:
        m, n = (int(s) for s in shapes[:2])
        if bm is None:
            bm = _default_row_bm(m, budget.round_up(n, budget.LANE))
        bm = int(bm)
    else:  # flash_attn: bk is the cache chunk size
        bk = int(bk) if bk is not None else budget.LANE
    depth = budget.PIPELINE_BUFFERS if depth is None else int(depth)

    return replace(ks, bm=bm, bn=bn, bk=bk,
                   pipeline=PipelineSpec(depth=depth))


def _cache_lookup(family, shapes, *, scheme, epilogue, platform):
    """Consult the committed tuning cache (lazy import: no cycle, and
    spec construction stays importable without jax)."""
    from repro.kernels import autotune

    return autotune.cached_spec(family, shapes, scheme=scheme,
                                epilogue_kind=epilogue_kind(epilogue),
                                platform=platform)
