"""Shared VMEM/tiling budget — one source of truth for kernels and audit.

Every number the Pallas kernels' block-size heuristics rely on lives
here, so the static kernel auditor (``repro.analysis.kernel_audit``)
checks the *same* constants the kernels use instead of re-deriving
"~1 MiB" comments.  The heuristic fallbacks live in one place —
``kernels/spec.py::resolve_spec`` (explicit spec field > tuning-cache
winner > heuristic) — and import from this module, as does the
autotuner's candidate legality filter (``kernels/autotune.py``); the
auditor
fails any captured ``pallas_call`` whose per-grid-step working set
(double-buffered operand tiles + single-buffered LUT constants)
exceeds :func:`vmem_budget`.

All limits assume 32-bit element types (f32 / int32 / uint32), which is
every dtype the kernel families move today.
"""
from __future__ import annotations

__all__ = [
    "LANE",
    "SUBLANE",
    "ELEM_BYTES",
    "LUT_TILE",
    "VMEM_BUDGET_BYTES",
    "PIPELINE_BUFFERS",
    "ROW_SLAB_BYTES",
    "W_SLAB_BYTES",
    "MAX_BM",
    "MAX_BN",
    "MAX_BK",
    "round_up",
    "slab_rows",
    "slab_depth",
    "tile_bytes",
    "check_working_set",
]

# TPU vector-register tile for 32-bit types: 8 sublanes x 128 lanes.
LANE = 128
SUBLANE = 8
ELEM_BYTES = 4

# Every kernel holds its 256-entry coefficient table as one (8, 256)
# int32 tile of identical rows: Mosaic gathers only within a vreg, from
# a source laid out as whole sublane tiles (float_approx.lut_take).
LUT_TILE = (SUBLANE, 256)

# Per-core VMEM capacity the kernels budget against.  TPU cores carry
# 16 MiB of VMEM; Mosaic's grid pipeline double-buffers every
# grid-varying operand, so the *effective* budget per grid step is
# working_set * PIPELINE_BUFFERS <= VMEM_BUDGET_BYTES.  The "cpu" entry
# bounds the interpreter path identically so geometry never forks per
# platform.
VMEM_BUDGET_BYTES = {"tpu": 16 * 2**20, "cpu": 16 * 2**20}
PIPELINE_BUFFERS = 2

# Per-operand slab targets used by the block-size heuristics: row slabs
# (x / out / pre-norm / residual tiles of a norm-epilogue matmul) stay
# under 1 MiB of f32 each, the weight slab under 2 MiB.  With four row
# slabs + one weight slab double-buffered that is ~12 MiB worst case,
# inside the 16 MiB budget with headroom for LUTs and semaphores.
ROW_SLAB_BYTES = 1 << 20
W_SLAB_BYTES = 1 << 21

# Hard caps on matmul block dims (multiples of the minimum tile).
MAX_BM = 256
MAX_BN = 256
MAX_BK = 512


def round_up(v: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``v``."""
    return -(-v // mult) * mult


def slab_rows(npad: int, slab_bytes: int = ROW_SLAB_BYTES) -> int:
    """Largest sublane-aligned row count with rows*npad f32 <= slab."""
    return max(SUBLANE, (slab_bytes // ELEM_BYTES // npad) // SUBLANE * SUBLANE)


def slab_depth(npad: int, slab_bytes: int = W_SLAB_BYTES) -> int:
    """Largest lane-aligned K depth with bk*npad f32 <= slab."""
    return max(LANE, (slab_bytes // ELEM_BYTES // npad) // LANE * LANE)


def tile_bytes(block_shape, elem_bytes: int = ELEM_BYTES) -> int:
    """Bytes of one VMEM tile for a BlockSpec block shape."""
    size = 1
    for d in block_shape:
        size *= int(d)
    return size * elem_bytes


def vmem_budget(platform: str = "tpu") -> int:
    """Per-core VMEM budget in bytes for ``platform``.

    An unknown platform raises: a kernel sized against a guessed budget
    would fail, or run slowly, only once it reached that device.
    """
    if platform not in VMEM_BUDGET_BYTES:
        raise KeyError(f"no VMEM budget for platform {platform!r}; have "
                       f"{sorted(VMEM_BUDGET_BYTES)}")
    return VMEM_BUDGET_BYTES[platform]


def check_working_set(working_set_bytes: int, platform: str = "tpu") -> None:
    """Raise if a kernel's per-grid-step working set blows the budget.

    Called by the family wrappers on the *resolved* block choice —
    explicit spec field, tuning-cache winner, or heuristic alike — so an
    oversized spec fails at call time with the same constant the static
    auditor enforces.
    """
    budget = vmem_budget(platform)
    if working_set_bytes > budget:
        raise ValueError(
            f"kernel working set {working_set_bytes} B exceeds the "
            f"{platform} VMEM budget {budget} B "
            "(repro.kernels.budget.VMEM_BUDGET_BYTES)"
        )
