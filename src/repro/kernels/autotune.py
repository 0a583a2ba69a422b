"""Device-measured KernelSpec autotuner with a committed tuning cache.

The ROADMAP's top open item, AMG-style (arxiv 2310.15495): instead of
trusting the static block heuristics, *search* the legal
``(bm, bn, bk, pipeline.depth)`` space per kernel family and shape
class, score every candidate — on a TPU by actually timing the kernel,
elsewhere with a deterministic static cost model — and persist the
winners in a committed, versioned ``TUNE_baseline.json`` (the same
ratchet discipline as ``BENCH_baseline.json``: regenerate with
``python -m benchmarks.run --retune``, review the diff like code, CI
diff-checks the file for uncommitted drift).

Cache keying.  One entry per ``(family, shape class, scheme,
epilogue kind)`` under a per-``platform`` subtree; the shape class
buckets each problem dim to the next power of two above its minimum
hardware tile, so one tuned entry covers a band of real shapes and the
key is a pure function of python ints — stable across jax pins.
:func:`repro.kernels.spec.resolve_spec` consults the cache through
:func:`cached_spec` with the documented precedence *explicit spec field
> cache hit > heuristic fallback (off-TPU / cache miss)*.

Legality before cost.  Candidates are pre-filtered through the same two
gates production calls hit: the wrappers' ``kernels/budget.py`` working-
set checks (an oversized candidate raises before any kernel is built)
and the static RPD005-008 geometry audit over the captured
``pallas_call`` (:mod:`repro.analysis.capture` +
``repro.analysis.kernel_audit.audit_call``) — so the tuner never times,
or commits, an illegal spec.  The kernel auditor in turn audits every
*committed* entry as a ``tuned/...`` variant (:func:`tuned_audit_
variants`), closing the loop: RPD005-008 gate the cache contents in CI.

Objectives.  On the target device (``platform == "tpu"`` and jax is
actually running on a TPU) candidates are wall-clock timed
(``objective: "device-measured"``).  Everywhere else — the CI host, a
dev laptop — scoring falls back to a deterministic roofline-style cost
model (``objective: "static-model"``): per-step HBM traffic and compute
either overlap (depth >= 2, paying a ``depth-1``-tile pipeline fill) or
serialize (depth 1), plus a per-grid-step scheduling overhead.  The
model only ranks candidates; its absolute numbers are nominal.  Being
deterministic, a ``--retune`` on the CI host reproduces the committed
cpu subtree byte-for-byte, which is what makes the drift check viable.

Search strategy is pluggable: :class:`ExhaustiveSearch` walks the whole
legal grid (it is small); the ``search(candidates, evaluate)``
interface is what a Bayesian strategy (AMG's endgame) would implement
by subsampling candidates and modelling ``evaluate``.

Numerics contract: for ``log_matmul`` and the ``fused_div`` family
every knob here is schedule-only — any committed spec is bit-exact
against the jnp oracle (asserted in ``tests/test_autotune.py``).  For
``flash_attn``, ``depth`` is schedule-only but ``bk`` (the cache chunk
size) re-chunks the online-softmax max, so that family keeps its
existing tight-allclose parity contract vs ``decode_attn_ref``
(bit-exact when the chunking is unchanged — see
``kernels/flash_attn/flash_attn.py``).
"""
from __future__ import annotations

import functools
import json
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from repro.kernels import budget
from repro.kernels.spec import (
    KernelSpec,
    PipelineSpec,
    _rebalance_norm_matmul,
    resolve_spec,
)

__all__ = [
    "TuningCache",
    "Workload",
    "ExhaustiveSearch",
    "workloads",
    "shape_class",
    "entry_key",
    "legal_candidates",
    "static_cost",
    "measure_candidate",
    "cached_spec",
    "get_tuning_cache",
    "set_tuning_cache",
    "default_cache_path",
    "tuned_audit_variants",
    "retune",
]

CACHE_VERSION = 1
CACHE_BASENAME = "TUNE_baseline.json"
ENV_VAR = "RAPID_TUNE_CACHE"

_CONTRACT = (
    "Committed KernelSpec tuning cache.  platforms.<platform>.entries "
    "maps '<family>/<shape class>/<scheme>/<epilogue kind>' to the "
    "winning (bm, bn, bk, depth) for that workload band, selected by "
    "repro.kernels.autotune over the legal candidate grid (budget + "
    "RPD005-008 pre-filtered; objective 'device-measured' on real "
    "hardware, deterministic 'static-model' elsewhere).  "
    "resolve_spec fills unset KernelSpec fields from here with "
    "precedence explicit > cache > heuristic.  Regenerate with "
    "'PYTHONPATH=src python -m benchmarks.run --retune' and commit the "
    "diff; CI re-runs the host-platform retune and fails on drift, and "
    "the kernel auditor re-checks every entry as a tuned/ variant."
)

_ENTRY_FIELDS = ("family", "shapes", "scheme", "epilogue_kind",
                 "bm", "bn", "bk", "depth", "cost_us", "objective")

# ---------------------------------------------------------------------------
# cache keying: shape classes + entry keys (pure python ints -> stable
# across jax pins and platforms)
# ---------------------------------------------------------------------------


def _bucket(v: int, tile: int) -> int:
    """Round ``v`` up to ``tile``, then to the next power of two."""
    v = budget.round_up(max(int(v), 1), tile)
    return 1 << (v - 1).bit_length()


def shape_class(family: str, shapes: Sequence[int]) -> str:
    """Bucketed shape-class label — part of the tuning-cache key."""
    s = [int(v) for v in shapes]
    if family == "log_matmul":
        m, n, k = s
        return (f"{_bucket(m, budget.SUBLANE)}x{_bucket(n, budget.LANE)}"
                f"x{_bucket(k, budget.LANE)}")
    if family in ("fused_softmax", "fused_rms", "fused_div_rowbcast"):
        m, n = s[:2]
        return f"{_bucket(m, budget.SUBLANE)}x{_bucket(n, budget.LANE)}"
    if family == "flash_attn":
        rows, c, g, hd = s
        return (f"r{_bucket(rows, budget.SUBLANE)}c{_bucket(c, budget.LANE)}"
                f"g{_bucket(g, budget.SUBLANE)}d{_bucket(hd, budget.LANE)}")
    raise KeyError(f"unknown kernel family {family!r}")


def entry_key(family: str, shapes: Sequence[int], scheme: Optional[str],
              epilogue_kind: str) -> str:
    """'<family>/<shape class>/<scheme>/<epilogue kind>' cache key."""
    return (f"{family}/{shape_class(family, shapes)}/"
            f"{scheme or 'exact'}/{epilogue_kind}")


# ---------------------------------------------------------------------------
# the committed cache document
# ---------------------------------------------------------------------------


class TuningCache:
    """Versioned winners document (``TUNE_baseline.json``).

    Layout::

        {"version": 1, "contract": "...",
         "platforms": {"cpu": {"objective": ..., "entries": {key: entry}},
                       "tpu": {...}}}

    ``load`` validates hard: corrupt JSON or a schema violation raises
    ``ValueError`` naming the problem, and a version mismatch is
    *stale* — the error says to regenerate with ``--retune``.  A
    missing file is an empty cache (fresh checkout, heuristics apply).
    """

    def __init__(self, doc: dict):
        self.doc = doc

    @classmethod
    def empty(cls) -> "TuningCache":
        return cls({"version": CACHE_VERSION, "contract": _CONTRACT,
                    "platforms": {}})

    @classmethod
    def load(cls, path: os.PathLike | str) -> "TuningCache":
        try:
            text = Path(path).read_text()
        except FileNotFoundError:
            return cls.empty()
        try:
            doc = json.loads(text)
        except json.JSONDecodeError as e:
            raise ValueError(
                f"corrupt tuning cache {path}: not valid JSON ({e}); "
                "regenerate with 'python -m benchmarks.run --retune'")
        if not isinstance(doc, dict) or "platforms" not in doc:
            raise ValueError(
                f"corrupt tuning cache {path}: missing 'platforms' section; "
                "regenerate with 'python -m benchmarks.run --retune'")
        if doc.get("version") != CACHE_VERSION:
            raise ValueError(
                f"stale tuning cache {path}: version {doc.get('version')!r} "
                f"!= {CACHE_VERSION}; regenerate with "
                "'python -m benchmarks.run --retune'")
        for platform, sub in doc["platforms"].items():
            entries = (sub or {}).get("entries", {})
            for key, e in entries.items():
                missing = [f for f in _ENTRY_FIELDS if f not in e]
                if missing:
                    raise ValueError(
                        f"corrupt tuning cache {path}: entry "
                        f"{platform}/{key} missing fields {missing}")
                for f in ("bm", "bn", "bk"):
                    if e[f] is not None and not isinstance(e[f], int):
                        raise ValueError(
                            f"corrupt tuning cache {path}: entry "
                            f"{platform}/{key} field {f}={e[f]!r} is not "
                            "an int or null")
                if not isinstance(e["depth"], int):
                    raise ValueError(
                        f"corrupt tuning cache {path}: entry "
                        f"{platform}/{key} depth={e['depth']!r} is not an "
                        "int")
        return cls(doc)

    def platforms(self) -> Tuple[str, ...]:
        return tuple(self.doc.get("platforms", {}))

    def entries(self, platform: str) -> Dict[str, dict]:
        sub = self.doc.get("platforms", {}).get(platform) or {}
        return sub.get("entries", {})

    def lookup(self, platform: str, key: str) -> Optional[dict]:
        return self.entries(platform).get(key)

    def set_platform(self, platform: str, entries: Dict[str, dict], *,
                     objective: str) -> None:
        """Replace one platform's subtree (a retune touches only the
        platform it actually scored on)."""
        self.doc.setdefault("platforms", {})[platform] = {
            "objective": objective, "entries": entries}

    def save(self, path: os.PathLike | str) -> None:
        with open(path, "w") as fh:
            json.dump(self.doc, fh, indent=2, sort_keys=True)
            fh.write("\n")


def default_cache_path() -> Path:
    """``$RAPID_TUNE_CACHE`` or ``TUNE_baseline.json`` at the repo root."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[3] / CACHE_BASENAME


_ACTIVE: Optional[TuningCache] = None


def get_tuning_cache() -> TuningCache:
    """The memoized process-wide cache ``resolve_spec`` consults."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = TuningCache.load(default_cache_path())
    return _ACTIVE


def set_tuning_cache(cache: Optional[TuningCache]) -> None:
    """Swap the active cache (``None`` = lazily reload from disk)."""
    global _ACTIVE
    _ACTIVE = cache


@functools.lru_cache(maxsize=1)
def _default_platform() -> str:
    import jax
    return jax.default_backend()


def cached_spec(family: str, shapes: Sequence[int], *,
                scheme: Optional[str], epilogue_kind: str,
                platform: Optional[str] = None) -> Optional[dict]:
    """Tuning-cache hit (an entry dict) or ``None`` — what
    :func:`repro.kernels.spec.resolve_spec` calls on a cache-eligible
    dispatch.  A corrupt/stale committed cache raises here, loudly, on
    the first dispatch that consults it."""
    cache = get_tuning_cache()
    platform = platform or _default_platform()
    entries = cache.entries(platform)
    if not entries:
        return None
    return entries.get(entry_key(family, shapes, scheme, epilogue_kind))


# ---------------------------------------------------------------------------
# tuned workloads: one per kernel family x bench shape class
# ---------------------------------------------------------------------------


def _operand(shape, dtype=None):
    """Deterministic non-trivial f32 data (no RNG: retunes reproduce)."""
    import jax.numpy as jnp
    n = 1
    for d in shape:
        n *= int(d)
    v = (jnp.arange(n, dtype=jnp.float32) % 61 - 30.0) / 8.0 + 0.25
    return v.reshape(shape)


@dataclass(frozen=True)
class Workload:
    """One tunable (family, shapes, scheme, epilogue kind) workload."""

    family: str
    shapes: Tuple[int, ...]
    scheme: Optional[str]
    epilogue_kind: str

    @property
    def key(self) -> str:
        return entry_key(self.family, self.shapes, self.scheme,
                         self.epilogue_kind)

    def epilogue(self):
        """The Epilogue object (log_matmul norm/act kinds), else None."""
        if self.family != "log_matmul" or self.epilogue_kind in ("plain",
                                                                 "act"):
            return None
        from repro.core.backend import Epilogue
        norm, _, pre = self.epilogue_kind.partition("+")
        return Epilogue(norm=norm, div_scheme="rapid9",
                        keep_prenorm=pre == "pre")

    def drive(self, spec: KernelSpec, *, interpret: bool = False):
        """Run the family wrapper once with ``spec``; returns the output
        (callers block on it when timing).  ``interpret=False`` under
        the capture shim records real dimension_semantics off-TPU."""
        import jax.numpy as jnp
        if self.family == "log_matmul":
            m, n, k = self.shapes
            kw = {}
            if self.epilogue_kind == "act":
                kw = dict(bias=jnp.zeros((n,), jnp.float32),
                          activation="silu")
            elif self.epilogue_kind != "plain":
                kw = dict(epilogue=self.epilogue())
            from repro.kernels.log_matmul.ops import log_matmul
            return log_matmul(_operand((m, k)), _operand((k, n)),
                              self.scheme, spec=spec, interpret=interpret,
                              **kw)
        if self.family == "fused_softmax":
            from repro.kernels.fused_div.ops import fused_softmax_div
            return fused_softmax_div(_operand(self.shapes), self.scheme,
                                     spec=spec, interpret=interpret)
        if self.family == "fused_rms":
            from repro.kernels.fused_div.ops import fused_rms_div
            return fused_rms_div(_operand(self.shapes), 1e-6, self.scheme,
                                 spec=spec, interpret=interpret)
        if self.family == "fused_div_rowbcast":
            from repro.kernels.fused_div.ops import fused_elementwise_div
            m, n = self.shapes
            denom = _operand((m, 1)) + 8.0  # strictly positive rows
            return fused_elementwise_div(_operand((m, n)), denom,
                                         self.scheme, spec=spec,
                                         interpret=interpret)
        if self.family == "flash_attn":
            rows, c, g, hd = self.shapes
            from repro.kernels.flash_attn.ops import flash_decode_attn
            return flash_decode_attn(
                _operand((rows, 1, g, hd)),
                _operand((rows, c, 1, hd)),
                _operand((rows, c, 1, hd)),
                jnp.zeros((rows, c), jnp.int32), c, 0, self.scheme,
                spec=spec, interpret=interpret)
        raise KeyError(f"unknown kernel family {self.family!r}")


#: matmul bench shape classes (mirrors the kernel auditor's sweep)
MATMUL_SHAPES: Dict[str, Tuple[int, int, int]] = {
    "square512": (512, 512, 512),
    "ktail130": (256, 256, 130),
    "skinny_m4": (4, 512, 512),
    "ntail300": (64, 300, 256),
    "deepk2048": (64, 256, 2048),
}


def workloads() -> List[Workload]:
    """Every tuned workload: all families across the bench shape classes.

    The general elementwise-div fallback and the integer
    ``rapid_mul``/``rapid_div`` units have no spec geometry to tune
    (fixed minimum tiles / flat maps) and are deliberately absent.
    """
    ws = [Workload("log_matmul", s, "rapid10", "plain")
          for s in MATMUL_SHAPES.values()]
    for kind in ("act", "rms", "rms+pre", "softmax"):
        ws.append(Workload("log_matmul", (512, 512, 512), "rapid10", kind))
    ws.append(Workload("log_matmul", (128, 4096, 512), "rapid10", "rms"))
    ws += [
        Workload("fused_softmax", (64, 1000), "rapid9", "plain"),
        Workload("fused_softmax", (8, 128), "rapid9", "plain"),
        Workload("fused_rms", (32, 300), "rapid9", "plain"),
        Workload("fused_div_rowbcast", (128, 4096), "rapid9", "plain"),
        Workload("flash_attn", (8, 256, 4, 64), "rapid9", "plain"),
        Workload("flash_attn", (2, 128, 8, 128), None, "plain"),
    ]
    return ws


# ---------------------------------------------------------------------------
# candidate grids + legality pre-filter
# ---------------------------------------------------------------------------

_BM_GRID = (8, 64, 128, 256)
_BN_GRID = (128, 256)
_BK_GRID = (128, 256, 512)
_BC_GRID = (128, 256, 512)   # flash_attn cache chunk
_DEPTH_GRID = (1, 2, 3)


def _raw_candidates(w: Workload) -> Iterable[KernelSpec]:
    if w.family == "log_matmul":
        for bm in _BM_GRID:
            for bn in _BN_GRID:
                for bk in _BK_GRID:
                    for depth in _DEPTH_GRID:
                        yield KernelSpec(bm=bm, bn=bn, bk=bk,
                                         pipeline=PipelineSpec(depth=depth))
    elif w.family == "flash_attn":
        for bk in _BC_GRID:
            for depth in _DEPTH_GRID:
                yield KernelSpec(bk=bk, pipeline=PipelineSpec(depth=depth))
    else:
        for bm in _BM_GRID:
            for depth in _DEPTH_GRID:
                yield KernelSpec(bm=bm, pipeline=PipelineSpec(depth=depth))


def _geometry_legal(w: Workload, spec: KernelSpec) -> bool:
    """Gate 2: capture the candidate's pallas_call(s) and run the
    RPD005-008 geometry audit over them; any finding disqualifies.
    Gate 1 (the wrapper's budget.check_working_set) shows up here as
    the wrapper raising before a call is captured."""
    from repro.analysis.capture import capture_pallas_calls
    from repro.analysis.kernel_audit import audit_call
    try:
        with capture_pallas_calls() as calls:
            w.drive(spec, interpret=False)
    except Exception:
        return False
    if not calls:
        return False
    for call in calls:
        findings, _ = audit_call(call, f"tune/{w.key}", w.family)
        if findings:
            return False
    return True


def legal_candidates(w: Workload) -> List[KernelSpec]:
    """The pre-filtered candidate list the search strategy scores.

    Candidates are canonicalized first (the norm-epilogue row/slab
    rebalance collapses many raw grid points to one geometry) and
    deduplicated, then pushed through both legality gates, so the tuner
    never evaluates — let alone times — an illegal spec.
    """
    norm = w.family == "log_matmul" and w.epilogue_kind not in ("plain",
                                                                "act")
    out: List[KernelSpec] = []
    seen = set()
    for spec in _raw_candidates(w):
        if norm:
            bm, bn, bk = _rebalance_norm_matmul(
                spec.bm, spec.bn, spec.bk, w.shapes[1])
            spec = KernelSpec(bm=bm, bn=bn, bk=bk, pipeline=spec.pipeline)
        sig = (spec.bm, spec.bn, spec.bk, spec.depth)
        if sig in seen:
            continue
        seen.add(sig)
        if _geometry_legal(w, spec):
            out.append(spec)
    return out


# ---------------------------------------------------------------------------
# objectives: deterministic static cost model / on-device wall time
# ---------------------------------------------------------------------------

_BW = 8.0e11       # nominal HBM bytes/s
_FLOPS = 2.0e13    # nominal lane ops/s (log-domain MACs)
_STEP_OVH = 2.0e-6  # per-grid/pipeline-step scheduling overhead (s)


def _model_time(copy_bytes: float, compute_ops: float, steps: int,
                depth: int, tile_copy_bytes: float) -> float:
    """Roofline-style schedule model shared by every family.

    Depth >= 2 overlaps the next tile's DMA with the current tile's
    compute (paying a ``depth-1``-tile pipeline fill); depth 1
    serializes copy and compute.  Only the *ranking* matters.
    """
    copy_t = copy_bytes / _BW
    compute_t = compute_ops / _FLOPS
    if depth >= 2:
        fill = (depth - 1) * (tile_copy_bytes / _BW)
        return max(copy_t, compute_t) + fill + _STEP_OVH * steps
    return copy_t + compute_t + _STEP_OVH * steps


def static_cost(w: Workload, spec: KernelSpec) -> float:
    """Deterministic modelled seconds for one (workload, candidate)."""
    e = budget.ELEM_BYTES
    if w.family == "log_matmul":
        m, n, k = w.shapes
        bm, bn, bk, depth = spec.bm, spec.bn, spec.bk, spec.depth
        mp = budget.round_up(m, bm)
        np_ = budget.round_up(n, bn)
        kp = budget.round_up(k, bk)
        steps = (mp // bm) * (np_ // bn) * (kp // bk)
        tile = (bm * bk + bk * bn) * e
        out_rows = 2 if w.epilogue_kind.endswith("+pre") else 1
        copy = steps * tile + out_rows * mp * np_ * e
        compute = float(mp) * np_ * kp
        return _model_time(copy, compute, steps, depth, tile)
    if w.family == "flash_attn":
        rows, c, g, hd = w.shapes
        bc, depth = spec.bk, spec.depth
        gp = budget.round_up(g, budget.SUBLANE)
        hdp = budget.round_up(hd, budget.LANE)
        cpad = budget.round_up(c, bc)
        nchunks = cpad // bc
        steps = rows * nchunks
        tile = (2 * bc * hdp + bc) * e
        copy = rows * ((2 * cpad * hdp + cpad) * e + 2 * gp * hdp * e)
        compute = 2.0 * rows * gp * cpad * hdp
        return _model_time(copy, compute, steps, depth, tile)
    m, n = w.shapes[:2]
    bm, depth = spec.bm, spec.depth
    npad = budget.round_up(n, budget.LANE)
    mp = budget.round_up(m, bm)
    steps = mp // bm
    tile = 2 * bm * npad * e
    copy = 2 * mp * npad * e
    compute = 4.0 * mp * npad
    return _model_time(copy, compute, steps, depth, tile)


def measure_candidate(w: Workload, spec: KernelSpec, *,
                      reps: int = 3) -> float:
    """Wall-clock seconds on the actual device (min over ``reps`` after
    a compile/warmup run) — the TPU objective."""
    import jax
    jax.block_until_ready(w.drive(spec, interpret=False))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(w.drive(spec, interpret=False))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# pluggable search
# ---------------------------------------------------------------------------


class ExhaustiveSearch:
    """Walk the whole legal grid; deterministic first-wins argmin.

    The strategy interface — ``search(candidates, evaluate) -> (best,
    cost, n_evaluated)`` over an ordered candidate list and a pure
    scoring callable — is what a Bayesian strategy (AMG arxiv
    2310.15495) would implement instead: subsample ``candidates``,
    model ``evaluate``, stop early.  Exhaustive is exact and, over the
    pre-filtered grids here (tens of points), cheap.
    """

    name = "exhaustive"

    def search(self, candidates: Sequence[KernelSpec],
               evaluate: Callable[[KernelSpec], float]
               ) -> Tuple[KernelSpec, float, int]:
        best: Optional[KernelSpec] = None
        best_cost = float("inf")
        n = 0
        for cand in candidates:
            cost = float(evaluate(cand))
            n += 1
            if best is None or cost < best_cost:
                best, best_cost = cand, cost
        if best is None:
            raise ValueError("no legal candidates to search")
        return best, best_cost, n


# ---------------------------------------------------------------------------
# retune: regenerate one platform subtree of the committed cache
# ---------------------------------------------------------------------------


def retune(platform: Optional[str] = None, *,
           path: Optional[os.PathLike | str] = None,
           strategy: Optional[ExhaustiveSearch] = None,
           verbose: bool = True) -> dict:
    """Re-search every workload and rewrite ``platform``'s cache subtree.

    Only the retuned platform's entries are replaced; other platforms'
    committed winners are preserved (a CPU CI retune must not clobber
    TPU-measured entries).  Candidates are timed on-device only when
    the retune targets the platform jax is actually running on AND that
    platform is a TPU; otherwise the deterministic static model scores
    them, keeping the CI drift check byte-stable.  Returns a summary
    dict (per-key winners + counts).
    """
    platform = platform or _default_platform()
    strategy = strategy or ExhaustiveSearch()
    path = Path(path) if path is not None else default_cache_path()
    try:
        cache = TuningCache.load(path)
    except ValueError as e:
        if verbose:
            print(f"retune: discarding unreadable cache ({e})")
        cache = TuningCache.empty()
    measured = platform == "tpu" and _default_platform() == "tpu"
    objective = "device-measured" if measured else "static-model"
    entries: Dict[str, dict] = {}
    for w in workloads():
        cands = legal_candidates(w)
        evaluate = ((lambda c, w=w: measure_candidate(w, c)) if measured
                    else (lambda c, w=w: static_cost(w, c)))
        best, cost, n = strategy.search(cands, evaluate)
        entries[w.key] = {
            "family": w.family,
            "shapes": list(w.shapes),
            "scheme": w.scheme,
            "epilogue_kind": w.epilogue_kind,
            "bm": best.bm, "bn": best.bn, "bk": best.bk,
            "depth": best.depth,
            "cost_us": round(cost * 1e6, 3),
            "objective": objective,
        }
        if verbose:
            print(f"retune[{platform}] {w.key}: bm={best.bm} bn={best.bn} "
                  f"bk={best.bk} depth={best.depth} "
                  f"({n} legal candidates, {objective} {cost * 1e6:.1f}us)")
    cache.set_platform(platform, entries, objective=objective)
    cache.save(path)
    set_tuning_cache(None)  # new winners visible to the next resolve
    if verbose:
        print(f"retune: wrote {len(entries)} {platform} entries to {path}")
    return {"platform": platform, "objective": objective, "path": str(path),
            "entries": entries}


# ---------------------------------------------------------------------------
# auditor integration: every committed entry is an audited variant
# ---------------------------------------------------------------------------


def entry_spec(entry: dict) -> KernelSpec:
    """The concrete KernelSpec a cache entry pins."""
    return KernelSpec(bm=entry.get("bm"), bn=entry.get("bn"),
                      bk=entry.get("bk"),
                      pipeline=PipelineSpec(depth=int(entry["depth"])))


def tuned_audit_variants() -> List[Tuple[str, str, Callable[[], None]]]:
    """(variant_id, family, driver) rows for every committed tuned spec.

    Consumed by ``repro.analysis.kernel_audit.iter_variants`` so the
    RPD005-008 geometry checks (and ``PIPELINE_REPORT.json``) gate the
    cache contents, not just the heuristic defaults.  Identical entries
    across platforms dedupe to one ``tuned/<key>`` variant; a platform
    whose winner diverges gets its own ``tuned/<key>@<platform>`` row.
    An absent cache contributes nothing; a corrupt one raises (the
    audit job should fail loudly, same as dispatch would).
    """
    cache = TuningCache.load(default_cache_path())
    rows: List[Tuple[str, str, Callable[[], None]]] = []
    seen: Dict[str, tuple] = {}
    for platform in sorted(cache.platforms()):
        for key, e in sorted(cache.entries(platform).items()):
            sig = (e.get("bm"), e.get("bn"), e.get("bk"), e.get("depth"),
                   tuple(e.get("shapes", ())))
            if seen.get(key) == sig:
                continue
            vid = f"tuned/{key}" if key not in seen else \
                f"tuned/{key}@{platform}"
            seen.setdefault(key, sig)
            w = Workload(e["family"], tuple(e["shapes"]), e.get("scheme"),
                         e["epilogue_kind"])
            spec = entry_spec(e)
            rows.append((vid, e["family"],
                         functools.partial(w.drive, spec, interpret=False)))
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.kernels.autotune",
        description="KernelSpec autotuner (winners -> TUNE_baseline.json)")
    ap.add_argument("--platform", default=None,
                    help="platform subtree to retune (default: the host's)")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help=f"cache file (default: $"
                         f"{ENV_VAR} or {CACHE_BASENAME} at the repo root)")
    ap.add_argument("--list", action="store_true",
                    help="print the tuned workloads and exit")
    args = ap.parse_args(argv)
    if args.list:
        for w in workloads():
            print(w.key)
        return 0
    retune(args.platform, path=args.cache)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
