"""Finding schema + baseline ratchet shared by both audit layers.

A :class:`Finding` is one escape — an operation that bypasses the
backend registry — discovered either by the AST lint (layer 1,
``repro.analysis.rules``) or by the jaxpr audit (layer 2,
``repro.analysis.jaxpr_audit``).  Both layers feed one JSON report and
one committed baseline (``AUDIT_baseline.json`` at the repo root).

The baseline is a **ratchet**, not a snapshot:

  * a current finding whose key is in the baseline is *allowlisted* —
    a known escape awaiting burn-down (the apps ROADMAP item);
  * a current finding whose key is NOT in the baseline is *new* and
    fails CI;
  * a baseline key with no current finding is *stale* — it warns (so
    the allowlist is shrunk in the same PR that fixes the escape) but
    does not fail.

Keys deliberately exclude line numbers so unrelated edits that shift
code do not churn the baseline:

  * AST findings key on ``(rule, file, code)`` where ``code`` is the
    stripped source line — a *moved* escape still matches, a *second
    copy* of the same line is a new escape (multiset semantics);
  * jaxpr findings key on ``(entry, primitive, file)`` — trace-level
    line attribution is too version-dependent (jax releases lower
    differently) to ratchet on, but a dot_general/div escaping in a
    file that had none is always a failure.  Count *increases* within
    an existing key are reported as warnings.
  * kernel findings (layer 3, ``repro.analysis.kernel_audit``) key on
    ``(rule, entry, primitive)`` where ``entry`` is the audited kernel
    variant id (``family/shape_class``) and ``primitive`` the operand
    label (``in0``/``out0``/``kernel``) — geometry is derived from
    BlockSpecs, identical on every jax pin, so the key carries no
    file/line at all.
"""
from __future__ import annotations

import json
from collections import Counter
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Finding",
    "CompareResult",
    "compare",
    "load_baseline",
    "dump_report",
    "prune_stale",
    "findings_from_dicts",
]

#: files the jaxpr layer could not attribute to a source line (older /
#: newer jax dropping source info on some transformed eqns).  These are
#: reported but never fail the ratchet — failing on them would make the
#: gate flap across jax pins.
UNATTRIBUTED = "<unattributed>"


@dataclass(frozen=True)
class Finding:
    """One registry-bypassing operation (either audit layer)."""

    layer: str            # "ast" | "jaxpr" | "kernel"
    rule: str             # RPD001..004 (ast) | "escape" (jaxpr) | RPD005..008
    file: str             # repo-relative path (or UNATTRIBUTED)
    line: int             # 1-based; informative only, not part of the key
    msg: str              # human-readable description
    code: str = ""        # stripped source line (ast layer)
    entry: str = ""       # entry point (jaxpr) | kernel variant id (kernel)
    primitive: str = ""   # jax primitive (jaxpr) | operand label (kernel)
    count: int = 1        # occurrences under this key (jaxpr layer)

    def key(self) -> Tuple[str, ...]:
        if self.layer == "ast":
            return ("ast", self.rule, self.file, self.code)
        if self.layer == "kernel":
            return ("kernel", self.rule, self.entry, self.primitive)
        return ("jaxpr", self.entry, self.primitive, self.file)

    def where(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        if self.layer == "jaxpr":
            return f"{self.entry}: {self.primitive} @ {loc}"
        if self.layer == "kernel":
            return f"{self.entry}: {self.primitive} ({self.file})"
        return loc


def findings_from_dicts(items: List[dict]) -> List[Finding]:
    fields = {f for f in Finding.__dataclass_fields__}
    return [Finding(**{k: v for k, v in d.items() if k in fields})
            for d in items]


@dataclass
class CompareResult:
    """Ratchet verdict: new findings fail, stale entries warn."""

    new: List[Finding] = field(default_factory=list)
    matched: List[Finding] = field(default_factory=list)
    stale: List[Finding] = field(default_factory=list)
    warnings: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.new

    def summary(self) -> str:
        parts = [f"{len(self.matched)} allowlisted",
                 f"{len(self.new)} new", f"{len(self.stale)} stale"]
        if self.warnings:
            parts.append(f"{len(self.warnings)} warnings")
        return ", ".join(parts)


def compare(current: List[Finding], baseline: List[Finding]) -> CompareResult:
    """Multiset ratchet: every current key must be covered by the baseline.

    AST keys may legitimately repeat (two identical escape lines in one
    file), so coverage is counted per key.  jaxpr findings arrive
    pre-aggregated (one Finding per key with a ``count``); a count
    increase within a covered key warns instead of failing — see the
    module docstring for why.
    """
    res = CompareResult()
    base_keys = Counter(f.key() for f in baseline)
    base_by_key: Dict[Tuple[str, ...], Finding] = {
        f.key(): f for f in baseline}
    seen = Counter()
    for f in sorted(current, key=lambda f: (f.file, f.line, f.rule)):
        k = f.key()
        seen[k] += 1
        if f.layer == "jaxpr" and f.file == UNATTRIBUTED:
            res.matched.append(f)
            res.warnings.append(
                f"unattributed jaxpr escape (not ratcheted): {f.where()}")
            continue
        if seen[k] <= base_keys[k]:
            res.matched.append(f)
            b = base_by_key[k]
            if f.layer == "jaxpr" and f.count > b.count:
                res.warnings.append(
                    f"escape count grew {b.count} -> {f.count} for "
                    f"{f.where()} (allowlisted file; not failing)")
        else:
            res.new.append(f)
    for f in baseline:
        k = f.key()
        if seen[k] < base_keys[k]:
            # consume one stale slot per unmatched baseline entry
            seen[k] += 1
            res.stale.append(f)
            res.warnings.append(
                f"stale baseline entry (escape fixed? shrink the "
                f"allowlist): {f.where()}")
    return res


#: baseline/report arrays, one per audit layer
LAYER_SECTIONS = ("ast", "jaxpr", "kernel")


def load_baseline(path: str) -> List[Finding]:
    with open(path) as fh:
        data = json.load(fh)
    items: List[dict] = []
    for section in LAYER_SECTIONS:
        items += data.get(section, [])
    return findings_from_dicts(items)


def dump_report(path: str, ast_findings: List[Finding],
                jaxpr_findings: List[Finding],
                kernel_findings: Optional[List[Finding]] = None,
                jaxpr_meta: Optional[dict] = None,
                result: Optional[CompareResult] = None) -> dict:
    """Write the merged three-layer JSON report (also the baseline format).

    A report file doubles as a baseline: ``load_baseline`` reads the
    same ``ast`` / ``jaxpr`` / ``kernel`` arrays, so regenerating the
    allowlist is ``python -m repro.analysis --json AUDIT_baseline.json``.
    """
    doc: dict = {
        "version": 1,
        "ast": [asdict(f) for f in ast_findings],
        "jaxpr": [asdict(f) for f in jaxpr_findings],
        "kernel": [asdict(f) for f in (kernel_findings or [])],
    }
    if jaxpr_meta is not None:
        doc["jaxpr_meta"] = jaxpr_meta
    if result is not None:
        doc["ratchet"] = {
            "ok": result.ok,
            "new": [asdict(f) for f in result.new],
            "stale": [asdict(f) for f in result.stale],
            "warnings": result.warnings,
        }
    if path:
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=False)
            fh.write("\n")
    return doc


def prune_stale(path: str, current: List[Finding]) -> int:
    """Drop baseline entries with no matching current finding, in place.

    The mechanical arm of the ratchet's stale warning: after a PR fixes
    an allowlisted escape, ``python -m repro.analysis --baseline
    AUDIT_baseline.json --prune-stale`` rewrites the baseline without
    the fixed entries (multiset semantics — with two identical
    allowlisted lines and one fixed, one entry survives).  Sections
    other than the per-layer finding arrays (``jaxpr_meta`` etc.) are
    preserved.  Returns the number of entries removed.
    """
    with open(path) as fh:
        data = json.load(fh)
    baseline = load_baseline(path)
    stale = Counter(f.key() for f in compare(current, baseline).stale)
    removed = 0
    for section in LAYER_SECTIONS:
        kept: List[dict] = []
        for item in data.get(section, []):
            (f,) = findings_from_dicts([item])
            k = f.key()
            if stale[k] > 0:
                stale[k] -= 1
                removed += 1
            else:
                kept.append(item)
        if section in data:
            data[section] = kept
    if removed:
        with open(path, "w") as fh:
            json.dump(data, fh, indent=2, sort_keys=False)
            fh.write("\n")
    return removed
