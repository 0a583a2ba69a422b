"""Benchmark harness: one module per paper table/figure.

  table3_accuracy    Table III accuracy columns (ARE/PRE/bias, all widths)
  table3_throughput  Table III throughput columns (CPU proxy + op costs)
  fused_div          fused divider family vs reduce+divide round-trips
  apps_qor           Figs. 8-10 end-to-end application QoR
  e2e_train          trainability of RAPID arithmetic (loss curves)
  roofline           kernel roofline: pipeline depth 1 vs 2 and the
                     fused flash-attention kernel vs separate passes
  roofline_report    SSRoofline table from the dry-run artifacts
  serve_load         continuous batching vs fixed-slot under a Poisson
                     arrival trace (tokens/s + p50/p99 latency)

``python -m benchmarks.run [name ...] [--smoke]`` — no names runs
everything.  ``--smoke`` runs every module at tiny shapes / one rep so
CI can prove the whole harness still executes (a bit-rot gate, not a
measurement); any sub-benchmark that raises is reported with its
traceback and the process exits non-zero.

Regression gate: ``--json PATH`` writes a machine-readable result file
(per-benchmark status + wall seconds); ``--baseline PATH`` diffs the
run against a committed reference (``BENCH_baseline.json`` at the repo
root) and fails when a benchmark present in the baseline is missing,
failed, or slower than ``--tolerance`` x its baseline wall time.  The
tolerance is deliberately generous — CI runners are noisy; the gate is
for order-of-magnitude rot (an accidentally-quadratic path, an
interpreter fallback), not microbenchmarking.  Sub-second baselines are
compared against ``tolerance * max(wall, MIN_GATED_WALL_S)`` so timer
jitter on trivial modules cannot fail a PR.

Benchmarks new in this run (no baseline row) are not gated, but a
gated run *auto-records* the ones that passed into the baseline
artifact — same mode only (smoke vs full) — so the module that skipped
the gate once is gated from its second run onward instead of silently
forever.

Tuning: ``--retune`` re-runs the KernelSpec autotuner
(``repro.kernels.autotune.retune``) for the host platform *before* the
benchmarks, rewriting ``--tune-baseline`` (default
``TUNE_baseline.json``); the benchmarks then run against the fresh
winners.  Off-TPU the tuner's objective is a deterministic static cost
model, so a CI ``--retune`` reproduces the committed file byte-for-byte
— the bench-gate job diff-checks it for uncommitted drift.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

ALL = ["table3_accuracy", "table3_throughput", "fused_div", "apps_qor",
       "e2e_train", "roofline", "roofline_report", "serve_load"]

#: Below this baseline wall time, the time gate compares against
#: tolerance * MIN_GATED_WALL_S instead (pure-jitter regime).
MIN_GATED_WALL_S = 2.0


def compare_to_baseline(results: dict, baseline: dict,
                        tolerance: float) -> list:
    """Diff a run's results against a baseline; return regression strings.

    ``results`` / ``baseline`` are ``{name: {"status", "wall_s"}}``.
    Regressions: a baseline benchmark that is missing or failed in this
    run, or whose wall time exceeds
    ``tolerance * max(baseline_wall, MIN_GATED_WALL_S)``.  Benchmarks
    new in this run (absent from the baseline) are not gated.
    """
    problems = []
    for name, base in baseline.items():
        got = results.get(name)
        if got is None:
            problems.append(f"{name}: present in baseline but did not run")
            continue
        if got.get("status") != "ok":
            problems.append(f"{name}: status {got.get('status')!r} "
                            "(baseline: ok)")
            continue
        budget = tolerance * max(float(base.get("wall_s", 0.0)),
                                 MIN_GATED_WALL_S)
        if float(got.get("wall_s", 0.0)) > budget:
            problems.append(
                f"{name}: wall {got['wall_s']:.1f}s exceeds "
                f"{budget:.1f}s (baseline {base.get('wall_s', 0):.1f}s "
                f"x tolerance {tolerance})")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", default=[],
                    help=f"benchmarks to run (default: all of {ALL})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, one rep: CI bit-rot gate")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-benchmark status + wall seconds as JSON")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="diff against a baseline JSON (BENCH_baseline.json) "
                         "and fail on regressions")
    ap.add_argument("--tolerance", type=float, default=4.0,
                    help="allowed wall-time ratio vs baseline (default 4.0; "
                         "generous on purpose — CI runners are noisy)")
    ap.add_argument("--retune", action="store_true",
                    help="re-run the KernelSpec autotuner for the host "
                         "platform before benchmarking (rewrites "
                         "--tune-baseline; see repro.kernels.autotune)")
    ap.add_argument("--tune-baseline", default="TUNE_baseline.json",
                    metavar="PATH",
                    help="tuning-cache file --retune rewrites (default "
                         "TUNE_baseline.json at the cwd/repo root)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    unknown = [n for n in args.names if n not in ALL]
    if unknown:
        ap.error(f"unknown benchmarks {unknown}; have {ALL}")
    names = args.names or ALL

    if args.retune:
        import os

        from repro.kernels import autotune
        print("===== retune =====")
        # point this process's spec resolution at the retuned file, so
        # the benchmarks below run against the fresh winners even when
        # --tune-baseline is a scratch copy (the CI drift check)
        os.environ[autotune.ENV_VAR] = str(args.tune_baseline)
        summary = autotune.retune(path=args.tune_baseline)
        print(f"===== retune done: {len(summary['entries'])} "
              f"{summary['platform']} entries "
              f"({summary['objective']}) =====")

    failures = []
    results = {}
    for name in names:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main(smoke=args.smoke)
            wall = time.time() - t0
            results[name] = {"status": "ok", "wall_s": round(wall, 2)}
            print(f"===== {name} done in {wall:.1f}s =====")
        except Exception as e:  # keep the harness going, fail at exit
            failures.append(name)
            results[name] = {"status": "failed",
                             "wall_s": round(time.time() - t0, 2),
                             "error": f"{type(e).__name__}: {e}"}
            traceback.print_exc()
            print(f"===== {name} FAILED: {type(e).__name__}: {e} =====")

    if args.json:
        payload = {"smoke": bool(args.smoke), "benchmarks": results}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {args.json}")

    rc = 0
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        rc = 1
    if args.baseline:
        with open(args.baseline) as f:
            base_doc = json.load(f)
        baseline = base_doc.get("benchmarks", {})
        problems = compare_to_baseline(results, baseline, args.tolerance)
        if problems:
            print("\nBENCHMARK REGRESSIONS vs baseline:")
            for p in problems:
                print(f"  - {p}")
            rc = 1
        else:
            print(f"\nbenchmark gate OK vs {args.baseline} "
                  f"(tolerance {args.tolerance}x)")
        # A benchmark added in this PR has no baseline row, so
        # compare_to_baseline skipped it above — and, left alone, would
        # keep skipping it forever.  Fold new ok modules into the
        # artifact now so the *second* run gates them.  Failed modules
        # are never recorded, and neither is a mode mismatch: smoke and
        # full walls differ by orders of magnitude, so a smoke run must
        # not seed rows a full-mode gate would then compare against.
        new_ok = sorted(n for n, r in results.items()
                        if n not in baseline and r.get("status") == "ok")
        if new_ok and bool(base_doc.get("smoke")) == bool(args.smoke):
            for n in new_ok:
                baseline[n] = {"status": "ok",
                               "wall_s": results[n]["wall_s"]}
                print(f"recorded new benchmark {n!r} into {args.baseline} "
                      f"(wall {results[n]['wall_s']:.1f}s)")
            base_doc["benchmarks"] = baseline
            with open(args.baseline, "w") as f:
                json.dump(base_doc, f, indent=2, sort_keys=True)
                f.write("\n")
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
