#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's and its control's.

    python3 bench/control.py --workload minicpm_2b-exact.batch \\
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 10

For each seed, in one process: the cell is served for ``--seconds`` as
in a benchmark run, and the numbers that ``bench/check.py`` compares are
read for what it served (the lower readings).  For each control seed the
control is read on the same prompts and served tokens: at every position
the token that the control puts first, and its gap under the reference
(the upper readings).  The control is the configuration file's
``control``, ``{"kind": "reference", "precision": "int8"}``: the
reference itself, computed in the precision below the configuration's
bfloat16.  Each control's numbers are judged against the configuration's
limits, as a run's are; a sound control reads ``"correct": false``.

The benchmark's own runs never run this.  Prints one JSON line per seed,
then the largest program reading and the smallest control reading of
each number.  Needs the chip, as ``bench/run.py`` does.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seeds, control_seeds, seconds, clock=time.perf_counter):
    """Yield one line per seed, then the summary line."""
    from bench import check, harness, spec
    from bench import weights as W

    cfg = cell.config
    sizes = W.Sizes.of(cfg)
    ref = spec.load_module("references", cfg["reference"])
    control = cfg["control"]
    if control["kind"] != "reference":
        raise ValueError(f"unknown control {control!r}")
    worst, best_control = {}, {}
    for seed in seeds:
        sv = harness.serve(cell, seed, seconds, clock(), lambda: {},
                           clock=clock)
        seqs = check.sample(sv.window.served, seed)
        rd = check.Readings(ref, sizes, seed, cfg["param_dtype"], seqs)
        nums = check.numbers(rd.served_gaps())
        line = {"seed": seed, "tokens": sum(len(s) for _, s in seqs),
                "program": nums,
                "program_correct": check.judge(nums, cfg["limits"])[0]}
        if seed in control_seeds:
            nums = check.numbers(rd.gaps(
                rd.reference_control_picks(control["precision"])))
            line["control"] = nums
            line["control_correct"] = check.judge(nums, cfg["limits"])[0]
        for k, v in line["program"].items():
            worst[k] = max(worst.get(k, v), v)
        for k, v in line.get("control", {}).items():
            best_control[k] = min(best_control.get(k, v), v)
        yield line
        del rd
    yield {"workload": cell.name, "control": control,
           "program_max": worst, "control_min": best_control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    sys.path.insert(0, str(ROOT))
    from bench import boot, spec

    cell = spec.resolve_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    if boot.tpus(cell.chips, f"control: cell {cell.name}") is None:
        return 2
    for line in readings(cell, seeds, control_seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
