#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload minicpm_2b-exact.chat --seed 7 \\
        --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; ``bench/spec.py`` says where their files are.  Weights and
traffic come from ``--seed``.  Set-up (weights on the device, programs
compiled or loaded from the compile cache in ``.jax_cache`` at the
checkout's root, the warm-up) is timed as ``setup_s``; then the engine
is driven for ``--seconds``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, from a profiler
trace of the window's last seconds.  Once the window has closed, what it
served is checked against the plain reference (``bench/check.py``).

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.  The last line of standard output is
the result: ``{"correct", "attempted", "failed", "metrics", "device",
["breakdown"], "check"}``; the numbers compared are also the last lines
of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import boot, spec
    from bench.peaks import peak_for

    cell = spec.resolve_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    devices = boot.tpus(cell.chips, f"bench: cell {cell.name}")
    if devices is None:
        return 2
    peak = peak_for(devices[0].device_kind)
    from bench import harness

    def device_info():
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": max(p for p in peaks if p is not None)
                if any(p is not None for p in peaks) else None}

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, peak, device_info)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
