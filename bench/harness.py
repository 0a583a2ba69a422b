"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` is everything ``bench/run.py`` does after it has found the
chips, so a test can drive a whole run on the CPU.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

from bench import check, drive, spec, traffic, xplane
from bench import weights as W
from bench.peaks import Peak

__all__ = ["Context", "serve", "run_cell", "TRACE_SECONDS",
           "OUT_DIR"]

#: a --trace 1 run traces the last this many seconds of its window
TRACE_SECONDS = 6.0
OUT_DIR = spec.ROOT / ".bench_out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a run served and knew of the engine: what a metric reader
    reads.  ``serve`` fills the first part, ``run_cell`` the trace."""

    window: drive.Window
    setup_s: float
    sizes: W.Sizes
    dtype: str                    # the configuration's served dtype
    n_slots: int
    capacity: int                 # KV positions of one slot's page table
    prefill_chunk: int
    device: dict
    peak: Optional[Peak] = None
    trace: Optional[xplane.Trace] = None
    traced_steps: list = field(default_factory=list)


def build_engine(cell: spec.Cell, params):
    from repro.models.layers import ParallelCtx
    from repro.models.model import Model
    from repro.serve.scheduler import ContinuousServeEngine

    model = Model(spec.model_config(cell.config))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("the benchmark's weights do not match the "
                         f"program's parameter tree: {got} != {want}")
    e = cell.traffic["engine"]
    return ContinuousServeEngine(
        model, params, ParallelCtx(), n_slots=e["n_slots"],
        max_len=e["max_len"], page_size=e["page_size"], n_pages=e["n_pages"],
        prefill_chunk=e["prefill_chunk"],
        backend=cell.config["arithmetic"]["backend"])


def warm_open_loop(engine) -> None:
    """Compile the prefill chunk and the decode tick before an open loop."""
    engine.submit(list(range(1, engine.prefill_chunk + 1)), max_new=2)
    while engine.pending:
        engine.step()


def _lateness(served, t_open):
    late = [s.submitted - s.due for s in served if s.due >= t_open]
    if late:
        log(f"generator: {len(late)} requests submitted, late by p50 "
            f"{1e3 * float(np.median(late)):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms after they were due")


def serve(cell: spec.Cell, seed: int, seconds: float, t_process: float,
          device_info: Callable[[], dict], trace_dir: Optional[Path] = None,
          clock: Callable[[], float] = time.perf_counter,
          trace_seconds: float = TRACE_SECONDS) -> Context:
    """Set up ``cell`` from ``seed``, drive it for ``seconds`` (tracing
    the last ``trace_seconds`` into ``trace_dir`` when given), and free
    the program's state."""
    cfg, mix = cell.config, cell.traffic
    sizes = W.Sizes.of(cfg)
    params = W.make_params(sizes, seed, cfg["param_dtype"])
    jax.block_until_ready(params)
    engine = build_engine(cell, params)
    reqs = traffic.requests(mix, seed, sizes.vocab)
    clients = None
    if mix["loop"] == "closed":
        clients = drive.fill(engine, reqs, mix["clients"], clock)
    else:
        warm_open_loop(engine)
    jax.block_until_ready(engine.cache)
    compiled = dict(engine.trace_counts)
    start_trace = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        start_trace = lambda: jax.profiler.start_trace(str(trace_dir))  # noqa: E731
    setup_s = clock() - t_process
    log(f"setup: {setup_s:.3f} s, programs compiled {compiled}")
    w = drive.run_window(engine, reqs, mix["loop"], seconds, clients,
                         trace_at=max(0.0, seconds - trace_seconds),
                         start_trace=start_trace, clock=clock)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    if engine.trace_counts != compiled:
        raise RuntimeError(f"compiled inside the window: {compiled} -> "
                           f"{engine.trace_counts}")
    out = Context(w, setup_s, sizes, cfg["dtype"], engine.n_slots,
                  engine.geom.slot_capacity, engine.prefill_chunk,
                  device_info())
    if mix["loop"] == "open":
        _lateness(w.served, w.t_open)
    log(f"window: {w.t_close - w.t_open:.3f} s, {len(w.steps)} steps, "
        f"{sum(len(s.times) for s in w.served)} tokens served in all")
    del engine, params, clients
    gc.collect()
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, peak: Peak,
             device_info: Callable[[], dict],
             clock: Callable[[], float] = time.perf_counter,
             trace_seconds: float = TRACE_SECONDS,
             out_dir: Path = OUT_DIR) -> dict:
    """Run ``cell`` once and return the result line's object."""
    cfg = cell.config
    sizes = W.Sizes.of(cfg)
    trace_dir = out_dir / "trace" if trace else None
    sv = serve(cell, seed, seconds, t_process, device_info, trace_dir,
               clock, trace_seconds)
    w, device = sv.window, sv.device

    seqs = check.sample(w.served, seed)
    ref = spec.load_module("references", cfg["reference"])
    if seqs:
        nums = check.numbers(check.Readings(
            ref, sizes, seed, cfg["param_dtype"], seqs).served_gaps())
    else:
        nums = {"gap_max": float("inf"), "gap_mean": float("inf")}
    correct, compared = check.judge(nums, cfg["limits"])
    for name in sorted(set(nums) - set(compared)):
        log(f"read, not compared: {name} {nums[name]!r}")

    tr, traced = None, []
    if trace:
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        tr = xplane.reduce_file(files[-1])
        traced = [s for s in w.steps if s.start >= w.trace_start]
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
    ctx = replace(sv, peak=peak, trace=tr, traced_steps=traced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = sum(1 for s in w.served if s.due <= w.t_close)
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if tr is not None:
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.top_idle()}
    log(f"check: {sum(len(s) for _, s in seqs)} served tokens of "
        f"{len(seqs)} requests against the reference")
    for name, c in compared.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    out["check"] = compared
    return out
