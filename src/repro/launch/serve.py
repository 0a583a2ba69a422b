"""Serving launcher: batched generation with optional RAPID arithmetic.

``python -m repro.launch.serve --arch yi_6b --reduced --approx``

``--continuous`` swaps the fixed-slot lockstep engine for the
continuous-batching one (paged KV, chunked prefill, slot recycling,
per-request streaming); greedy outputs match per request.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ARCH_IDS, RAPID, get_config
from repro.launch.backend_args import add_backend_args, apply_backend_args
from repro.models.layers import ParallelCtx
from repro.models.model import Model
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--approx", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: paged KV + chunked prefill "
                         "+ slot recycling (repro.serve.scheduler)")
    add_backend_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.approx:
        cfg = cfg.with_(approx=RAPID)
    cfg = apply_backend_args(cfg, args)
    assert cfg.family not in ("encdec", "vlm"), \
        "serve demo targets pure-text archs (frontend stubs need batches)"

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.continuous:
        engine = ContinuousServeEngine(
            model, params, ParallelCtx(), n_slots=args.batch,
            max_len=args.cache, temperature=args.temperature)
    else:
        engine = ServeEngine(model, params, ParallelCtx(),
                             cache_n=args.cache,
                             temperature=args.temperature)
    prompts = [[1 + (i + j) % 32 for j in range(5 + i)]
               for i in range(args.batch)]
    t0 = time.time()
    out = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in out)
    for i, o in enumerate(out):
        print(f"req{i}: {o}")
    mode = "continuous" if args.continuous else "fixed-slot"
    print(f"{n_tok} tokens in {dt:.2f}s ({n_tok/dt:.1f} tok/s, {mode}, "
          f"approx={'RAPID' if args.approx else 'exact'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
