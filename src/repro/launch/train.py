"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs real training (synthetic or bin corpus) on whatever devices exist.
On the CPU container this trains reduced configs; on a real pod the same
entry point builds the production mesh and shards per parallel/sharding
rules.  ``--approx`` turns on the paper's RAPID arithmetic end to end.
"""
from __future__ import annotations

import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ARCH_IDS, RAPID, get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.backend_args import add_backend_args, apply_backend_args
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.layers import ParallelCtx
from repro.models.model import Model
from repro.parallel.sharding import make_rules, named_sharding_tree
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import OptConfig
from repro.train.trainstep import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke scale)")
    ap.add_argument("--approx", action="store_true",
                    help="enable RAPID approximate mul/div")
    add_backend_args(ap)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.approx:
        cfg = cfg.with_(approx=RAPID)
    cfg = apply_backend_args(cfg, args)

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = make_rules(cfg, multi_pod=args.multi_pod)
    elif len(jax.devices()) > 1:
        mesh = make_local_mesh(data=len(jax.devices()))
        rules = make_rules(cfg)
    else:
        mesh, rules = None, {}
    ctx = ParallelCtx(mesh, rules) if mesh is not None else ParallelCtx()

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    oc = OptConfig(name=cfg.optimizer, lr=args.lr,
                   schedule=cfg.lr_schedule, total_steps=args.steps)
    init_opt, train_step = make_train_step(model, oc, ctx,
                                           microbatches=args.microbatches)
    opt_state = init_opt(params)

    if mesh is not None:
        pspecs = named_sharding_tree(mesh, model.pspecs(rules))
        params = jax.device_put(params, pspecs)

    src = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    step_fn = jax.jit(train_step, donate_argnums=(0, 1))
    lc = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                    ckpt_dir=args.ckpt_dir, log_every=10)
    state = train_loop(step_fn, params, opt_state, src, lc)
    print(f"final loss: {state.losses[-1]:.4f} "
          f"(first {state.losses[0]:.4f}) over {state.step} steps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
