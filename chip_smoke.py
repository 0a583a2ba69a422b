#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU.

    python3 chip_smoke.py             # serve minicpm_2b, one chip
    python3 chip_smoke.py --chips 4   # sharded RAPID training, four chips

One chip: minicpm_2b at its published widths (40 layers, d_model 2304,
36 heads of 64, d_ff 5760, vocab 122753) with random weights from
``SEED`` is served by the continuous-batching engine
(``repro.serve.scheduler.ContinuousServeEngine``) twice: the exact arm
and the RAPID arm, every registry site pinned to the compiled ``pallas``
kernels.  Before that, the RAPID matmul and rms kernels are checked
against the jnp formulation at those widths (``KERNEL_TOL``).  Parameters are bfloat16: float32 parameters leave the exact
arm's step without room on a 16 GB chip.  Checks: every request gets its
tokens, the KV pool and the prefill logits are finite, both decode steps
hold compiled Mosaic kernels (``tpu_custom_call``), and the RAPID arm's
prefill logits agree with the same arm on the ``jnp`` backend within
``LOGIT_TOL``.

Four chips: minicpm_2b at full width, depth cut to 2 layers so one chip
holds float32 parameters plus Adam state, takes 3 RAPID-arm train steps
on one device and then on a 2x2 (data, model) mesh under the
``repro.parallel.sharding`` rules; the losses must agree within
``LOSS_TOL``.

The script refuses to run without a TPU: it exits non-zero and prints no
result.  Lines before the last are smoke readings, not metrics.  The
last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "minicpm_2b"
#: weights, prompts and training batches are drawn from this seed
SEED = 0
#: prompt lengths of the served requests (one more request than slots,
#: so admission waits for a slot to recycle)
PROMPT_LENS = (23, 61, 128, 200, 300)
MAX_NEW = 16
N_SLOTS, MAX_LEN, PAGE_SIZE, PREFILL_CHUNK = 4, 512, 16, 64
#: prompt of the RAPID pallas-vs-jnp prefill comparison
CHECK_LEN = 32
#: ||pallas - jnp|| / ||jnp|| over the RAPID arm's prefill logits.  The
#: kernel accumulates each matmul one product at a time and the jnp scan
#: in chunks of 64; the last-bit differences move some operands across a
#: RAPID coefficient boundary, and 40 layers amplify them.  On the CPU at
#: these widths, the two accumulation orders (jnp scan, chunk 1 vs 64)
#: differ by 0.114 after 40 layers, while the RAPID and exact arms differ
#: by 0.59.  On one TPU v5e the pallas and jnp arms differ by 0.288 and
#: the RAPID and exact arms by 0.367, so at this depth the comparison
#: catches gross faults only; the kernels themselves are held to
#: KERNEL_TOL in kernel_phase.
LOGIT_TOL = 0.3
#: max |pallas - jnp| / max |jnp| of one kernel call at real width
KERNEL_TOL = 1e-5
#: the mesh resolves every RAPID site to the jnp formulation, whose XLA
#: gather ran at about 0.17e9 approximate products/s on one v5e in the
#: serve phase's reference, so the step is kept to 32 tokens
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 2, 3, 2, 16
#: max |loss(mesh) - loss(one device)| relative to the one-device loss
LOSS_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    """||got - want|| / ||want|| over a logit vector."""
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def kernel_phase(seed: int) -> None:
    """The RAPID kernels against the jnp formulation at minicpm widths.

    The matmul kernel accumulates in the order of the chunk=1 jnp scan,
    and the fused rms divider evaluates the jnp expression, so both
    agree to the last bits; a wrong coefficient lookup is off by about
    1e-2.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core import backend as be

    cfg = get_config(ARCH)
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (8, cfg.d_model), jnp.float32)
    w = jax.random.normal(kw, (cfg.d_model, cfg.d_ff), jnp.float32)
    pairs = {
        "log_matmul": [jax.jit(lambda x, w, b=b, c=c: be.matmul(
            x, w, "rapid10", backend=b, chunk=c))(x, w)
            for b, c in (("pallas", 64), ("jnp", 1))],
        "fused_rms_div": [jax.jit(lambda x, b=b: be.rms_div(
            x, cfg.norm_eps, "rapid9", backend=b))(x)
            for b in ("pallas", "jnp")],
    }
    for name, (got, want) in pairs.items():
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        log(f"[kernel] {name} {tuple(got.shape)} pallas vs jnp: max rel "
            f"diff {err:.3e} (tolerance {KERNEL_TOL:.0e})")
        check(err <= KERNEL_TOL, f"{name}: pallas vs jnp differ by {err}")


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import EXACT, RAPID, BACKEND_SITES, get_config
    from repro.core import backend as be
    from repro.models.layers import ParallelCtx
    from repro.models.model import Model
    from repro.serve.scheduler import ContinuousServeEngine

    cfg = get_config(ARCH).with_(param_dtype="bfloat16")
    log(f"[serve] {ARCH}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} params={cfg.param_dtype} "
        f"activations={cfg.dtype}")
    t0 = time.perf_counter()
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"[serve] init {time.perf_counter() - t0:.1f} s, "
        f"device peak {peak_gb(jax.devices()[0])}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in PROMPT_LENS]
    ctx = ParallelCtx()

    def paged_step(model):
        return jax.jit(lambda p, c, t, pt, off, nv: model.decode_paged(
            p, t, c, pt, off, nv, ctx))

    def prefill_logits(model, prompt):
        """decode_paged over one prompt chunk on a fresh pool — the
        engine's prefill step — returning the last token's logits."""
        pages = -(-len(prompt) // PAGE_SIZE)
        cache = model.init_paged_cache(pages + 1, PAGE_SIZE)
        table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
        logits, _ = paged_step(model)(
            params, cache, jnp.asarray([prompt], jnp.int32), table,
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), len(prompt), jnp.int32))
        return np.asarray(logits[0], np.float32)

    check_prompt = prompts[0][:CHECK_LEN]
    logits = {}
    for arm, approx in (("exact", EXACT), ("rapid", RAPID)):
        model = Model(cfg.with_(approx=approx))
        engine = ContinuousServeEngine(
            model, params, ctx, n_slots=N_SLOTS, max_len=MAX_LEN,
            page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
            backend="pallas")
        pinned = engine.model.cfg.approx
        sites = {s: pinned.backend_for(s)
                 for s in ("default",) + BACKEND_SITES}
        check(set(sites.values()) == {"pallas"},
              f"{arm}: sites not pinned to pallas: {sites}")
        check(be.resolve_backend_name(pinned.backend_for("mlp")) == "pallas",
              f"{arm}: mlp site resolves elsewhere")
        t0 = time.perf_counter()
        outs = engine.generate(prompts, max_new=MAX_NEW)
        wall = time.perf_counter() - t0
        for n, out in zip(PROMPT_LENS, outs):
            check(len(out) == MAX_NEW,
                  f"{arm}: prompt of {n} got {len(out)} tokens")
            check(all(0 <= t < cfg.padded_vocab for t in out),
                  f"{arm}: token id out of range: {out}")
        check(engine.alloc.n_free == engine.geom.usable_pages,
              f"{arm}: pages leaked")
        finite = all(bool(jnp.isfinite(a).all())
                     for a in jax.tree.leaves(engine.cache))
        check(finite, f"{arm}: non-finite values in the KV pool")
        n_tok = sum(len(o) for o in outs)
        log(f"[serve:{arm}] {len(prompts)} requests, prompts {PROMPT_LENS}, "
            f"{n_tok} tokens in {wall:.1f} s wall (compiles included), "
            f"traces {engine.trace_counts}, device peak "
            f"{peak_gb(jax.devices()[0])}")
        log(f"[serve:{arm}] first tokens {[o[:4] for o in outs]}")

        # the compiled decode step holds Mosaic kernels, not interpreted ones
        tokens = jnp.zeros((N_SLOTS, 1), jnp.int32)
        offsets = jnp.zeros((N_SLOTS,), jnp.int32)
        hlo = paged_step(engine.model).lower(
            params, engine.cache, tokens, jnp.asarray(engine.page_table),
            offsets, offsets).compile().as_text()
        n_kernels = hlo.count("tpu_custom_call")
        check(n_kernels > 0, f"{arm}: decode step has no tpu_custom_call")
        log(f"[serve:{arm}] decode step HLO: {n_kernels} tpu_custom_call "
            "sites")

        t0 = time.perf_counter()
        logits[arm] = prefill_logits(engine.model, check_prompt)
        check(bool(np.isfinite(logits[arm]).all()),
              f"{arm}: non-finite prefill logits")
        log(f"[serve:{arm}] prefill logits ({CHECK_LEN} tokens, pallas) "
            f"{time.perf_counter() - t0:.1f} s incl. compile")
        del engine

    # the RAPID arm again on the jnp backend: same arithmetic, other code
    ref_model = Model(cfg.with_(approx=be.pin_backends(RAPID, "jnp")))
    t0 = time.perf_counter()
    want = prefill_logits(ref_model, check_prompt)
    log(f"[serve:rapid] prefill logits ({CHECK_LEN} tokens, jnp) "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check(bool(np.isfinite(want).all()), "rapid: non-finite jnp logits")
    err = rel_err(logits["rapid"], want)
    log(f"[serve:rapid] pallas vs jnp prefill logits: rms rel err "
        f"{err:.3e} (tolerance {LOGIT_TOL}); rapid vs exact arm "
        f"{rel_err(logits['exact'], want):.3e}; argmax agree "
        f"{int(np.argmax(logits['rapid'])) == int(np.argmax(want))}")
    check(err <= LOGIT_TOL,
          f"rapid: pallas vs jnp logits differ by {err:.3e}")


def train_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs.base import RAPID, get_config
    from repro.core import backend as be
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_local_mesh
    from repro.models.layers import ParallelCtx
    from repro.models.model import Model
    from repro.parallel.sharding import make_rules, named_sharding_tree
    from repro.train.optimizer import OptConfig
    from repro.train.trainstep import make_train_step

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg = get_config(ARCH).with_(n_layers=TRAIN_LAYERS, approx=RAPID)
    model = Model(cfg)
    log(f"[train] {ARCH}: layers={cfg.n_layers} (cut from 40) "
        f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab_size}, "
        f"RAPID arm, batch {TRAIN_BATCH} x seq {TRAIN_SEQ}")
    # on a multi-device TPU every site pins to the context-dependent
    # AUTO_HW entry, which resolves to jnp outside shard_map bodies
    site = be.pin_backends(cfg.approx).backend_for("mlp")
    log(f"[train] RAPID sites pin to {site!r}, resolving to "
        f"{be.resolve_backend_name(site)!r} under the partitioner")
    check(site == be.AUTO_HW, f"expected {be.AUTO_HW!r} sites, got {site!r}")
    oc = OptConfig(name=cfg.optimizer, schedule="constant",
                   total_steps=TRAIN_STEPS)
    src = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=seed)

    def run(mesh):
        if mesh is None:
            ctx = ParallelCtx()
        else:
            ctx = ParallelCtx(mesh, make_rules(cfg))
        init_opt, step = make_train_step(model, oc, ctx)
        params = model.init(jax.random.PRNGKey(seed))
        opt_state = init_opt(params)
        if mesh is not None:
            params = jax.device_put(params, named_sharding_tree(
                mesh, model.pspecs(ctx.rules)))
        step_fn = jax.jit(step, donate_argnums=(0, 1))
        losses = []
        t0 = time.perf_counter()
        for i in range(TRAIN_STEPS):
            params, opt_state, metrics = step_fn(
                params, opt_state, src.batch_at(i), jax.numpy.int32(i))
            losses.append(float(metrics["loss"]))
        wall = time.perf_counter() - t0
        return losses, wall

    one, wall1 = run(None)
    log(f"[train] one device: losses {one} ({wall1:.1f} s wall incl. "
        f"compile), device peak {peak_gb(jax.devices()[0])}")
    mesh = make_local_mesh(data=2, model=2)
    sharded, wall4 = run(mesh)
    log(f"[train] 2x2 mesh {dict(mesh.shape)}: losses {sharded} "
        f"({wall4:.1f} s wall incl. compile)")
    check(all(np.isfinite(one + sharded)), "non-finite training loss")
    err = max(abs(a - b) / abs(a) for a, b in zip(one, sharded))
    log(f"[train] mesh vs one device: max rel loss diff {err:.3e} "
        f"(tolerance {LOSS_TOL:.0e})")
    check(err <= LOSS_TOL, f"losses differ by {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve minicpm_2b; 4: sharded training only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    log(f"[smoke] devices {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        train_phase(SEED)
    else:
        kernel_phase(SEED)
        serve_phase(SEED)
    log(f"[smoke] total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
