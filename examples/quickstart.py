"""Quickstart: RAPID approximate arithmetic in 30 lines.

Run: PYTHONPATH=src python examples/quickstart.py

CI (.github/workflows/ci.yml) gates every PR — badge:
https://github.com/<org>/<repo>/actions/workflows/ci.yml/badge.svg

  job          what it proves
  -----------  ------------------------------------------------------
  lint         ruff correctness rules (ruff.toml) + compileall
  tier1        full suite on jax 0.9.0, real-width kernel compiles
               for a described TPU v5e included
  parity       jnp oracle vs pallas-interpret bit-exactness sweep
  multidevice  EP/TP shard_map tests on 8 fake XLA devices — the
               kernels really run on local shards
  bench-gate   benchmarks.run --retune --smoke + regression diff
               against the committed BENCH_baseline.json, and a drift
               check that the retune reproduced TUNE_baseline.json
               byte-for-byte (JSON uploaded as a PR artifact);
               serve_load additionally asserts continuous batching
               beats fixed-slot tokens/s at equal KV memory
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.float_approx import approx_div, approx_mul
from repro.core.ops import qmatmul

# --- elementwise: the paper's multiplier/divider on floats -------------
a = jnp.asarray([3.0, 58.0, -7.5], jnp.float32)
b = jnp.asarray([4.0, 18.0, 2.5], jnp.float32)
print("exact   mul:", np.asarray(a * b))
print("mitchell mul:", np.asarray(approx_mul(a, b, "mitchell")))
print("rapid10  mul:", np.asarray(approx_mul(a, b, "rapid10")))
print("rapid9   div:", np.asarray(approx_div(a, b, "rapid9")),
      "(exact:", np.asarray(a / b), ")")

# --- matmul through the logarithmic multiplier --------------------------
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
w = jnp.asarray(rng.normal(size=(128, 32)), jnp.float32)
exact = x @ w
approx = qmatmul(x, w, "rapid10")
rel = float(jnp.abs(approx - exact).mean() / jnp.abs(exact).mean())
print(f"\nmatmul rel-L1 error (rapid10): {rel:.4%}  "
      "(near-zero bias -> errors cancel in dot products)")

# --- it differentiates: straight-through gradients ----------------------
g = jax.grad(lambda x: qmatmul(x, w, "rapid10").sum())(x)
print("grad shape:", g.shape, "finite:", bool(jnp.isfinite(g).all()))

# --- backend selection & epilogues --------------------------------------
# Every approximate op routes through the backend registry
# (repro.core.backend): "jnp" (partitioner-visible oracle), "pallas"
# (TPU kernels) or "pallas-interpret" (kernels on CPU, for parity
# checks).  Selection precedence at any call site:
#   backend= argument > $RAPID_BACKEND env var > process default
#   (backend.set_default_backend) > hardware autodetect.
# Model configs carry a *per-site* map instead of one global name, so a
# single model can mix execution paths:
#   cfg.with_site_backends({"mlp": "pallas", "logits": "jnp"})
# (sites: mlp / attn_proj / logits / norm / softmax / default; the
# launchers expose the same via --backend / --site-backend SITE=NAME).
from repro.core.backend import Epilogue, resolve_backend_name

print("\nresolved backend:", resolve_backend_name(None))

# The epilogue menu fuses a whole block tail into the matmul's output
# tile: norm(activation(x @ w + bias) + residual) in one VMEM-resident
# pass, with the normalization divide running through the RAPID divider.
bias = jnp.zeros((32,), jnp.float32)
residual = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
tail, res_stream = qmatmul(
    x, w, "rapid10",
    bias=bias,
    residual=residual,
    epilogue=Epilogue(activation="silu", norm="rms", div_scheme="rapid9",
                      keep_prenorm=True),  # also emit the pre-norm value
)
print("fused block tail:", tail.shape, "residual stream:", res_stream.shape)

# --- KernelSpec: one spec object, and the pipeline-depth knob -----------
# Every Pallas kernel family (log_matmul, the fused_div variants,
# rapid_mul / rapid_div elementwise, flash-decode attention) accepts the
# same spec object instead of per-family positional tuples.
#
# Migration notes (removed APIs):
#   * `log_matmul(..., blocks=(bm, bn, bk))` and tuple specs are gone —
#     passing `blocks=` raises TypeError; write
#     `spec=KernelSpec(bm=..., bn=..., bk=...)` instead.
#   * The deprecated `ApproxConfig.backend` / `.matmul_backend` read
#     aliases are gone — reads raise AttributeError; use
#     `cfg.backend_for(site)` (lint rule RPD009 hard-errors on any
#     source site, and is not baselineable).
from repro.kernels.log_matmul.ops import log_matmul
from repro.kernels.spec import KernelSpec, PipelineSpec

spec = KernelSpec(bm=8, bn=128, bk=128,            # tile geometry (None: auto)
                  pipeline=PipelineSpec(depth=2))  # in-flight copy stages
# depth=1 lowers the classic grid formulation; depth>=2 emits a manual
# async-copy pipeline (HBM-resident operands, `depth` VMEM tile buffers
# rotating behind DMA semaphores).  The knob is schedule-only — every
# depth is bit-exact against the jnp oracle (tests/test_parity_sweep.py)
# — and the kernel auditor re-checks the VMEM working set *at the
# requested depth* (PIPELINE_REPORT.json: pipeline_depth/scratch_bytes).
y1 = log_matmul(x, w, "rapid10", interpret=True,
                spec=KernelSpec(pipeline=PipelineSpec(depth=1)))
y2 = log_matmul(x, w, "rapid10", interpret=True, spec=spec)
print("\ndepth 1 vs depth 2 bit-identical:", bool((y1 == y2).all()))
# benchmarks/roofline.py times the depth-1 vs depth-2 schedules and the
# fused flash-attention kernel vs the separate-passes path on a shared
# arithmetic-intensity axis.

# --- autotuning kernel specs --------------------------------------------
# Fields you leave as None are filled by resolve_spec (kernels/spec.py),
# the single choke point every wrapper and core/backend.py dispatcher
# goes through.  Per-field precedence:
#
#   explicit KernelSpec field  >  tuning-cache hit  >  heuristic
#
# The tuning cache is TUNE_baseline.json at the repo root (override with
# $RAPID_TUNE_CACHE): committed, versioned winners produced by the
# autotuner in repro.kernels.autotune, which times every budget-legal
# (bm, bn, bk, depth) candidate per kernel family on the actual device
# — real wall time on TPU, a deterministic static cost model elsewhere,
# so CI can regenerate the file byte-for-byte.  Entries are keyed by
# (family, bucketed shape class, scheme, epilogue kind, platform), so
# nearby dispatch shapes that tile identically share a winner.  Every
# knob the tuner searches is schedule-only: a cached spec stays
# bit-exact against the jnp oracle (tests/test_autotune.py proves it
# for every committed entry).
#
#   PYTHONPATH=src python -m benchmarks.run --retune   # re-search + save
#   PYTHONPATH=src python -m repro.kernels.autotune --list  # inspect
#
# Pin a spec manually when you want to override the cache at one call
# site — an explicit field always wins:
from repro.kernels.spec import resolve_spec

auto = resolve_spec("log_matmul", (512, 512, 512), scheme="rapid10")
pinned = resolve_spec("log_matmul", (512, 512, 512),
                      KernelSpec(bm=64), scheme="rapid10")
print("tuned 512^3 spec:", (auto.bm, auto.bn, auto.bk, auto.depth),
      "| pinned bm wins:", pinned.bm)

# --- running sharded with the pallas backend ----------------------------
# The pallas kernels are *per-device*, so on a multi-device process the
# hardware autodetect answers per call site: pjit-visible (global-view)
# matmuls resolve to the partitionable "jnp" formulation, while code
# traced inside a `repro.compat.shard_map` body — the EP/TP expert
# compute in models/moe.py, the flash-decode combine — sees per-shard
# shapes and legally runs the kernels on each local shard.  Engines and
# train steps pin per-site backends at build (core.backend.pin_backends);
# on a multi-device TPU an auto site pins as the AUTO_HW sentinel, which
# re-resolves only from the memoized hardware probe + the trace context,
# so the same pinned config routes jnp under pjit and pallas under
# shard_map, and post-build env changes can't flip compiled kernels.
#
#   from repro.launch.mesh import make_local_mesh
#   from repro.parallel.sharding import make_rules
#   mesh = make_local_mesh(data=2, model=4)           # EP over "model"
#   ctx = ParallelCtx(mesh, make_rules(cfg))
#   out = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
#
# Locality is detected from the axis environment; shard_map bodies
# must run under jit — the eager shard_map interpreter has no pallas
# rule.  CI's `multidevice` job forces an
# 8-device CPU host (XLA_FLAGS=--xla_force_host_platform_device_count=8)
# and checks the sharded EP/TP forward bit-exact against the
# single-device oracle (tests/test_shardmap_parity.py).
from repro import compat

print("\nin shard_map?", compat.in_shard_map(),
      "| axis env:", compat.axis_env_sizes())

# --- continuous-batching serve ------------------------------------------
# Two serving engines live in repro.serve:
#   * ServeEngine (engine.py): fixed-slot lockstep — one dense KV cache
#     of cache_n tokens per slot, the whole batch prefills together and
#     decodes until the *longest* request finishes.
#   * ContinuousServeEngine (scheduler.py): a request queue with
#     per-slot admission, KV in a block-paged pool (paged_kv.py) so
#     memory scales with live tokens, chunked prefill interleaved with
#     decode ticks, slot recycling the moment a request completes, and
#     per-request streaming.  Decode runs one compiled step with fixed
#     [n_slots, 1] shapes and a dynamic occupancy mask, so mid-flight
#     admissions/evictions never retrace.  Greedy outputs are
#     bit-identical to the fixed-slot engine per request.
# benchmarks/serve_load.py races the two under a Poisson arrival trace
# at equal peak KV memory; CI asserts continuous wins tokens/s.
from repro.configs.base import get_config
from repro.models.model import Model
from repro.serve.scheduler import ContinuousServeEngine

cfg = get_config("minicpm_2b").reduced().with_(dtype="float32")
model = Model(cfg)
eng = ContinuousServeEngine(model, model.init(jax.random.PRNGKey(0)),
                            n_slots=2, max_len=32, page_size=8,
                            prefill_chunk=8)
print("\nstreaming 3 requests through 2 slots:")
for ev in eng.stream([[5, 6, 7], [8, 9], [10, 11, 12, 13]], max_new=4):
    print(f"  rid={ev.rid} token={ev.token} done={ev.done}")
print("decode compiled", eng.trace_counts["decode"], "time(s); pages free:",
      eng.alloc.n_free, "/", eng.geom.usable_pages)

# --- auditing approximate-dispatch coverage ------------------------------
# The paper's end-to-end numbers assume the approximate units replaced
# *every* multiply/divide in the datapath — one raw `/` or `@` silently
# reverts a site to exact arithmetic.  repro.analysis proves coverage
# in three layers:
#
#   PYTHONPATH=src python -m repro.analysis.lint          # layer 1 (fast)
#   PYTHONPATH=src python -m repro.analysis.jaxpr_audit   # layer 2 (traces)
#   PYTHONPATH=src python -m repro.analysis.kernel_audit  # layer 3 (geometry)
#   PYTHONPATH=src python -m repro.analysis \
#       --baseline AUDIT_baseline.json --json report.json   # all + ratchet
#
# Layer 1 is an AST lint (rules RPD001-RPD004: raw matmul/div in
# models/apps/serve/train, LUT re-baking under jit, literal backend
# strings — `python -m repro.analysis.lint --list-rules`).  Layer 2
# traces every entry point (forward, decode, paged decode, trainstep,
# each app) and censuses the jaxpr: registry-dispatched ops are
# log-domain (bitcast + integer add + LUT gather) and so emit ZERO
# dot_general/div primitives — any such primitive whose innermost user
# frame is outside core/+kernels/ is an escape.  It also flags retrace
# hazards (unhashable config leaves) and duplicated baked-in LUTs.
#
# A genuinely-exact site is declared, with a mandatory reason (inline,
# or as the LAST comment line directly above the statement):
#
#     return acc / l[..., None]  # audit: exact — the exact-softmax arm
#
# --- kernel geometry audit (layer 3) -------------------------------------
# Layers 1+2 prove mul/div *route through* the registry; layer 3 proves
# the Pallas kernels the registry dispatches are geometrically legal
# before they touch a TPU.  A capture shim (repro.analysis.capture)
# monkeypatches pl.pallas_call under jax.disable_jit(), drives every
# registered kernel family (log_matmul, the fused_div variants,
# rapid_mul/rapid_div) through its public wrapper across the bench
# shape classes, and checks each captured grid/BlockSpec/index-map:
#
#   RPD005  per-grid-step VMEM working set (double-buffered) vs the
#           explicit budget in repro.kernels.budget — the same
#           constants resolve_spec's heuristics derive block sizes
#           from (and the autotuner's candidate filter enforces)
#   RPD006  lane (%128) / sublane (%8) alignment, blocks divide the
#           padded dims
#   RPD007  index maps surjective onto the block grid (a non-surjective
#           map silently drops elements) + every registry family has an
#           audited variant
#   RPD008  output tiles revisited across a grid dim must accumulate or
#           guard with pl.when(program_id == first/last), never on a
#           "parallel" dim
#
#   PYTHONPATH=src python -m repro.analysis.kernel_audit --list-variants
#   PYTHONPATH=src python -m repro.analysis.kernel_audit \
#       --report PIPELINE_REPORT.json
#
# The committed PIPELINE_REPORT.json records per-variant pipeline
# legality (grid, semantics, working set, revisit discipline,
# double_buffer_safe) — the contract the software-pipelining work must
# preserve.
#
# Everything else lives in AUDIT_baseline.json: a *ratchet* — new
# findings in any layer fail CI (the `audit` job, on both jax pins),
# known ones are allowlisted for burn-down, entries you fixed warn as
# stale (CI passes --fail-stale, so fix means shrink the baseline).
# After an intentional change, regenerate with
# `PYTHONPATH=src python -m repro.analysis --json AUDIT_baseline.json`
# (or drop fixed entries in place with `--baseline AUDIT_baseline.json
# --prune-stale`) and review the diff like code.  Operators get the
# same thing plus an optional compiled-HLO cross-check via
# `python -m repro.launch.audit --hlo dumped.txt`.
from repro.analysis import RULES
from repro.core.backend import dispatch_signature, registered_sites

print("\naudit rules:", ", ".join(sorted(RULES)))
print("dispatch sites:", registered_sites())
print("jnp backend div family ->", dispatch_signature("jnp")["div"])
