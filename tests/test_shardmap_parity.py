"""shard_map-aware pallas backend: EP/TP/FSDP parity sweep.

The tentpole contract: with the "mlp" site on ``pallas-interpret``, the
sharded MoE forward (every dispatch mode: EP weight-gather, EP
all-to-all, token-gather) must equal the single-device oracle — *bit*
equal wherever each device contracts a contiguous K range (EP/TP; the
oracle is the jnp scan at ``chunk=1``, the kernel's slab accumulation
order), and within f32 reduction tolerance where FSDP splits the
contraction dim across ranks (token-gather regroups the K sum).

The in-process sweep needs a multi-device process; CI's ``multidevice``
job provides one via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the tests skip on fewer devices).  One subprocess smoke test stays
unmarked so the plain tier-1 run keeps end-to-end coverage.
"""
import os
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ApproxConfig, get_config
from repro.core import backend as be
from repro.core.ops import qmatmul, qmatmul_batched
from repro.models import moe
from repro.models.layers import ParallelCtx
from repro.models.moe import moe_ffn, moe_params
from repro.models.params import materialize
from repro.launch.mesh import make_local_mesh
from repro.parallel.sharding import make_rules

NDEV = 8

def sweep(fn):
    """The in-process sweep marks: ``multidevice`` (CI job selector),
    ``parity`` (bit-exactness gate family), and the 8-device skip."""
    for mark in (
        pytest.mark.skipif(
            jax.device_count() < NDEV,
            reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8"),
        pytest.mark.parity,
        pytest.mark.multidevice,
    ):
        fn = mark(fn)
    return fn


def _moe_cfg(backends):
    # float32 activations keep every cross-device combination (psum /
    # all_to_all scatter-adds of <= k=2 per-token contributions) an IEEE
    # commutative 2-term sum, so the sharded/local comparison is exact.
    return get_config("qwen3_moe_235b_a22b").reduced().with_(
        n_experts=4, experts_per_token=2, d_model=64, d_ff=64,
        vocab_size=512, n_layers=1, dtype="float32", capacity_factor=8.0,
        approx=ApproxConfig(mul_scheme="rapid10", backends=backends))


def _moe_inputs(cfg):
    params = materialize(moe_params(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8, cfg.d_model)),
                    jnp.float32)
    return params, x


def _jnp_oracle(cfg, params, x, monkeypatch):
    """Single-device jnp forward with chunk=1 (the kernel's slab
    accumulation order, see test_backend's bit-exactness notes)."""
    monkeypatch.setattr(moe, "qmatmul_batched",
                        partial(qmatmul_batched, chunk=1))
    out = moe_ffn(x, params, cfg.with_backend("jnp"), ParallelCtx())
    monkeypatch.undo()
    return out


# mesh shape x rule knobs covering the EP/TP dispatch modes: weight-
# gather (seq replicated), all-to-all (sequence sharded on the model
# axis), EP over a different data/model split, and batch-unsharded EP.
EP_TP_SPECS = [
    pytest.param((2, 4), dict(fsdp=False, seq_parallel=False),
                 id="ep-weight-gather-2x4"),
    pytest.param((2, 4), dict(fsdp=False, seq_parallel=True),
                 id="ep-a2a-seq-sharded-2x4"),
    pytest.param((4, 2), dict(fsdp=False, seq_parallel=False),
                 id="ep-weight-gather-4x2"),
    pytest.param((2, 4), dict(fsdp=False, seq_parallel=False,
                              shard_batch=False),
                 id="ep-batch-replicated-2x4"),
]


@sweep
@pytest.mark.parametrize("mesh_shape,rules_kw", EP_TP_SPECS)
def test_moe_sharded_kernel_bitexact_vs_jnp_oracle(mesh_shape, rules_kw,
                                                   monkeypatch):
    """EP/TP shard_map bodies running the pallas kernels on local shards
    reproduce the single-device jnp oracle bit for bit."""
    cfg = _moe_cfg({"mlp": "pallas-interpret", "default": "jnp"})
    params, x = _moe_inputs(cfg)
    oracle = _jnp_oracle(cfg, params, x, monkeypatch)

    mesh = make_local_mesh(*mesh_shape)
    ctx = ParallelCtx(mesh, make_rules(cfg, **rules_kw))
    out = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    np.testing.assert_array_equal(
        np.asarray(out).view(np.int32), np.asarray(oracle).view(np.int32))


@sweep
def test_moe_fsdp_token_gather_matches_oracle_to_f32_tolerance(monkeypatch):
    """The FSDP token-gather mode splits the down-projection's K dim
    across ranks, regrouping the f32 reduction — equal to the oracle to
    reduction tolerance, not bitwise."""
    cfg = _moe_cfg({"mlp": "pallas-interpret", "default": "jnp"})
    params, x = _moe_inputs(cfg)
    oracle = _jnp_oracle(cfg, params, x, monkeypatch)

    mesh = make_local_mesh(data=4, model=2)
    ctx = ParallelCtx(mesh, make_rules(cfg, fsdp=True, seq_parallel=False))
    out = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


@sweep
@pytest.mark.parametrize("m,n,k", [
    (16, 64, 64),    # local N = 16 over 4-way TP: heavy lane padding
    (8, 256, 128),   # local N = 64, K one block
    (32, 96, 40),    # unaligned everything
])
def test_tp_matmul_under_shard_map_bitexact(m, n, k):
    """Plain TP: rows sharded on data, columns on model — resolve_spec
    sees the *per-shard* shapes inside the body and the fused epilogue
    stays intact per shard."""
    from jax.sharding import PartitionSpec

    from repro.compat import shard_map

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)

    mesh = make_local_mesh(data=2, model=4)

    def body(xl, wl, bl):
        return qmatmul(xl, wl, "rapid10", backend="pallas-interpret",
                       bias=bl, activation="silu")

    out = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec("data", None), PartitionSpec(None, "model"),
                  PartitionSpec("model")),
        out_specs=PartitionSpec("data", "model"), check_vma=False,
    ))(x, w, b)
    ref = qmatmul(x, w, "rapid10", backend="pallas-interpret",
                  bias=b, activation="silu")
    np.testing.assert_array_equal(
        np.asarray(out).view(np.int32), np.asarray(ref).view(np.int32))


@sweep
def test_flash_decode_combine_runs_in_body_and_matches_unsharded():
    """The seq-sharded decode combine now divides inside the manual
    region (fused div kernel per shard); partial-stat psums regroup the
    row sums, so parity with the unsharded path is to f32 tolerance."""
    from repro.models.layers import decode_attention

    rng = np.random.default_rng(3)
    B, H, KV, hd, C = 1, 4, 2, 16, 64
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, C, KV, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, C, KV, hd)), jnp.float32)
    sp = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :], (B, C))
    acfg = ApproxConfig(div_scheme="rapid9",
                        backends={"softmax": "pallas-interpret",
                                  "default": "jnp"})

    ref = decode_attention(q, kc, vc, sp, C - 1, 0, acfg)

    mesh = make_local_mesh(data=1, model=NDEV)
    ctx = ParallelCtx(mesh, make_rules(None, shard_batch=False,
                                       shard_cache_seq=True))
    sharded = jax.jit(lambda q, kc, vc, sp: decode_attention(
        q, kc, vc, sp, C - 1, 0, acfg, ctx, seq_shard_axis="model"))
    out = sharded(q, kc, vc, sp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the divide really traces inside the shard_map body as the kernel
    jaxpr = str(jax.make_jaxpr(
        lambda q, kc, vc, sp: decode_attention(
            q, kc, vc, sp, C - 1, 0, acfg, ctx, seq_shard_axis="model"))(
        q, kc, vc, sp))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr


@sweep
def test_auto_hw_pin_routes_kernels_only_inside_manual_regions(monkeypatch):
    """On a (faked) multi-device TPU, an AUTO_HW-pinned config traces
    the pallas kernels inside the EP shard_map bodies while the same
    config's global-view (mesh-less) forward stays on the jnp oracle —
    the per-call-site routing the tentpole adds."""
    # patch the memoized probe, not jax.default_backend: the kernel
    # wrappers must keep seeing the real CPU platform (interpret mode)
    monkeypatch.setattr(be, "_device_probe", lambda: ("tpu", NDEV))
    monkeypatch.delenv(be.ENV_VAR, raising=False)
    cfg = _moe_cfg("auto")
    pinned = cfg.with_(approx=be.pin_backends(cfg.approx))
    assert pinned.approx.backend_for("mlp") == be.AUTO_HW
    params, x = _moe_inputs(pinned)

    mesh = make_local_mesh(data=2, model=4)
    ctx = ParallelCtx(mesh, make_rules(pinned, fsdp=False,
                                       seq_parallel=False))
    sharded = str(jax.make_jaxpr(
        lambda x, p: moe_ffn(x, p, pinned, ctx))(x, params))
    local = str(jax.make_jaxpr(
        lambda x, p: moe_ffn(x, p, pinned, ParallelCtx()))(x, params))
    assert "pallas_call" in sharded
    assert "pallas_call" not in local


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from functools import partial
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ApproxConfig, get_config
    from repro.models import moe
    from repro.models.layers import ParallelCtx
    from repro.models.moe import moe_ffn, moe_params
    from repro.models.params import materialize
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import make_rules
    from repro.core.ops import qmatmul_batched

    cfg = get_config("qwen3_moe_235b_a22b").reduced().with_(
        n_experts=4, experts_per_token=2, d_model=64, d_ff=64,
        vocab_size=512, n_layers=1, dtype="float32", capacity_factor=8.0,
        approx=ApproxConfig(mul_scheme="rapid10",
                            backends={"mlp": "pallas-interpret",
                                      "default": "jnp"}))
    params = materialize(moe_params(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8, 64)),
                    jnp.float32)

    moe.qmatmul_batched = partial(qmatmul_batched, chunk=1)
    oracle = moe_ffn(x, params, cfg.with_backend("jnp"), ParallelCtx())
    moe.qmatmul_batched = qmatmul_batched

    mesh = make_local_mesh(data=2, model=4)
    ctx = ParallelCtx(mesh, make_rules(cfg, fsdp=False, seq_parallel=False))
    out = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    assert np.array_equal(np.asarray(out).view(np.int32),
                          np.asarray(oracle).view(np.int32))
    print("OK")
""")


def test_moe_shard_map_kernel_parity_subprocess():
    """Tier-1 coverage on a single-device host: one EP spec, spawned
    with 8 fake XLA devices, sharded pallas-interpret vs the jnp
    oracle, bit-exact."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
