"""Real-width compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, and refuses what Mosaic
cannot lower (unaligned slices, gathers across vregs, VMEM overflow)
exactly as it would on the chip.  Shapes are minicpm_2b's (d_model
2304, d_ff 5760, 36 heads of 64) at the serve path's prefill-chunk and
decode row counts; block geometry is what ``resolve_spec`` picks for
the ``tpu`` platform, committed tuning-cache winners included.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core.backend import Epilogue
from repro.kernels.flash_attn.ops import flash_decode_attn
from repro.kernels.fused_div.ops import fused_rms_div, fused_softmax_div
from repro.kernels.log_matmul.ops import log_matmul
from repro.kernels.spec import resolve_spec

D_MODEL, D_FF, N_HEADS, HEAD_DIM = 2304, 5760, 36, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for one v5e chip; return the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n,k,epilogue,residual", [
    (64, D_FF, D_MODEL, Epilogue(activation="silu"), False),   # mlp w1
    (4, D_MODEL, D_FF, Epilogue(), True),                      # mlp w2
    (64, 3 * D_MODEL, D_MODEL, Epilogue(), False),             # fused qkv
    (4, D_MODEL, D_MODEL,                                      # norm tail
     Epilogue(norm="rms", div_scheme="rapid9", keep_prenorm=True), True),
], ids=["d_model-d_ff-silu", "d_ff-d_model-residual", "d_model-3d_model",
        "rms-tail"])
def test_log_matmul_compiles(one_chip, m, n, k, epilogue, residual):
    ks = resolve_spec("log_matmul", (m, n, k), scheme="rapid10",
                      epilogue=epilogue, platform="tpu")

    def fn(x, w, *r):
        return log_matmul(x, w, "rapid10", epilogue=epilogue, spec=ks,
                          residual=r[0] if r else None, interpret=False)

    shapes = [((m, k), jnp.float32), ((k, n), jnp.float32)]
    if residual:
        shapes.append(((m, n), jnp.float32))
    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("family", ["fused_rms", "fused_softmax"])
def test_fused_dividers_compile(one_chip, family):
    rows = 64
    ks = resolve_spec(family, (rows, D_MODEL), scheme="rapid9",
                      platform="tpu")
    if family == "fused_rms":
        def fn(x):
            return fused_rms_div(x, 1e-6, "rapid9", spec=ks,
                                 interpret=False)
    else:
        def fn(x):
            return fused_softmax_div(x, "rapid9", spec=ks, interpret=False)
    hlo = _compile(one_chip, fn, ((rows, D_MODEL), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("scheme", ["rapid9", None], ids=["rapid", "exact"])
def test_flash_decode_compiles_over_paged_view(one_chip, scheme):
    """Four slots of a 512-token paged view, 64-wide heads (narrower than
    a 128-lane tile, so the kernel pads them)."""
    slots, cache = 4, 512
    ks = resolve_spec("flash_attn", (slots * N_HEADS, cache, 1, HEAD_DIM),
                      scheme=scheme, platform="tpu")

    def fn(q, k, v, sp, pos):
        return flash_decode_attn(q, k, v, sp, pos, 0, scheme, spec=ks,
                                 interpret=False)

    hlo = _compile(one_chip, fn,
                   ((slots, N_HEADS, 1, HEAD_DIM), jnp.float32),
                   ((slots, cache, N_HEADS, HEAD_DIM), jnp.bfloat16),
                   ((slots, cache, N_HEADS, HEAD_DIM), jnp.bfloat16),
                   ((slots, cache), jnp.int32),
                   ((slots,), jnp.int32))
    assert "tpu_custom_call" in hlo
