"""Operation and byte counts of the kernels' work, and the roofline reader."""
from types import SimpleNamespace

import pytest

from bench import flops
from bench import weights as W
from bench.peaks import peak_for

SIZES = W.Sizes(layers=40, d=2304, heads=36, kv_heads=36, ff=5760,
                vocab=122753, eps=1e-5, rope_theta=1e4)


def test_flash_decode_bytes_follow_the_served_dtype():
    f2, b2 = flops.flash_decode_call(SIZES, 8, 384, 2)
    f4, b4 = flops.flash_decode_call(SIZES, 8, 384, 4)
    assert f2 == f4 == 4.0 * 8 * 36 * 64 * 384
    kv = 2 * 8 * 384 * 36 * 64
    assert b2 == 2 * (kv + 2 * 8 * 36 * 64) + 4 * 8 * 384
    assert b4 - b2 == 2 * (kv + 2 * 8 * 36 * 64)


def test_least_time_takes_the_slower_of_compute_and_memory():
    peak = peak_for("TPU v5 lite")
    assert flops.least_time([(197e12, 1.0)], peak) == pytest.approx(1.0)
    assert flops.least_time([(1.0, 819e9)] * 3, peak) == pytest.approx(3.0)


def _trace(seconds, n):
    return SimpleNamespace(op_seconds=lambda pattern: (seconds, n))


def test_flash_decode_roofline_reads_the_configuration_dtype():
    from bench import spec

    reader = spec.load_module("metrics", "flash_decode_roofline")
    steps = [SimpleNamespace(decode_tokens=8)] * 5
    ctx = SimpleNamespace(sizes=SIZES, n_slots=8, capacity=384,
                          dtype="bfloat16", peak=peak_for("TPU v5 lite"),
                          trace=_trace(0.1, 200), traced_steps=steps)
    bf16 = reader.read(ctx)
    _, b = flops.flash_decode_call(SIZES, 8, 384, 2)
    assert bf16 == pytest.approx(100.0 * 5 * 40 * b / 819e9 / 0.1)
    ctx.dtype = "float32"
    assert reader.read(ctx) > 1.9 * bf16
    ctx.trace = _trace(0.0, 0)
    assert reader.read(ctx) is None
    ctx.trace = None
    assert reader.read(ctx) is None
