"""The trace reduction: busy time, window, idle attribution, kernel time."""
import pytest

from bench import xplane
from bench.xplane import Op


def _op(name, start_ms, dur_ms):
    return Op(name, start_ms * 1e6, dur_ms * 1e6)


@pytest.mark.parametrize("event, name", [
    ("%flash_decode_pallas.11 = f32[288,8,128]{2,1,0} custom-call(...)",
     "flash_decode_pallas"),
    ("%log_matmul_pipelined.81 = f32[8,5888]{1,0} custom-call(...)",
     "log_matmul_pipelined"),
    ("%constant_dynamic-update-slice_fusion.4 = bf16[40,193] fusion(...)",
     "constant_dynamic-update-slice_fusion"),
    ("%copy.85 = bf16[1,193,16,36,64] copy(...)", "copy"),
    ("%while.13 = (s32[]) while(...)", "while"),
    ("fusion", "fusion"),
])
def test_op_names(event, name):
    assert xplane.op_name(event) == name


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [_op("fusion", 1, 2), _op("custom-call", 2, 2),   # 1..4 overlap
           _op("fusion", 6, 1),                             # 6..7
           _op("copy", 9, 3)]                               # 9..12, cut at 10
    host = [("engine.step", 0, 5e6), ("gen.wait", 5e6, 8e6),
            ("engine.step", 8e6, 10e6)]
    t = xplane.reduce_planes([ops], host)
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s == pytest.approx(0.005)        # 3 + 1 + 1 ms
    # idle: 0..1 and 4..5 in a step (2 ms), 5..6 and 7..8 waiting (2 ms),
    # 8..9 in a step (1 ms)
    assert t.idle_by_host["engine.step"] == pytest.approx(0.003)
    assert t.idle_by_host["gen.wait"] == pytest.approx(0.002)
    assert t.top_idle()[0][0] == "engine.step"


def test_kernel_time_and_top_ops():
    ops = [_op("custom-call", 0, 4), _op("custom-call", 5, 4),
           _op("fusion", 9, 1)]
    t = xplane.reduce_planes([ops], [("engine.step", 0, 10e6)])
    assert t.op_seconds("custom-call") == (pytest.approx(0.008), 2)
    assert t.top_ops()[0] == ["custom-call", pytest.approx(0.008)]


def test_busy_is_averaged_over_devices():
    a = [_op("x", 0, 10)]
    b = [_op("x", 0, 5)]
    t = xplane.reduce_planes([a, b], [("engine.step", 0, 10e6)])
    assert t.busy_s == pytest.approx(0.0075)


def test_no_step_span_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes([[_op("x", 0, 1)]], [("gen.wait", 0, 1e6)])


def test_reduction_of_a_trace_recorded_on_the_chip():
    """A tiny engine traced for 0.25 s on one TPU v5 lite; the run there
    reported busy_s 0.002055745 and window_s 0.205346432."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (Path(__file__).parent / "data" / "small.xplane.pb.gz").read_bytes())
    t = xplane.reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert t.n_devices == 1
    assert t.window_s == pytest.approx(0.205346432, rel=1e-9)
    assert t.busy_s == pytest.approx(0.002055745, rel=1e-9)
    seconds, calls = t.op_seconds("^flash_decode")
    assert calls == 102 and seconds == pytest.approx(0.000573764, rel=1e-9)
    assert t.op_seconds("^log_matmul") == (0.0, 0)
    assert set(t.idle_by_host) == {"engine.step"}
    assert sum(t.idle_by_host.values()) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-9)
    assert "while" not in {name for name, _ in t.top_ops(100)}
