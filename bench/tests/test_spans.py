"""What the engine names in a trace: program runs, innermost host spans,
device time under a scope; the waits it stamps; the readers of each."""
from types import SimpleNamespace

import pytest

from bench import drive, spans, spec
from bench.spans import Run

MS = 1e6


def _read(metric, ctx):
    return spec.load_module("metrics", metric).read(ctx)


HOST = [("engine.step", 0, 10 * MS), ("serve.admit", 0, 1 * MS),
        ("serve.decode", 1 * MS, 3 * MS), ("serve.fetch", 3 * MS, 8 * MS),
        ("serve.sample", 8 * MS, 9 * MS), ("gen.wait", 10 * MS, 12 * MS)]

HLO = """\
HloModule jit_decode_tick, is_scheduled=true

%fused_computation (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %add.1 = f32[2]{0} add(%p, %p), metadata={op_name="jit(decode_tick)/while/body/paged_kv/scatter" stack_frame_id=3}
}

ENTRY %main.5 (a: f32[2]) -> f32[2] {
  %a = f32[2]{0} parameter(0)
  %fusion.3 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_tick)/while/body/paged_kv/scatter" stack_frame_id=3}
  %fusion.4 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_tick)/paged_kv_view/select" stack_frame_id=5}
  ROOT %copy.7 = f32[2]{0} copy(%fusion.3), metadata={op_name="jit(decode_tick)/while/body/dynamic_slice" stack_frame_id=4}
}
"""


def test_innermost_span_takes_each_moment():
    assert spans.innermost(HOST) == [
        (0, 1 * MS, "serve.admit"), (1 * MS, 3 * MS, "serve.decode"),
        (3 * MS, 8 * MS, "serve.fetch"), (8 * MS, 9 * MS, "serve.sample"),
        (9 * MS, 10 * MS, "engine.step"), (10 * MS, 12 * MS, "gen.wait")]


def _trace():
    runs = [Run("jit_decode_tick", 1.5 * MS, 5.5 * MS),
            Run("jit_prefill_chunk", 20 * MS, 1 * MS)]      # outside
    ops = [("fusion.3", 1.5 * MS, 2 * MS), ("copy.7", 4 * MS, 3 * MS),
           ("fusion.4", 5 * MS, 1 * MS), ("fusion.3", 20 * MS, 1 * MS)]
    return spans.reduce_planes([runs], [ops], HOST)


def test_idle_goes_to_the_innermost_span():
    """Device busy 1.5-3.5 and 4-7 ms of a 10 ms step: the idle 5.5 ms
    splits by the span the host is in: 4 ms under serve.* spans."""
    t = _trace()
    assert t.window_s == pytest.approx(0.010)
    idle = {k: pytest.approx(v * 1e-3) for k, v in {
        "serve.admit": 1.0, "serve.decode": 0.5, "serve.fetch": 1.5,
        "serve.sample": 1.0, "engine.step": 1.0}.items()}
    assert t.idle_by_span == idle
    assert list(t.idle_split()) == ["serve.fetch", "serve.admit",
                                    "serve.sample", "serve.decode"]
    assert _read("engine_idle_share", SimpleNamespace(engine_trace=t)) == \
        pytest.approx(40.0)


def test_program_runs_inside_the_window():
    t = _trace()
    assert t.run_seconds("jit_decode_tick") == [pytest.approx(0.0055)]
    assert t.run_seconds("jit_prefill_chunk") == []
    ctx = SimpleNamespace(engine_trace=t)
    assert _read("decode_tick_ms", ctx) == pytest.approx(5.5)
    assert _read("prefill_chunk_ms", ctx) is None


def test_scope_joins_instructions_to_their_op_name():
    """``fusion.3`` lies under ``paged_kv``; ``copy.7`` under the layer
    scan's slice and ``fusion.4`` under another name do not."""
    assert spans.op_names(HLO) == {
        "add.1": "jit(decode_tick)/while/body/paged_kv/scatter",
        "fusion.3": "jit(decode_tick)/while/body/paged_kv/scatter",
        "fusion.4": "jit(decode_tick)/paged_kv_view/select",
        "copy.7": "jit(decode_tick)/while/body/dynamic_slice"}
    t = _trace()
    assert t.scope_seconds("jit_decode_tick", HLO, "paged_kv") == (
        pytest.approx(0.002), 1)
    ctx = SimpleNamespace(engine_trace=t, programs={"jit_decode_tick": HLO})
    assert _read("paged_kv_ms_per_tick", ctx) == pytest.approx(2.0)


def test_scope_time_is_per_decode_tick():
    runs = [Run("jit_decode_tick", 0, 4 * MS),
            Run("jit_decode_tick", 5 * MS, 4 * MS)]
    ops = [("fusion.3", 1 * MS, 1 * MS), ("fusion.3", 6 * MS, 3 * MS)]
    t = spans.reduce_planes([runs], [ops], [("engine.step", 0, 10 * MS)])
    ctx = SimpleNamespace(engine_trace=t, programs={"jit_decode_tick": HLO})
    assert _read("paged_kv_ms_per_tick", ctx) == pytest.approx(2.0)
    assert _read("decode_tick_ms", ctx) == pytest.approx(4.0)


def _served(rid, due):
    return drive.Served(request=None, rid=rid, due=due, submitted=due)


def test_waits_of_requests_due_in_the_window():
    """Admission and first-chunk waits on the engine's clock; a request
    still waiting at the close counts what it has waited, one not yet
    admitted has no prefill wait, one due before the window none."""
    from repro.serve.scheduler import RequestTimes

    w = drive.Window(t_open=10.0, t_close=20.0)
    w.served = [_served(0, 9.0), _served(1, 11.0), _served(2, 12.0),
                _served(3, 19.0)]
    times = [RequestTimes(0, 9.0, 9.5, 9.6), RequestTimes(1, 11.0, 11.5, 13.5),
             RequestTimes(2, 12.0, 16.0), RequestTimes(3, 19.0)]
    assert spans.waits(w, times, "submitted", "admitted") == \
        [0.5, 4.0, 1.0]
    assert spans.waits(w, times, "admitted", "first_chunk") == [2.0, 4.0]
    ctx = SimpleNamespace(window=w, request_times=times)
    assert _read("admit_wait_p95_ms", ctx) == pytest.approx(
        1e3 * (1.0 + 0.9 * 3.0))
    assert _read("prefill_wait_p95_ms", ctx) == pytest.approx(
        1e3 * (2.0 + 0.95 * 2.0))


def test_waits_skip_requests_the_record_no_longer_holds():
    """The engine keeps the last ``TIMES_KEPT`` requests' times: one that
    fell out of the record has no wait."""
    from repro.serve.scheduler import RequestTimes

    w = drive.Window(t_open=0.0, t_close=5.0,
                     served=[_served(0, 1.0), _served(1, 2.0)])
    ctx = SimpleNamespace(window=w, request_times=[RequestTimes(1, 2.0, 2.5)])
    assert _read("admit_wait_p95_ms", ctx) == pytest.approx(500.0)
    ctx.request_times = []
    assert _read("admit_wait_p95_ms", ctx) is None


@pytest.mark.parametrize("metric", [
    "decode_tick_ms", "prefill_chunk_ms", "paged_kv_ms_per_tick",
    "engine_idle_share", "admit_wait_p95_ms", "prefill_wait_p95_ms"])
def test_reader_of_a_run_without_engine_readings_reads_nothing(metric):
    """A context that holds only what the harness gives today (no engine
    trace, programs or request times) reads None, not an error."""
    w = drive.Window(t_open=0.0, t_close=1.0)
    assert _read(metric, SimpleNamespace(window=w, trace=None)) is None


def test_compiled_programs_of_a_tiny_engine():
    """The engine's two programs at the shapes it runs them, with the
    metadata the scope join reads."""
    from bench import harness
    from bench import weights as W
    from bench.tests import tiny

    cell = tiny.tiny_cell("minicpm_2b-exact.batch")
    params = W.make_params(W.Sizes.of(cell.config), 3_000_000_019,
                           cell.config["param_dtype"])
    eng = harness.build_engine(cell, params)
    programs = spans.compiled_programs(eng)
    assert sorted(programs) == ["jit_decode_tick", "jit_prefill_chunk"]
    for name, text in programs.items():
        assert text.startswith("HloModule " + name)
        assert any("paged_kv" in v.split("/")
                   for v in spans.op_names(text).values())
    assert eng.trace_counts == {"decode": 1, "prefill": 1}


@pytest.mark.parametrize("name", ["minicpm_2b-exact.chat",
                                  "minicpm_2b-exact.batch"])
def test_tiny_run_reads_both_waits(name, monkeypatch):
    """A tiny CPU run of the cell, its engine on the drive's clock and its
    ``request_times`` handed to the readers as PERF.md §7 wires them."""
    from bench import harness
    from bench.tests import tiny

    engines = []
    build = harness.build_engine

    def build_on_the_drive_clock(cell, params):
        engines.append(build(cell, params))
        engines[-1].clock = clock
        return engines[-1]

    monkeypatch.setattr(harness, "build_engine", build_on_the_drive_clock)
    with tiny.step_clock() as clock:
        ctx = harness.serve(tiny.tiny_cell(name), 3_000_000_019, 1.0,
                            clock(), lambda: {"platform": "cpu"},
                            clock=clock)
    ctx = SimpleNamespace(**vars(ctx),
                          request_times=list(engines[0].request_times))
    for metric in ("admit_wait_p95_ms", "prefill_wait_p95_ms"):
        v = _read(metric, ctx)
        assert v is not None and 0.0 <= v < 1e3, (metric, v)


def _recorded():
    """A tiny engine (the chat cell's tiny shapes, flash decode as a
    Pallas kernel) traced for 0.2 s on one TPU v5 lite, with the compiled
    text of its two programs from the same run."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    data = Path(__file__).parent / "data"
    raw = gzip.decompress((data / "engine.xplane.pb.gz").read_bytes())
    programs = {p: gzip.decompress(
        (data / f"engine.{p}.hlo.txt.gz").read_bytes()).decode()
        for p in ("jit_decode_tick", "jit_prefill_chunk")}
    return ProfileData.from_serialized_xspace(raw), programs


def test_programs_and_spans_of_a_trace_recorded_on_the_chip():
    """Every module the trace ran is one of the engine's two named
    programs (no ``jit__lambda``, no eager ``convert_element_type``), and
    each engine step holds its ``serve.*`` phases."""
    from collections import Counter

    pd, _ = _recorded()
    modules, host = Counter(), Counter()
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Modules":
                    modules[ev.name.split("(")[0]] += 1
                elif plane.name.startswith("/host:") and (
                        ev.name == "engine.step" or
                        ev.name.startswith(spans.SERVE)):
                    host[ev.name] += 1
    assert modules == {"jit_decode_tick": 21, "jit_prefill_chunk": 3}
    assert host == {"engine.step": 21, "serve.admit": 21,
                    "serve.decode": 21, "serve.prefill": 3,
                    "serve.fetch": 24, "serve.sample": 24}


def test_readers_of_a_trace_recorded_on_the_chip():
    """The run there read the same numbers: 21 decode ticks and 2
    prefill chunks in the window, idle split by the innermost span, the
    page writes under ``paged_kv`` by the instruction-to-metadata join;
    the benchmark's own reduction of the same trace still finds the
    kernel as ``flash_decode_pallas``."""
    from bench import xplane

    pd, programs = _recorded()
    t = spans.reduce_profile(pd)
    assert t.n_devices == 1
    assert t.window_s == pytest.approx(0.099217206, rel=1e-9)
    assert len(t.run_seconds("jit_decode_tick")) == 21
    assert len(t.run_seconds("jit_prefill_chunk")) == 2
    assert t.idle_by_span["serve.decode"] == pytest.approx(0.037336433,
                                                           rel=1e-6)
    assert t.idle_by_span["serve.fetch"] == pytest.approx(0.023326141,
                                                          rel=1e-6)
    assert t.idle_by_span["engine.step"] < 0.02 * sum(
        t.idle_split().values())
    assert t.scope_seconds("jit_decode_tick", programs["jit_decode_tick"],
                           "attention") == (pytest.approx(0.000279665), 21)
    ctx = SimpleNamespace(engine_trace=t, programs=programs)
    for metric, value in (("decode_tick_ms", 0.03327104761904762),
                          ("prefill_chunk_ms", 0.0249165),
                          ("paged_kv_ms_per_tick", 0.005732952380952381),
                          ("engine_idle_share", 68.12203621214557)):
        assert _read(metric, ctx) == pytest.approx(value, rel=1e-9), metric
    old = xplane.reduce_profile(pd)
    assert old.window_s == t.window_s
    assert old.op_seconds("^flash_decode_pallas$") == (
        pytest.approx(0.000236243), 42)
