"""``correct`` is decided by the reference: a sound run passes, a run with
the timed path broken underneath fails, and so does the control.

A whole run is driven on the CPU at a tiny size (``tiny.py``, on a step
clock, so that it serves the same requests on any machine), past the
harness's look for a chip.  The limits here are the tiny model's: over
8 seeds of each cell, sound runs read a widest gap of at most 0.008 and
a ``gap_mean`` of at most 1.6e-4, the int8 control a ``gap_mean`` of
0.85e-4 to 6.6e-4 (logits of this model spread by ~0.1).  At this size
the two overlap on a few seeds, which the chip's size does not
(``PERF.md``); on the seeds used here they lie on either side of the
``gap_mean`` limit, by 1.5x or more.
"""
import numpy as np
import pytest

from bench.tests import tiny

LIMITS = {"gap_max": 0.05, "gap_mean": 0.0002}


@pytest.mark.parametrize("name", ["minicpm_2b-exact.batch",
                                  "minicpm_2b-exact.chat"])
def test_sound_run_is_correct(name):
    out = tiny.run(tiny.tiny_cell(name, LIMITS))
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) >= {"tokens_per_s", "itl_p95_ms", "setup_s"}


def test_token_altered_where_it_is_produced(monkeypatch):
    from repro.serve.scheduler import ContinuousServeEngine

    sample = ContinuousServeEngine._sample
    calls = []

    def altered(self, logits_row, slot):
        tok = sample(self, logits_row, slot)
        calls.append(tok)
        return (tok + 1) % 512 if len(calls) % 10 == 0 else tok

    monkeypatch.setattr(ContinuousServeEngine, "_sample", altered)
    out = tiny.run(tiny.tiny_cell("minicpm_2b-exact.batch", LIMITS))
    assert not out["correct"], out["check"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.serve import scheduler

    real = scheduler.ContinuousServeEngine.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        decode = self._decode

        def stale(p, c, *rest):
            logits, _ = decode(p, c, *rest)
            return logits, c          # the KV writes of the tick are lost
        self._decode = stale

    monkeypatch.setattr(scheduler.ContinuousServeEngine, "__init__", init)
    out = tiny.run(tiny.tiny_cell("minicpm_2b-exact.batch", LIMITS))
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("name", ["minicpm_2b-exact.batch",
                                  "minicpm_2b-exact.chat"])
def test_control_is_judged_not_correct(name):
    """The configuration's control (the reference in int8) on the same
    prompts and served tokens fails the limits that the program passes."""
    from bench import control

    with tiny.step_clock() as clock:
        lines = list(control.readings(tiny.tiny_cell(name, LIMITS),
                                      [3_000_000_007], {3_000_000_007}, 1.0,
                                      clock))
    line = lines[0]
    assert line["program_correct"], line
    assert not line["control_correct"], line
    prog, ctrl = line["program"], line["control"]
    assert np.isfinite(list(prog.values())).all()
    assert ctrl["gap_mean"] > prog["gap_mean"], (prog, ctrl)
