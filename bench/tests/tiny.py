"""A cell of the benchmark cut to a size the CPU runs in seconds.

Tiny runs go by a step clock, not the host's: every reading moves it by
``StepClock.DT`` and the generator's sleeps move it instead of waiting,
so a run serves the same requests however loaded the CPU is.
"""
import contextlib
import copy
from types import SimpleNamespace
from unittest import mock

from bench import drive, spec
from bench.peaks import PEAKS

TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=256, vocab_size=512)


def tiny_cell(name: str, limits=None) -> spec.Cell:
    cell = spec.resolve_cell(spec.load_benchmark(), name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY)
    cfg["arithmetic"]["backend"] = "jnp"
    if limits is not None:
        cfg["limits"] = limits
    mix = copy.deepcopy(cell.traffic)
    mix["engine"].update(n_slots=4, max_len=64, prefill_chunk=16,
                         n_pages=None)
    mix["prompt"].update(lo=4, hi=20)
    mix["output"].update(lo=4, hi=30)
    if "clients" in mix:
        mix["clients"] = 4
    else:                 # the tiny model serves a request in milliseconds
        mix["rate_per_s"] = 20.0
    cell.config, cell.traffic = cfg, mix
    return cell


class StepClock:
    DT = 1e-3

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += self.DT
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += max(0.0, seconds)


@contextlib.contextmanager
def step_clock():
    clock = StepClock()
    with mock.patch.object(drive, "time",
                           SimpleNamespace(sleep=clock.sleep,
                                           perf_counter=clock)):
        yield clock


def run(cell: spec.Cell, seed: int = 3_000_000_019, seconds: float = 1.0):
    from bench import harness

    with step_clock() as clock:
        return harness.run_cell(cell, seed, seconds, False, clock(),
                                PEAKS["TPU v5 lite"],
                                lambda: {"platform": "cpu"}, clock=clock)
