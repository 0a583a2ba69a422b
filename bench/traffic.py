"""The one traffic generator: reads a mix file of ``bench/traffic/``.

A mix file gives the loop, the length distributions, the arrival process
and the engine's geometry::

    {"loop": "closed", "clients": 8,
     "prompt": {"dist": "uniform", "lo": 16, "hi": 64},
     "output": {"dist": "uniform", "lo": 192, "hi": 320},
     "engine": {"n_slots": 8, "max_len": 384, ...}}

or ``"loop": "open"`` with ``"rate_per_s"`` (Poisson arrivals).

Every seed gets the same work, drawn one of two ways:

* A mix with a ``schedule_seed`` has one schedule for every run: prompt
  and output lengths and the gaps between arrivals are independent draws
  (exponential gaps: a Poisson process) from a stream that the
  ``schedule_seed`` fixes.  The run's seed draws only the prompts' token
  ids.  An open loop's tail needs that: at ~40 requests a window, the
  p95 of TTFT moves by tens of percent between orders of the same
  requests.
* Otherwise lengths come in blocks of ``BLOCK`` requests; each block
  holds the distribution's ``BLOCK`` mid-quantiles in an order shuffled
  by the run's seed, so any whole number of blocks has the same lengths.

The Poisson trace and mixed lengths follow ``benchmarks/serve_load.py``'s
``make_trace``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional

import numpy as np

__all__ = ["Request", "inverse_cdf", "quantiles", "requests", "BLOCK"]

BLOCK = 16


@dataclass
class Request:
    index: int
    prompt: List[int]
    out_len: int
    offset_s: Optional[float]   # due time after the window opens (open loop)


def inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    """Values of a length or gap distribution at probabilities ``u``."""
    kind = dist["dist"]
    if kind == "uniform":        # integers lo..hi inclusive
        lo, hi = dist["lo"], dist["hi"]
        return np.floor(lo + u * (hi - lo + 1)).astype(np.int64)
    if kind == "loguniform":     # integers, log-uniform over [lo, hi]
        lo, hi = np.log(dist["lo"]), np.log(dist["hi"] + 1)
        return np.floor(np.exp(lo + u * (hi - lo))).astype(np.int64)
    if kind == "exponential":    # seconds, of mean ``mean``
        return -np.log1p(-u) * dist["mean"]
    raise ValueError(f"unknown distribution {kind!r}")


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles ((i + 0.5) / n) of a distribution; gaps
    are scaled so that their mean is the stated one."""
    q = inverse_cdf(dist, (np.arange(n) + 0.5) / n)
    return q * (dist["mean"] / q.mean()) if dist["dist"] == "exponential" \
        else q


def _blocked(dist: dict, rng: np.random.Generator) -> Iterator:
    q = quantiles(dist, BLOCK)
    while True:
        yield from rng.permutation(q)


def _independent(dist: dict, rng: np.random.Generator) -> Iterator:
    while True:
        yield from inverse_cdf(dist, rng.random(BLOCK))


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The mix's requests in order, endless; the same seed gives the same
    requests."""
    rng = np.random.default_rng(int(seed))
    toks = np.random.default_rng(rng.integers(2**63))
    draw = _blocked
    if "schedule_seed" in mix:
        rng = np.random.default_rng(int(mix["schedule_seed"]))
        draw = _independent
    plens = draw(mix["prompt"], np.random.default_rng(rng.integers(2**63)))
    olens = draw(mix["output"], np.random.default_rng(rng.integers(2**63)))
    gaps = None
    if mix["loop"] == "open":
        gaps = draw({"dist": "exponential", "mean": 1.0 / mix["rate_per_s"]},
                    np.random.default_rng(rng.integers(2**63)))
    elif mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    t = 0.0
    i = 0
    while True:
        offset = None
        if gaps is not None:
            t += float(next(gaps))
            offset = t
        n = int(next(plens))
        yield Request(i, toks.integers(1, vocab, n).tolist(), int(next(olens)),
                      offset)
        i += 1
