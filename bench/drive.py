"""Drive ``ContinuousServeEngine`` through ``submit``/``step`` for one window.

Closed loop: one client per slot; the clients' first requests are
admitted and prefilled during set-up, and each client sends its next
request the moment the last completes.  Open loop: requests are
submitted when due on the arrival schedule, whether or not earlier ones
have finished, and each is timed from when it was due.

Every engine call and every wait of the generator sits in a
``jax.profiler.TraceAnnotation`` (``engine.step``, ``gen.submit``,
``gen.wait``), so a trace can say what the host did in each idle gap.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

import jax

from bench.traffic import Request

__all__ = ["Served", "Step", "Window", "fill", "run_window"]


@dataclass
class Served:
    request: Request
    rid: int
    due: float                     # when it was due (host clock)
    submitted: float
    tokens: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    done: bool = False


@dataclass
class Step:
    start: float
    end: float
    decode_tokens: int             # tokens of the decode tick
    prefill_rows: int              # prompt tokens absorbed by this step
    contexts: List[int]            # context of each decoded slot
    prefill_offset: int


@dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    served: List[Served] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    trace_start: Optional[float] = None


class _Loop:
    def __init__(self, engine, clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.by_rid = {}
        self.all: List[Served] = []
        self.steps: List[Step] = []

    def submit(self, req: Request, due: float) -> Served:
        with jax.profiler.TraceAnnotation("gen.submit"):
            rid = self.engine.submit(req.prompt, max_new=req.out_len)
        s = Served(req, rid, due, self.clock())
        self.by_rid[rid] = s
        self.all.append(s)
        return s

    def _prefilled(self) -> dict:
        return {s.rid: s.n_prefilled for s in self.engine._slots
                if s is not None}

    def step(self) -> List[Served]:
        """One engine tick; returns the requests it completed."""
        before = self._prefilled()
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("engine.step"):
            events = self.engine.step()
        t1 = self.clock()
        finished, contexts = [], []
        for ev in events:
            s = self.by_rid[ev.rid]
            if ev.token is not None:
                if s.tokens:   # a decoded token (the first closes a prefill)
                    contexts.append(len(s.request.prompt) + len(s.tokens))
                s.tokens.append(ev.token)
                s.times.append(t1)
            if ev.done:
                s.done = True
                finished.append(s)
        rows, offset = 0, 0
        for rid, n in self._prefilled().items():
            if n > before.get(rid, 0):
                rows, offset = n - before.get(rid, 0), before.get(rid, 0)
        self.steps.append(Step(t0, t1, len(contexts), rows, contexts, offset))
        return finished


def fill(engine, reqs: Iterator[Request], clients: int,
         clock: Callable[[], float] = time.perf_counter) -> _Loop:
    """Set-up of a closed loop: admit and prefill one request per client."""
    d = _Loop(engine, clock)
    for _ in range(clients):
        d.submit(next(reqs), clock())
    while any(not s.tokens for s in d.all):
        for s in d.step():
            d.submit(next(reqs), clock())
    return d


def run_window(engine, reqs: Iterator[Request], loop: str, seconds: float,
               clients: Optional[_Loop] = None, trace_at: float = None,
               start_trace: Callable[[], None] = None,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Measure for ``seconds``; the window closes at the end of the last
    step that started before the deadline.  ``start_trace`` is called
    once, before the first step that starts ``trace_at`` seconds or more
    after the window opened."""
    d = clients or _Loop(engine, clock)
    w = Window()
    w.t_open = clock()
    end = w.t_open + seconds
    pending: Optional[Request] = next(reqs) if loop == "open" else None
    while True:
        now = clock()
        if now >= end:
            break
        if start_trace is not None and w.trace_start is None and \
                now - w.t_open >= trace_at:
            start_trace()
            w.trace_start = clock()
        if loop == "open":
            while w.t_open + pending.offset_s <= now:
                d.submit(pending, w.t_open + pending.offset_s)
                pending = next(reqs)
        if engine.pending:
            for _ in d.step():
                if loop == "closed":
                    d.submit(next(reqs), clock())
        else:
            due = (w.t_open + pending.offset_s if loop == "open" else end)
            with jax.profiler.TraceAnnotation("gen.wait"):
                time.sleep(max(0.0, min(due, end) - clock()))
    w.t_close = now
    w.served = d.all
    w.steps = [s for s in d.steps if s.start >= w.t_open]
    return w
