"""Continuous-batching serve engine: request queue + paged KV + streaming.

The fixed-slot :class:`~repro.serve.engine.ServeEngine` prefills a batch
together and decodes it in lockstep — a finished sequence burns its slot
until the whole batch drains, and cache memory is ``B * cache_n`` no
matter how short the requests are.  This engine keeps every decode slot
busy instead, the ReservationStations idiom of a pipelined ALU applied
to serving:

  * requests queue in FCFS order and are *admitted* into any free slot
    the moment the page allocator can cover their worst case
    (``len(prompt) + max_new`` tokens of KV);
  * prompts prefill in fixed-size chunks *interleaved* with decode
    ticks, so long prompts never stall ongoing generations;
  * KV lives in a block-paged pool (``repro.serve.paged_kv``) addressed
    through per-slot page tables — memory scales with live tokens;
  * finished requests free their pages and slot immediately (slot
    recycling / eviction) and their tokens stream out per request as
    :class:`StreamEvent`s.

Both engine phases run through one compiled ``Model.decode_paged``; the
decode tick has fixed ``[n_slots, 1]`` shapes with a dynamic occupancy
mask (``n_valid``), so it compiles exactly once, and the prefill tick
has fixed ``[1, prefill_chunk]`` shapes, so it too compiles once.

Sampling keys derive per request: ``fold_in(fold_in(root, rid), k)`` for
a request's k-th draw — a request's sampled tokens are deterministic
and independent of which requests happen to co-reside in the batch.

Tracing costs nothing unless a profiler is collecting.  The two programs
are ``jit_decode_tick`` and ``jit_prefill_chunk`` in a device trace;
each phase of :meth:`ContinuousServeEngine.step` runs inside one
``jax.profiler.TraceAnnotation`` (``serve.admit``, ``serve.prefill``,
``serve.decode``, ``serve.fetch``, ``serve.sample``), whose enabled
check is the profiler's own.  Each request is stamped on the engine's
clock when it is submitted, admitted, and when its first prefill chunk
is dispatched (:class:`RequestTimes`, a bounded record).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import backend as be
from repro.models.layers import ParallelCtx
from repro.models.model import Model
from repro.serve.paged_kv import PageAllocator, PageGeometry

__all__ = ["StreamEvent", "RequestTimes", "ContinuousServeEngine",
           "TIMES_KEPT"]

#: how many requests' :class:`RequestTimes` an engine keeps, newest last
TIMES_KEPT = 4096


@dataclass(frozen=True)
class StreamEvent:
    """One streamed output: a token for ``rid``, and/or its completion.

    ``token`` is None on a pure completion event (stop token seen — the
    stop token itself is never emitted — or the request was cancelled).
    """

    rid: int
    token: Optional[int]
    done: bool


@dataclass
class RequestTimes:
    """When a request was submitted, admitted into a slot, and had its
    first prefill chunk dispatched, on the engine's clock; None until
    it happens."""

    rid: int
    submitted: float
    admitted: Optional[float] = None
    first_chunk: Optional[float] = None


@dataclass
class _Queued:
    rid: int
    prompt: List[int]
    max_new: int
    stop_token: Optional[int]
    times: RequestTimes


@dataclass
class _Slot:
    rid: int
    prompt: List[int]
    max_new: int
    stop_token: Optional[int]
    pages: List[int]
    times: RequestTimes
    n_prefilled: int = 0
    length: int = 0              # KV tokens stored for this slot
    last_token: Optional[int] = None   # pending token to feed to decode
    n_generated: int = 0
    n_sampled: int = 0           # sampling-key counter (includes stop draw)
    out: List[int] = field(default_factory=list)


class ContinuousServeEngine:
    """Continuous-batching engine over a block-paged KV cache.

    ``max_len`` bounds one request's total tokens (prompt + generated)
    and fixes the per-slot page-table width; ``n_pages`` sizes the
    shared pool (default: every slot can be full simultaneously — the
    same peak KV memory as a fixed-slot engine with ``cache_n ==
    max_len``, but shorter requests leave their pages to others).
    ``clock`` stamps :attr:`request_times`, the waits of the last
    :data:`TIMES_KEPT` requests submitted.
    """

    def __init__(self, model: Model, params: dict,
                 ctx: Optional[ParallelCtx] = None, n_slots: int = 4,
                 max_len: int = 256, page_size: int = 16,
                 n_pages: Optional[int] = None, prefill_chunk: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 backend: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        if model.cfg.family not in ("dense", "moe"):
            raise ValueError(
                "continuous batching serves decoder-only text families "
                f"(dense/moe); got {model.cfg.family!r}")
        pinned = be.pin_backends(model.cfg.approx, backend)
        if pinned != model.cfg.approx:
            model = Model(model.cfg.with_(approx=pinned))
        self.model = model
        self.params = params
        self.ctx = ctx or ParallelCtx()
        self.backend = pinned.backend_for("default")
        self.n_slots = n_slots
        self.prefill_chunk = prefill_chunk
        self.temperature = temperature
        self.seed = seed
        self.clock = clock

        pages_per_slot = -(-max_len // page_size)
        if n_pages is None:
            n_pages = n_slots * pages_per_slot + 1  # + scratch page
        self.geom = PageGeometry(page_size, n_pages, pages_per_slot)
        self.alloc = PageAllocator(self.geom)
        self.page_table = np.zeros((n_slots, pages_per_slot), np.int32)
        self.cache = model.init_paged_cache(n_pages, page_size)

        self._queue: deque = deque()
        self._slots: List[Optional[_Slot]] = [None] * n_slots
        self._next_rid = 0
        self._root_key = jax.random.PRNGKey(seed)
        self.request_times: deque = deque(maxlen=TIMES_KEPT)
        # trace-time counters: each jit retrace == one compile, so the
        # bench gate can assert "decode recompiles at most once"
        self.trace_counts = {"decode": 0, "prefill": 0}

        # named, so that a device trace tells the two programs apart
        def decode_tick(p, c, t, pt, off, nv):
            self.trace_counts["decode"] += 1
            return self.model.decode_paged(p, t, c, pt, off, nv, self.ctx)

        def prefill_chunk(p, c, t, pt, off, nv):
            self.trace_counts["prefill"] += 1
            return self.model.decode_paged(p, t, c, pt, off, nv, self.ctx)

        self._decode = jax.jit(decode_tick)
        self._prefill = jax.jit(prefill_chunk)

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int = 32,
               stop_token: Optional[int] = None) -> int:
        """Queue a request; returns its rid.  Admission happens inside
        :meth:`step` as soon as a slot and enough pages free up."""
        total = len(prompt) + max_new
        if not prompt or max_new < 1:
            raise ValueError(
                f"need a non-empty prompt ({len(prompt)}) and max_new >= 1 "
                f"({max_new})")
        if total > self.geom.slot_capacity:
            raise ValueError(
                f"prompt length {len(prompt)} + max_new {max_new} = {total} "
                f"exceeds slot capacity {self.geom.slot_capacity} "
                f"({self.geom.pages_per_slot} pages x {self.geom.page_size})")
        if self.geom.pages_for(total) > self.geom.usable_pages:
            raise ValueError(
                f"request needs {self.geom.pages_for(total)} pages; pool "
                f"has only {self.geom.usable_pages} usable pages")
        rid = self._next_rid
        self._next_rid += 1
        times = RequestTimes(rid, self.clock())
        self.request_times.append(times)
        self._queue.append(_Queued(rid, list(prompt), max_new, stop_token,
                                   times))
        return rid

    def cancel(self, rid: int) -> bool:
        """Evict a queued or running request; frees its slot and pages."""
        for i, q in enumerate(self._queue):
            if q.rid == rid:
                del self._queue[i]
                return True
        for b, s in enumerate(self._slots):
            if s is not None and s.rid == rid:
                self._evict(b)
                return True
        return False

    @property
    def pending(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    @property
    def n_live_tokens(self) -> int:
        return sum(s.length for s in self._slots if s is not None)

    def _evict(self, b: int) -> None:
        slot = self._slots[b]
        self.alloc.free(slot.pages)
        self.page_table[b, :] = 0
        self._slots[b] = None

    def _admit(self) -> None:
        """FCFS admission: head of queue waits for slot + pages (no
        skip-ahead, so a big request cannot starve)."""
        for b in range(self.n_slots):
            if not self._queue or self._slots[b] is not None:
                continue
            req = self._queue[0]
            pages = self.alloc.alloc(
                self.geom.pages_for(len(req.prompt) + req.max_new))
            if pages is None:
                break
            self._queue.popleft()
            self.page_table[b, :] = 0
            self.page_table[b, :len(pages)] = pages
            req.times.admitted = self.clock()
            self._slots[b] = _Slot(req.rid, req.prompt, req.max_new,
                                   req.stop_token, pages, req.times)

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _sample(self, logits_row, slot: _Slot) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        key = jax.random.fold_in(
            jax.random.fold_in(self._root_key, slot.rid), slot.n_sampled)
        slot.n_sampled += 1
        return int(jax.random.categorical(
            key, jnp.asarray(logits_row) / self.temperature))

    def _emit(self, b: int, tok: int, events: List[StreamEvent]) -> None:
        slot = self._slots[b]
        if slot.stop_token is not None and tok == slot.stop_token:
            # stop token terminates the request without being emitted
            events.append(StreamEvent(slot.rid, None, True))
            self._evict(b)
            return
        slot.out.append(tok)
        slot.n_generated += 1
        done = slot.n_generated >= slot.max_new
        events.append(StreamEvent(slot.rid, tok, done))
        if done:
            self._evict(b)
        else:
            slot.last_token = tok

    # ------------------------------------------------------------------
    # engine loop
    # ------------------------------------------------------------------
    def step(self) -> List[StreamEvent]:
        """One engine tick: admit, one prefill chunk, one decode step."""
        events: List[StreamEvent] = []
        with TraceAnnotation("serve.admit"):
            self._admit()

        # chunked prefill, interleaved: the lowest-numbered slot with an
        # unfinished prompt absorbs one fixed-shape chunk per tick
        pf = [(b, s) for b, s in enumerate(self._slots)
              if s is not None and s.n_prefilled < len(s.prompt)]
        if pf:
            b, slot = pf[0]
            with TraceAnnotation("serve.prefill", rid=slot.rid):
                CK = self.prefill_chunk
                chunk = slot.prompt[slot.n_prefilled:slot.n_prefilled + CK]
                toks = np.zeros((1, CK), np.int32)
                toks[0, :len(chunk)] = chunk
                # numpy, not Python lists: jnp.asarray of a list runs a
                # convert program of its own on the device
                logits, self.cache = self._prefill(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self.page_table[b:b + 1]),
                    jnp.asarray(np.array([slot.length], np.int32)),
                    jnp.asarray(np.array([len(chunk)], np.int32)))
            if slot.times.first_chunk is None:
                slot.times.first_chunk = self.clock()
            slot.n_prefilled += len(chunk)
            slot.length += len(chunk)
            if slot.n_prefilled == len(slot.prompt):
                with TraceAnnotation("serve.fetch"):
                    row = np.asarray(logits)[0]
                with TraceAnnotation("serve.sample"):
                    self._emit(b, self._sample(row, slot), events)

        # one decode tick across every slot with a pending token
        with TraceAnnotation("serve.decode"):
            tokens = np.zeros((self.n_slots, 1), np.int32)
            offsets = np.zeros((self.n_slots,), np.int32)
            n_valid = np.zeros((self.n_slots,), np.int32)
            live = []
            for b, s in enumerate(self._slots):
                if s is not None and s.last_token is not None:
                    tokens[b, 0] = s.last_token
                    offsets[b] = s.length
                    n_valid[b] = 1
                    live.append(b)
            if live:
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(self.page_table), jnp.asarray(offsets),
                    jnp.asarray(n_valid))
        if live:
            with TraceAnnotation("serve.fetch"):
                lg = np.asarray(logits)
            with TraceAnnotation("serve.sample"):
                for b in live:
                    slot = self._slots[b]
                    slot.length += 1
                    slot.last_token = None
                    self._emit(b, self._sample(lg[b], slot), events)
        return events

    def stream(self, prompts: List[List[int]], max_new: int = 32,
               stop_token: Optional[int] = None) -> Iterator[StreamEvent]:
        """Submit ``prompts`` and yield events until the engine drains."""
        for p in prompts:
            self.submit(p, max_new, stop_token)
        while self.pending:
            yield from self.step()

    def generate(self, prompts: List[List[int]], max_new: int = 32,
                 stop_token: Optional[int] = None) -> List[List[int]]:
        """Drain helper with the fixed-slot engine's signature."""
        rids = [self.submit(p, max_new, stop_token) for p in prompts]
        outs = {r: [] for r in rids}
        while self.pending:
            for ev in self.step():
                if ev.token is not None and ev.rid in outs:
                    outs[ev.rid].append(ev.token)
        return [outs[r] for r in rids]
