"""Host spans of the serving loop: the program's one span recorder.

``span(name, rid)`` times a block of host work with ``time.perf_counter_ns``
(the clock ``Request.submit_s`` and every other host stamp use) into a
process-wide ring of ``RING_SIZE`` records, and enters
``jax.profiler.TraceAnnotation(name)``, so that during a profiler session
the same span lands on the host plane of the device trace. The recorder is
always on; with the profiler off a span costs a few microseconds.

A record is ``(name, t0_ns, t1_ns, step, rid, tid, gauges)``: ``step``
numbers the ``engine.step`` spans of the process (for any other span, the
latest one begun), ``rid`` is a request id or -1, ``tid`` the writing
thread. Once the ring is full the oldest records are overwritten and
counted in ``dropped()``; ``totals()`` keeps counting over the whole life
of the process.

Span names (fixed: ``bench/metrics`` and PERF.md read them by name):

==================  ========================================================
``engine.step``     all of ``ServingEngine.step``; its record carries the
                    gauges ``kv_blocks_committed``, ``kv_blocks_live``,
                    ``slots_decoding`` and ``attn_pages_visited``, sampled
                    as the step ends
``engine.plan``     ``Scheduler.plan_step`` with its admission and
                    preemption callbacks
``engine.admit``    one granted admission: the allocator, copy-on-write
                    block copies and the ``begin_slot`` (or monolithic
                    prefill) dispatch; inside ``engine.plan``
``engine.chunk``    one prompt chunk: host preparation and its dispatch
``engine.reserve``  the look-ahead block reservation before a decode round
``engine.dispatch`` the call of the step, scan or speculative program: host
                    enqueue time only
``engine.sync``     the wait for the round's active mask, the one place the
                    host waits on the device
``engine.pull``     the ``steps`` and ``out`` pulls and the ``on_tokens`` tap
``engine.finish``   completion bookkeeping, ``backend.free_slot`` included
``gateway.loop``    the gateway's loop thread from the return of one engine
                    step to the next: cancels, inbox forwarding, resolving
                    finished requests, handing out streamed tokens
``host.gc``         one garbage collection (``gc.callbacks``)
``host.compile``    one XLA compile (JAX's ``backend_compile_duration``
                    event); on the ring only, as it is known once it ended
==================  ========================================================

A span that interrupts another on its thread lies inside it in time, so
``steps()`` files ``host.gc`` and ``host.compile`` under the ``engine.*``
span they interrupted.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gc
import itertools
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional

import jax

RING_SIZE = 1 << 16
STEP = "engine.step"


class Record(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    step: int
    rid: int
    tid: int
    gauges: Optional[Dict[str, int]]


class Recorder:
    """A fixed ring of span records plus running totals per name. Writers
    on several threads, and a ``gc`` callback that can fire in the middle
    of a write, share one re-entrant lock."""

    def __init__(self, size: int = RING_SIZE):
        self.size = size
        self._ring: List[Optional[Record]] = [None] * size
        self._n = 0                      # records ever written
        self._totals: Dict[str, List[int]] = {}     # name -> [count, ns]
        self._lock = threading.RLock()

    def record(self, name: str, t0_ns: int, t1_ns: int, step: int,
               rid: int = -1, gauges: Optional[Dict[str, int]] = None
               ) -> None:
        rec = Record(name, t0_ns, t1_ns, step, rid, threading.get_ident(),
                     gauges)
        with self._lock:
            self._ring[self._n % self.size] = rec
            self._n += 1
            tot = self._totals.setdefault(name, [0, 0])
            tot[0] += 1
            tot[1] += t1_ns - t0_ns

    def dropped(self) -> int:
        return max(0, self._n - self.size)

    def records(self) -> List[Record]:
        """The ring's records, oldest first."""
        with self._lock:
            n, ring = self._n, list(self._ring)
        if n <= self.size:
            return ring[:n]
        i = n % self.size
        return ring[i:] + ring[:i]

    def totals(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"count": c, "s": ns * 1e-9}
                    for k, (c, ns) in sorted(self._totals.items())}

    def steps(self, t_from: float, t_to: float
              ) -> Optional[List["StepRecord"]]:
        """One ``StepRecord`` per ``engine.step`` that began in
        ``[t_from, t_to)`` (``time.perf_counter`` seconds), in order; None
        when the ring has overwritten records that began there."""
        recs = self.records()
        if self.dropped() and recs and recs[0].t1_ns > t_from * 1e9:
            return None
        by_tid: Dict[int, List[Record]] = collections.defaultdict(list)
        for r in recs:
            if r.name != STEP:
                by_tid[r.tid].append(r)
        for v in by_tid.values():
            v.sort(key=lambda r: r.t0_ns)
        starts = {tid: [r.t0_ns for r in v] for tid, v in by_tid.items()}
        out = []
        for s in sorted((r for r in recs if r.name == STEP
                         and t_from <= r.t0_ns * 1e-9 < t_to),
                        key=lambda r: r.t0_ns):
            kids = by_tid.get(s.tid, [])
            i = bisect.bisect_left(starts.get(s.tid, []), s.t0_ns)
            inside = []
            while i < len(kids) and kids[i].t0_ns <= s.t1_ns:
                if kids[i].t1_ns <= s.t1_ns:
                    inside.append((kids[i].name, kids[i].t0_ns * 1e-9,
                                   kids[i].t1_ns * 1e-9))
                i += 1
            out.append(StepRecord(s.t0_ns * 1e-9, s.t1_ns * 1e-9, s.step,
                                  dict(s.gauges or {}), inside))
        return out


@dataclasses.dataclass
class StepRecord:
    """One ``engine.step``, in ``time.perf_counter`` seconds: the spans
    inside it on its thread, ``(name, t0, t1)`` in start order, and the
    gauges sampled as it ended."""
    t0: float
    t1: float
    step: int
    gauges: Dict[str, int]
    spans: List[tuple]

    def seconds(self, name: str) -> float:
        """Total time of the spans called ``name`` inside this step."""
        return sum(b - a for n, a, b in self.spans if n == name)

    def first_start(self, names: Iterable[str]) -> Optional[float]:
        names = set(names)
        return next((a for n, a, _ in self.spans if n in names), None)

    def last_end(self, name: str) -> Optional[float]:
        ends = [b for n, _, b in self.spans if n == name]
        return max(ends) if ends else None


_REC = Recorder()
_step_numbers = itertools.count(1)
_step = 0                # number of the latest engine.step span begun


class _Span:
    __slots__ = ("name", "rid", "gauges", "_step", "_t0", "_ann")

    def __init__(self, name: str, rid: int):
        self.name, self.rid, self.gauges = name, rid, None

    def __enter__(self) -> "_Span":
        global _step
        if self.name == STEP:
            _step = next(_step_numbers)
        self._step = _step
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        _REC.record(self.name, self._t0, t1, self._step, self.rid,
                    self.gauges)


def span(name: str, rid: int = -1) -> _Span:
    """Time the enclosed host work as span ``name``. Set ``.gauges`` on the
    object it yields to store counters on its record."""
    return _Span(name, rid)


def steps(t_from: float, t_to: float) -> Optional[List[StepRecord]]:
    return _REC.steps(t_from, t_to)


def totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "s"}}`` over the life of the process."""
    return _REC.totals()


def dropped() -> int:
    """Records the ring has overwritten."""
    return _REC.dropped()


def records() -> List[Record]:
    return _REC.records()


# -- spans with no call site -------------------------------------------------

_gc_open: list = []      # (t0_ns, annotation) of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        ann = jax.profiler.TraceAnnotation("host.gc")
        ann.__enter__()
        _gc_open.append((time.perf_counter_ns(), ann))
    elif _gc_open:
        t0, ann = _gc_open.pop()
        t1 = time.perf_counter_ns()
        ann.__exit__(None, None, None)
        _REC.record("host.gc", t0, t1, _step)


def _on_duration(event: str, duration_s: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        t1 = time.perf_counter_ns()
        _REC.record("host.compile", t1 - int(duration_s * 1e9), t1, _step)


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
