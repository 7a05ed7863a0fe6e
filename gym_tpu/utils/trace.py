"""The program's one span recorder, on the profiler's clock.

``span(name, **ids)`` marks a layer boundary. In one ``with`` it

1. enters ``jax.profiler.TraceAnnotation(name, seq=..., **ids)``. Outside
   a profiler session that is a check of one flag; inside one
   (``jax.profiler.trace`` / ``start_trace`` / ``start_server``,
   ``fit(profile_dir=)``) the span lands on the host plane of the same
   ``xplane.pb`` as the device's operations. "Tracing on" is "a profiler
   session is active"; there is no other switch;
2. adds its duration, and the time its thread ran inside it, to the
   process-wide table ``totals()``: ``{name: (count, total_s, max_s,
   cpu_s)}``, always on and never trimmed;
3. appends ``Record(seq, name, t0, t1, parent, ids, cpu)`` to one ring of
   ``CAPACITY`` records, read by ``records(**match)``.

Two clocks, both always on: ``t0`` / ``t1`` are ``time.perf_counter_ns()``
and ``cpu`` is the difference of the thread's CPU clock
(``time.thread_time_ns()``) at the same two moments. ``seconds - cpu`` is
the time the thread stood off the CPU inside the span: it waited for the
device, for a lock (the interpreter's among them) or for the OS to schedule
it; the clock cannot say which. The thread clock is a system call (0.3 us
on this sandbox, 6 us on the chip's host and 20-30 under a serving load:
PERF.md §6, PR 37), so a reading younger than ``REUSE_NS`` on the same
thread is used again: where one span closes and the next opens, both take
one reading. ``cpu`` is exact to ``REUSE_NS`` at either end where the
clock is fine; on the chip's host it ticks in steps of 10 ms, so there only
sums over many spans mean anything. A span still marks a boundary that is
crossed tens of times a round or a step, never once a token.

Who annotates: only the thread that feeds the device. The profiler's host
plane is where the chip's idle gaps are booked to what the host was doing,
so a span of any other thread (an HTTP handler's) is recorder-only
(``annotate=False``): it would lie over the driver's leaves and take their
gaps.

``parent`` is the ``seq`` of the span open around this one on the same
thread. A span inherits its parent's ids, so ``request`` and ``round``
reach the engine's leaves without the engine knowing either. ``seq`` is on
the xplane event too: a record and its event pair up, and any pair gives
the offset between the two clocks. Span names and the metric each is for
are listed in PERF.md §3.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 8192     # a 51 s window of fit or of serving writes under 2,500
REUSE_NS = 50_000   # how old a reading of the thread's CPU clock may be


class Record(NamedTuple):
    seq: int
    name: str
    t0: int                     # time.perf_counter_ns()
    t1: int
    parent: Optional[int]       # seq of the enclosing span on this thread
    ids: Dict[str, Any]         # run, step / request, round / bytes, ...
    cpu: int = 0                # ns the thread ran inside it (0: not stamped)

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


_lock = threading.Lock()
_ring: "collections.deque[Record]" = collections.deque(maxlen=CAPACITY)
# name -> [count, total_s, max_s, cpu_s]
_totals: Dict[str, List[float]] = {}
_seq = itertools.count(1)
# .open: the innermost open Span, or None; .cpu: (perf_counter_ns,
# thread_time_ns) of the thread's last reading of its CPU clock
_tls = threading.local()


def _commit(recs) -> None:
    with _lock:
        for rec in recs:
            _ring.append(rec)
            row = _totals.get(rec.name)
            if row is None:
                row = _totals[rec.name] = [0, 0.0, 0.0, 0.0]
            s = rec.seconds
            row[0] += 1
            row[1] += s
            if s > row[2]:
                row[2] = s
            row[3] += rec.cpu * 1e-9


def _thread_cpu_ns(now: int) -> int:
    """The thread's CPU clock at ``now`` (``perf_counter_ns``): its last
    reading if that is under ``REUSE_NS`` old, else a new one."""
    last = getattr(_tls, "cpu", None)
    if last is not None and now - last[0] < REUSE_NS:
        return last[1]
    cpu = time.thread_time_ns()
    _tls.cpu = (now, cpu)
    return cpu


def _emit(outer: Optional["Span"], recs) -> None:
    """Commit ``recs``, or leave them with the nearest holding span open
    around them: records below a holding span wait for its verdict."""
    while outer is not None and outer._held is None:
        outer = outer.parent
    if outer is not None:
        outer._held.extend(recs)
    else:
        _commit(recs)


class Span:
    """One open span; what ``span()`` returns and ``with`` binds. ``ids``
    may be added to until it closes (an id known only later, as the
    request id in the HTTP handler, still lands in the record)."""

    __slots__ = ("name", "ids", "seq", "parent", "t0", "c0", "keep",
                 "_held", "_annotate", "_annotation")

    def __init__(self, name: str, annotate: bool, hold: bool, ids: dict):
        self.name, self.ids = name, ids
        self.keep = True
        self._held = [] if hold else None
        self._annotate, self._annotation = annotate, None

    def __enter__(self) -> "Span":
        outer = getattr(_tls, "open", None)
        self.parent = outer
        if outer is not None and outer.ids:
            self.ids = {**outer.ids, **self.ids}
        self.seq = next(_seq)
        _tls.open = self
        if self._annotate and TraceAnnotation.is_enabled():
            self._annotation = TraceAnnotation(self.name, seq=self.seq,
                                               **self.ids)
            self._annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        self.c0 = _thread_cpu_ns(self.t0)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        c1 = _thread_cpu_ns(t1)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        outer = self.parent
        _tls.open = outer
        rec = Record(self.seq, self.name, self.t0, t1,
                     outer.seq if outer is not None else None, self.ids,
                     c1 - self.c0)
        if self._held is None:
            _emit(outer, [rec])
        elif self.keep:
            _emit(outer, self._held + [rec])
        return False


def span(name: str, *, annotate: bool = True, hold: bool = False,
         **ids) -> Span:
    """``with span("serve.admit", request=7):`` — see the module's
    docstring. ``annotate=False`` keeps the span out of the profiler (a
    parent that covers its leaves would take their idle gaps, and so
    would a span of a thread that does not feed the device).
    ``hold=True`` keeps back this span's record and those below it until
    it closes, and drops them all if ``.keep`` was set false by then (a
    scheduler round that turned out idle)."""
    return Span(name, annotate, hold, ids)


def record(name: str, t0_s: float, t1_s: float, **ids) -> None:
    """A span after the fact, from two ``time.perf_counter()`` stamps the
    program already keeps (a request's wait in the queue). Recorder only:
    the profiler takes no event that has already ended; ``cpu`` stays 0,
    no thread was stamped."""
    outer = getattr(_tls, "open", None)
    if outer is not None and outer.ids:
        ids = {**outer.ids, **ids}
    rec = Record(next(_seq), name, int(t0_s * 1e9), int(t1_s * 1e9),
                 outer.seq if outer is not None else None, ids)
    _emit(outer, [rec])


def totals() -> Dict[str, Tuple[int, float, float, float]]:
    """``{name: (count, total_s, max_s, cpu_s)}`` since the process
    started."""
    with _lock:
        return {k: (int(v[0]), v[1], v[2], v[3])
                for k, v in _totals.items()}


def records(name: Optional[str] = None, **match) -> List[Record]:
    """The ring's records, oldest first, whose name is ``name`` (when
    given) and whose ids hold every ``match`` pair: ``records(run="timed")``,
    ``records("serve.admit", request=7)``."""
    with _lock:
        recs = list(_ring)
    return [r for r in recs
            if (name is None or r.name == name)
            and all(r.ids.get(k) == v for k, v in match.items())]
