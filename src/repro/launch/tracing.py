"""Host spans of the serving path: one recorder, seen from two sides.

``with tracer.span(name, rid):`` records a :class:`Span`: wall start and
end on ``time.perf_counter_ns()`` (the clock of ``Request.t_*``), the
thread's CPU time over the span (wall much longer than CPU is time spent
waiting for the interpreter lock or the OS), an id, the id of the
innermost span open on the same thread when it began, and the request it
served.  The same ``with`` enters ``jax.profiler.TraceAnnotation(name)``,
so a profiler trace of the process shows every span on the host plane, on
the profiler's clock, beside the device's operations.  With no profiler
session the annotation costs a level check.

Spans are kept in a fixed-capacity ring, read by
:meth:`Tracer.spans_between`, and summed per name over the tracer's life
(:meth:`Tracer.totals`).  The engine and the HTTP front door record into
:func:`get_tracer`'s process-global instance unless given their own;
tests pass their own for isolation.  The names they record are
:data:`SPAN_NAMES`.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# What the engine thread records.  Front door (server.py, one engine-loop
# pass): wait for work, submit the pending batch, hand out the step's
# tokens, close finished streams.  Scheduler (engine.py, paged step):
# the step and, inside it, reap, admission, each prefill chunk's host
# preparation, launch, sync and first-token sampling, the decode chunk's
# preparation, launch and sync, and the token hand-out.  Everything
# outside the ``*.dispatch`` and ``*.sync`` spans runs with no program
# in flight.
SPAN_NAMES = (
    "front.wait", "front.intake", "front.emit", "front.terminal",
    "sched.step", "sched.reap", "sched.admit",
    "prefill.prep", "prefill.dispatch", "prefill.sync", "prefill.sample",
    "decode.prep", "decode.dispatch", "decode.sync", "sched.retire",
)
# about 40 spans an engine step: this holds several minutes of serving
CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    cpu_ns: int             # the thread's CPU time inside the span
    span_id: int
    parent_id: Optional[int]
    rid: Optional[int]      # the request the span served, if one

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class _Open:
    """One ``with tracer.span(...)``.  After the block, :attr:`span` is
    what was recorded (its clock readings time the block once, for
    callers that need the interval too)."""

    __slots__ = ("_tracer", "_name", "_rid", "_ann", "_stack", "_id",
                 "_parent", "_t0", "_c0", "span")

    def __init__(self, tracer: "Tracer", name: str, rid: Optional[int]):
        self._tracer = tracer
        self._name = name
        self._rid = rid
        self.span: Optional[Span] = None

    def __enter__(self) -> "_Open":
        self._stack = stack = self._tracer._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(self._tracer._ids)
        stack.append(self._id)
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        # the wall readings enclose the CPU readings
        self._t0 = time.perf_counter_ns()
        self._c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        c1 = time.thread_time_ns()
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        self.span = Span(self._name, self._t0, t1, c1 - self._c0, self._id,
                         self._parent, self._rid)
        self._tracer.add(self.span)
        return False


class Tracer:
    """A ring of the newest ``capacity`` spans plus per-name totals."""

    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._ring: "collections.deque[Span]" = collections.deque(
            maxlen=int(capacity))
        self._totals: Dict[str, List[int]] = {}
        # the latest start among the spans the ring has dropped
        self._lost_start_ns: Optional[int] = None
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, rid: Optional[int] = None) -> _Open:
        return _Open(self, name, rid)

    def add(self, span: Span) -> None:
        """Keep a finished span (each ``with`` calls this on exit)."""
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                lost = ring[0].start_ns
                if self._lost_start_ns is None or lost > self._lost_start_ns:
                    self._lost_start_ns = lost
            ring.append(span)
            tot = self._totals.get(span.name)
            if tot is None:
                tot = self._totals[span.name] = [0, 0, 0]
            tot[0] += 1
            tot[1] += span.end_ns - span.start_ns
            tot[2] += span.cpu_ns

    def spans_between(self, t0: float, t1: float) -> Optional[List[Span]]:
        """The finished spans that began in ``[t0, t1)`` (``perf_counter``
        seconds), in the order they ended; ``None`` once the ring has
        dropped a span that began at or after ``t0``, so that a partial
        window is never read as a whole one."""
        lo, hi = t0 * 1e9, t1 * 1e9
        with self._lock:
            if self._lost_start_ns is not None and self._lost_start_ns >= lo:
                return None
            return [s for s in self._ring if lo <= s.start_ns < hi]

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per span name since the tracer was made: ``count``, ``wall_s``
        and ``cpu_s``."""
        with self._lock:
            return {name: {"count": n, "wall_s": wall / 1e9,
                           "cpu_s": cpu / 1e9}
                    for name, (n, wall, cpu) in sorted(self._totals.items())}


_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer (engine and server default)."""
    return _GLOBAL
