"""The program's host spans (``Run.spans``), reduced per engine step.

The engine thread records each paged engine step as one ``sched.step``
span whose children are its phases (``sched.reap/admit/retire``,
``prefill.prep/dispatch/sync/sample``, ``decode.prep/dispatch/sync``),
and between steps the front door's ``front.wait/intake/emit/terminal``
(``repro.launch.tracing.SPAN_NAMES``).  Only ``*.dispatch`` and
``*.sync`` run with a program in flight.

A step counts where its ``sched.step`` began inside the traced window,
with all of its children; a front-door span counts where it began
inside the window.  Spans are read by their fields alone (``name``,
``start_ns``, ``end_ns``, ``span_id``, ``parent_id``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

STEP = "sched.step"
DISPATCH = ("prefill.dispatch", "decode.dispatch")
SYNC = ("prefill.sync", "decode.sync")
FRONT = ("front.intake", "front.emit", "front.terminal")


def began_in_window(run, names: Sequence[str]) -> List:
    """The spans named ``names`` that began inside the traced window."""
    lo, hi = run.trace_t0 * 1e9, run.trace_t1 * 1e9
    return [s for s in run.spans if s.name in names and lo <= s.start_ns < hi]


def steps(run) -> Optional[List]:
    """The window's engine steps; None where the run holds no spans (not
    traced, or the tracer's ring dropped some) or no step began."""
    if run.spans is None or run.trace_t0 is None:
        return None
    return began_in_window(run, (STEP,)) or None


def wall_ns(spans) -> int:
    return sum(s.end_ns - s.start_ns for s in spans)


def children(run, parents, names: Sequence[str]) -> List:
    """The spans named ``names`` whose parent is one of ``parents``."""
    ids = {p.span_id for p in parents}
    return [s for s in run.spans if s.parent_id in ids and s.name in names]
