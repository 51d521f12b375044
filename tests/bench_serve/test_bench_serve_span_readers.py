"""The per-layer readers of the program's host spans
(``servebench/spans.py``, ``metrics/{step_host,dispatch,front_door}_ms.tok``)
on a hand-made span list, and the cases in which they read nothing."""
import os
import sys

import pytest

SERVE = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "benchmarks", "serve"))
sys.path.insert(0, SERVE)

from servebench import cells  # noqa: E402
from servebench.record import Run  # noqa: E402

from repro.launch.tracing import Span, Tracer  # noqa: E402

NAMES = ("step_host_ms.tok", "dispatch_ms.tok", "front_door_ms.tok")
BASE = 1_000_000_000      # the traced window is 1.0 .. 1.1 s
MS = 1_000_000


def _span(name, a, b, sid, parent=None):
    return Span(name, BASE + a * MS, BASE + b * MS, 0, sid, parent, None)


# Steps 1 (10..50 ms) and 20 (61..95) lie inside the window; step 30
# (98..130) began inside it, so it counts with its children, which began
# after the window closed; step 40 (-20..5) began before it, so neither
# it nor its child that began inside the window counts.  Front-door spans
# count where they began inside the window; front.wait never counts.
SPANS = [
    _span("sched.step", -20, 5, 40),
    _span("decode.dispatch", 0, 3, 41, 40),
    _span("front.intake", -5, 2, 42),
    _span("sched.step", 10, 50, 1),
    _span("sched.reap", 10, 11, 2, 1),
    _span("prefill.prep", 11, 13, 3, 1),
    _span("prefill.dispatch", 13, 15, 4, 1),
    _span("prefill.sync", 15, 25, 5, 1),
    _span("prefill.sample", 25, 26, 6, 1),
    _span("decode.prep", 26, 28, 7, 1),
    _span("decode.dispatch", 28, 31, 8, 1),
    _span("decode.sync", 31, 47, 9, 1),
    _span("sched.retire", 47, 49, 10, 1),
    _span("front.emit", 50, 55, 11),
    _span("front.terminal", 55, 56, 12),
    _span("front.wait", 56, 60, 13),
    _span("front.intake", 60, 61, 14),
    _span("sched.step", 61, 95, 20),
    _span("decode.dispatch", 62, 64, 21, 20),
    _span("decode.sync", 64, 90, 22, 20),
    _span("sched.retire", 90, 93, 23, 20),
    _span("sched.step", 98, 130, 30),
    _span("decode.dispatch", 101, 104, 31, 30),
    _span("decode.sync", 104, 125, 32, 30),
    _span("front.emit", 130, 135, 33),
]


def _readers():
    return cells.load_metric_readers(list(NAMES))


def _run(spans, traced=True):
    return Run(cell="c", conf={}, mix={"loop": "closed"}, engine={},
               t0=0.5, t_end=2.0, requests=[], records=[],
               trace_t0=1.0 if traced else None,
               trace_t1=1.1 if traced else None, spans=spans)


def test_readers_average_their_spans_per_step():
    got = {n: r.read(_run(SPANS)) for n, r in _readers().items()}
    # host time with no program in flight: step wall less dispatch and
    # sync, 40 - 31, 34 - 28 and 32 - 24 ms over three steps
    assert got["step_host_ms.tok"] == pytest.approx((9 + 6 + 8) / 3)
    assert got["dispatch_ms.tok"] == pytest.approx((5 + 2 + 3) / 3)
    # emit 5 + terminal 1 + intake 1 ms
    assert got["front_door_ms.tok"] == pytest.approx((5 + 1 + 1) / 3)


def test_readers_read_nothing_without_a_trace():
    for name, r in _readers().items():
        assert r.read(_run(None, traced=False)) is None, name


def test_readers_read_nothing_once_the_ring_dropped_a_span():
    """What ``bench.py`` hands the run: the tracer's spans from the
    traced window's start, ``None`` once its ring dropped one of them."""
    tracer = Tracer(capacity=4)
    for s in SPANS[3:9]:
        tracer.add(s)
    spans = tracer.spans_between(1.0, 2.0)
    assert spans is None
    for name, r in _readers().items():
        assert r.read(_run(spans)) is None, name


def test_readers_read_nothing_without_a_step():
    front = [s for s in SPANS if s.name.startswith("front.")]
    for name, r in _readers().items():
        assert r.read(_run(front)) is None, name
