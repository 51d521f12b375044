"""Scheduler: host milliseconds per engine step with no program in
flight: each ``sched.step`` span's wall time less its ``*.dispatch`` and
``*.sync`` children (so its self time, ``*.prep``, ``*.sample`` and
``sched.reap/admit/retire``), averaged over the steps that began in the
traced window (``servebench.spans``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import spans  # noqa: E402


def read(run):
    steps = spans.steps(run)
    if not steps:
        return None
    busy = spans.children(run, steps, spans.DISPATCH + spans.SYNC)
    return (spans.wall_ns(steps) - spans.wall_ns(busy)) / len(steps) / 1e6
