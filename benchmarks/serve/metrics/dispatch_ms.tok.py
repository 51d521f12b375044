"""Compiled programs, host side: milliseconds per engine step spent
launching programs (``prefill.dispatch`` and ``decode.dispatch``, the
calls into the compiled prefill and decode chunks), averaged over the
steps that began in the traced window (``servebench.spans``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import spans  # noqa: E402


def read(run):
    steps = spans.steps(run)
    if not steps:
        return None
    return spans.wall_ns(spans.children(run, steps, spans.DISPATCH)) \
        / len(steps) / 1e6
