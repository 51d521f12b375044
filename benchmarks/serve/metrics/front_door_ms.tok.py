"""Front door: milliseconds per engine step the HTTP server's engine
loop spends handing requests to the engine, streaming tokens out and
closing finished streams (``front.intake``, ``front.emit``,
``front.terminal``; not ``front.wait``, which waits for work), over the
steps that began in the traced window (``servebench.spans``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import spans  # noqa: E402


def read(run):
    steps = spans.steps(run)
    if not steps:
        return None
    return spans.wall_ns(spans.began_in_window(run, spans.FRONT)) \
        / len(steps) / 1e6
