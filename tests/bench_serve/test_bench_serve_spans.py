"""The program's host span names against the ones the trace reduction
keeps."""
import os
import sys

SERVE = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "benchmarks", "serve"))
sys.path.insert(0, SERVE)

from servebench import tracereduce  # noqa: E402

from repro.launch import tracing  # noqa: E402


def test_program_spans_are_not_the_reductions_own():
    """The reduction keeps host events by these exact names and assumes
    they never overlap: no program span may take one."""
    kept = set(tracereduce.HOST_SPANS) | {tracereduce.WINDOW_SPAN}
    assert not kept & set(tracing.SPAN_NAMES)
    assert not any(n.startswith(("engine.", "servebench."))
                   for n in tracing.SPAN_NAMES)
