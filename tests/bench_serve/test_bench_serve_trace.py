"""The trace reduction (``servebench/tracereduce.py``): idle share,
per-program device time and gap attribution, on a small hand-made trace
and on a small trace recorded on a TPU v5e."""
import json
import os
import sys

import pytest

SERVE = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "benchmarks", "serve"))
sys.path.insert(0, SERVE)

from servebench import tracereduce as tr  # noqa: E402

DEV = "/device:TPU:0"
MS = 1_000_000


def _ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, int(start_ms * MS), int(dur_ms * MS))


# window 0..100 ms.  Device: a prefill chunk 10..20, a decode chunk
# 30..70 (two ops that overlap), an op 95..110 running past the window.
# Host: engine.step 5..75 and 90..100, engine.submit 78..80.
EVENTS = [
    _ev(tr.HOST_PLANE, "python3", tr.WINDOW_SPAN, 0, 100),
    _ev(tr.HOST_PLANE, "python3", "engine.step", 5, 70),
    _ev(tr.HOST_PLANE, "python3", "engine.submit", 78, 2),
    _ev(tr.HOST_PLANE, "python3", "engine.step", 90, 10),
    _ev(tr.HOST_PLANE, "python3", "unrelated", 0, 100),
    _ev(DEV, tr.MODULES_LINE, "jit_ngraph_m_paged_prefill32(7)", 10, 10),
    _ev(DEV, tr.OPS_LINE, "fusion.1", 10, 10),
    _ev(DEV, tr.MODULES_LINE, "jit_ngraph_m_paged_chunk4(3)", 30, 40),
    _ev(DEV, tr.OPS_LINE, "fusion.2", 30, 30),
    _ev(DEV, tr.OPS_LINE, "convolution.3", 50, 20),
    _ev(DEV, tr.MODULES_LINE, "jit_ngraph_m_paged_chunk4(3)", 95, 15),
    _ev(DEV, tr.OPS_LINE, "fusion.2", 95, 15),
    _ev(DEV, "Steps", "ignored", 0, 100),
]


def test_idle_share_is_one_minus_the_union_of_ops():
    s = tr.reduce(EVENTS)
    assert s.window_s == pytest.approx(0.1)
    # busy: 10..20, 30..70, 95..100 (clipped) = 55 ms
    assert s.busy_s == pytest.approx(0.055)
    assert s.idle_share == pytest.approx(0.45)
    assert s.devices == 1


def test_program_time_per_run():
    s = tr.reduce(EVENTS)
    d, p = s.programs["decode_chunk"], s.programs["prefill_chunk"]
    # both decode runs start inside the window; their whole device time
    assert d.runs == 2 and d.device_s == pytest.approx(0.055)
    assert d.mean_ms == pytest.approx(27.5)
    assert p.runs == 1 and p.mean_ms == pytest.approx(10.0)


def test_top_ops_are_clipped_to_the_window():
    s = tr.reduce(EVENTS)
    ops = dict(s.device_ops)
    # named by the program that ran them
    assert ops["m_paged_chunk4:fusion.2"] == pytest.approx(0.035)
    assert ops["m_paged_chunk4:convolution.3"] == pytest.approx(0.02)
    assert ops["m_paged_prefill32:fusion.1"] == pytest.approx(0.01)
    assert [n for n, _ in s.device_ops][0] == "m_paged_chunk4:fusion.2"


def test_gaps_are_attributed_to_what_the_host_did():
    s = tr.reduce(EVENTS)
    gaps = dict(s.idle_gaps)
    # gaps 0..10, 20..30 and 70..95; steps cover 5..10, 20..30, 70..75
    # and 90..95, the submit 78..80, nothing the other 18 ms
    assert gaps[tr.IN_STEP] == pytest.approx(0.025)
    assert gaps["engine.submit"] == pytest.approx(0.002)
    assert gaps[tr.WAITING] == pytest.approx(0.018)
    assert sum(gaps.values()) == pytest.approx(0.045)
    assert s.idle_gaps[0][0] == tr.IN_STEP


def test_gap_with_no_host_span():
    events = [_ev(tr.HOST_PLANE, "t", tr.WINDOW_SPAN, 0, 10),
              _ev(DEV, tr.OPS_LINE, "fusion.1", 0, 4)]
    s = tr.reduce(events)
    assert dict(s.idle_gaps) == {tr.WAITING: pytest.approx(0.006)}


def test_a_trace_without_a_tpu_plane_is_an_error():
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce([_ev(tr.HOST_PLANE, "t", tr.WINDOW_SPAN, 0, 10)])


def test_window_span_must_be_unique():
    with pytest.raises(ValueError, match="one"):
        tr.reduce([_ev(DEV, tr.OPS_LINE, "f", 0, 1)])


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: a 2048-wide tanh 'decode chunk'
    and a 512-row 'prefill chunk', five of each, under the benchmark's
    own window and step spans (events kept as :func:`load_xplane` keeps
    them).  The device clock runs about 1 ms ahead of the host spans, so
    the first pair starts before the window span does."""
    path = os.path.join(os.path.dirname(__file__), "v5e_probe_trace.json")
    with open(path) as fh:
        events = [tuple(e) for e in json.load(fh)]
    s = tr.reduce(events)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.0352466, rel=1e-4)
    d, p = s.programs["decode_chunk"], s.programs["prefill_chunk"]
    assert (d.runs, p.runs) == (4, 4)
    assert d.mean_ms == pytest.approx(0.0095, rel=0.05)
    assert p.mean_ms == pytest.approx(0.0238, rel=0.05)
    # the chip is busy ~0.13 ms of 35 ms; every idle second is attributed
    assert 0.99 < s.idle_share < 1.0
    gaps = dict(s.idle_gaps)
    assert set(gaps) == {tr.IN_STEP, tr.WAITING}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.device_ops[0][0].startswith("model_paged_prefill32:%fusion")
