"""Host spans (repro.launch.tracing): the recorder's nesting, ring and
totals, and the spans a paged engine behind the HTTP front door records
while it serves: the documented names and no others, each step's phases
nested inside it without overlap, prefill spans tagged with their
request, and the engine's own timings read from the spans' clock."""
import threading

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import loadgen
from repro.launch.engine import LAT_SAMPLES, ServeEngine
from repro.launch.server import running_server
from repro.launch.tracing import SPAN_NAMES, Span, Tracer, get_tracer

CFG = get_config("deepseek-7b").reduced()


def _engine(tracer, slots=2, max_len=32):
    return ServeEngine(CFG, slots=slots, max_len=max_len, mode="paged",
                       seed=0, page_size=4, chunk_steps=3, prefill_chunk=4,
                       tracer=tracer)


def _one_span(tr, name):
    with tr.span(name) as open_span:
        pass
    return open_span.span


def test_spans_nest_per_thread_and_sum_per_name():
    tr = Tracer()
    other = []
    with tr.span("outer") as outer:
        with tr.span("inner", rid=7) as inner:
            pass
        # another thread's stack is its own: "outer" is not open there
        t = threading.Thread(
            target=lambda: other.append(_one_span(tr, "elsewhere")))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    o, i = outer.span, inner.span
    assert o.parent_id is None and i.parent_id == o.span_id
    assert other[0].parent_id is None
    assert i.rid == 7 and o.rid is None
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert 0 <= i.cpu_ns
    tot = tr.totals()
    assert set(tot) == {"outer", "inner", "elsewhere"}
    assert tot["inner"]["count"] == 1
    assert tot["outer"]["wall_s"] == pytest.approx(o.seconds)


def test_a_span_that_raised_is_kept_and_closed():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    with tr.span("after") as after:
        pass
    assert after.span.parent_id is None
    assert [s.name for s in tr.spans_between(0.0, float("inf"))] == [
        "boom", "after"]


def test_ring_keeps_the_newest_and_refuses_a_dropped_window():
    tr = Tracer(capacity=4)
    for k in range(10):
        tr.add(Span("s", k * 10**9, k * 10**9 + 5, 1, k + 1, None, None))
    # the spans that began at 0..5 s were dropped
    assert tr.spans_between(0.0, 100.0) is None
    assert [s.span_id for s in tr.spans_between(6.0, 100.0)] == [7, 8, 9, 10]
    assert [s.span_id for s in tr.spans_between(6.0, 8.0)] == [7, 8]
    assert tr.spans_between(5.0, 100.0) is None
    assert tr.totals()["s"]["count"] == 10


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_served_requests_record_every_documented_span():
    """Three clients on two slots, prompts of three prefill chunks: the
    engine thread records every name in SPAN_NAMES and no other; each
    step's phases nest inside it and do not overlap; prefill spans name
    their request; /v1/metrics reports the totals."""
    tr = Tracer()
    assert get_tracer() is not tr
    P, G, n = 10, 5, 3
    prompts = loadgen.make_prompts(n, P, CFG.vocab, seed=1)
    eng = _engine(tr)
    with running_server(eng, max_wait_queue=n) as srv:
        res = loadgen.run_load(srv.base_url, prompts, G)
        assert res.statuses == {200: n} and not res.errors
        metrics = loadgen.fetch_json(srv.base_url, "/v1/metrics")
    spans = tr.spans_between(0.0, float("inf"))
    named = _by_name(spans)
    assert set(named) == set(SPAN_NAMES)
    assert len(SPAN_NAMES) == len(set(SPAN_NAMES))

    by_id = {s.span_id: s for s in spans}
    for s in named["sched.step"]:
        assert s.parent_id is None
        kids = sorted((c for c in spans if c.parent_id == s.span_id),
                      key=lambda c: c.start_ns)
        for c in kids:
            assert s.start_ns <= c.start_ns <= c.end_ns <= s.end_ns
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
    for name in SPAN_NAMES:
        if name.startswith(("front.", "sched.step")):
            assert all(s.parent_id is None for s in named[name]), name
        else:
            assert all(by_id[s.parent_id].name == "sched.step"
                       for s in named[name]), name

    rids = set(srv.engine._requests)
    for name in ("prefill.prep", "prefill.dispatch", "prefill.sync",
                 "prefill.sample"):
        assert {s.rid for s in named[name]} == rids, name
    # a ten-token prompt is two whole chunks of four, which share one
    # batched dispatch (with another prompt's where both were admitted
    # in one step), and a two-token tail dispatched alone; one
    # first-token sample each
    assert -(-2 * n // 4) + n <= len(named["prefill.dispatch"]) <= 2 * n
    assert len(named["prefill.sample"]) == n

    tot = metrics["engine"]["spans"]
    assert tot["sched.step"]["count"] >= 1
    assert set(tot) <= set(SPAN_NAMES)
    for v in tot.values():
        assert v["wall_s"] >= 0 and v["cpu_s"] >= 0


def test_engine_times_each_interval_once_through_its_spans():
    """The paged engine's decode and prefill seconds are the spans'
    own intervals; its latency samples are bounded."""
    tr = Tracer()
    eng = _engine(tr)
    rng = np.random.default_rng(2)
    for p, g in [(10, 6), (5, 4), (7, 8)]:
        eng.submit(rng.integers(0, CFG.vocab, size=(p,)), g)
    rep = eng.run()
    named = _by_name(tr.spans_between(0.0, float("inf")))
    dispatch = {s.parent_id: s for s in named["decode.dispatch"]}
    sync = {s.parent_id: s for s in named["decode.sync"]}
    assert set(dispatch) == set(sync) and len(sync) == rep.steps
    decode_ns = sum(sync[k].end_ns - dispatch[k].start_ns for k in sync)
    assert eng.decode_seconds == pytest.approx(decode_ns / 1e9)
    preps = sorted(named["prefill.prep"], key=lambda s: s.start_ns)
    syncs = sorted(named["prefill.sync"], key=lambda s: s.start_ns)
    prefill_ns = sum(b.end_ns - a.start_ns for a, b in zip(preps, syncs))
    assert rep.prefill_seconds == pytest.approx(prefill_ns / 1e9)
    assert rep.decode_tok_s > 0 and rep.p95_ms >= rep.p50_ms > 0
    assert eng.lat_ms.maxlen == LAT_SAMPLES


def test_a_small_ring_holds_its_bound_while_serving():
    tr = Tracer(capacity=16)
    eng = _engine(tr)
    eng.submit(np.arange(10, dtype=np.int32), 6)
    eng.run()
    assert len(tr._ring) == 16
    assert tr.totals()["sched.step"]["count"] > 0
    assert tr.spans_between(0.0, float("inf")) is None
