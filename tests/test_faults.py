"""Request-lifecycle fault tolerance: the FaultInjector's deterministic
rules, cancellation returning slots/pages with exact accounting,
deadlines as a distinct terminal status, and step-failure containment
(degraded, never silently dead)."""
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.engine import ServeEngine
from repro.launch.faults import FaultError, FaultInjector

CFG = get_config("deepseek-7b").reduced()


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab, size=(n,)).astype(np.int32)


def _paged(slots=2, max_len=16, faults=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("chunk_steps", 3)
    return ServeEngine(CFG, slots=slots, max_len=max_len, mode="paged",
                      seed=0, faults=faults, **kw)


# -- the injector itself ------------------------------------------------------
def test_injector_spec_parsing_and_validation():
    inj = FaultInjector("dispatch.raise=after:3,admit.reject=prob:0.5,"
                        "dispatch.delay=every:4:0.25")
    assert inj.enabled("dispatch.raise")
    assert not inj.enabled("client.disconnect_after_n")
    assert inj.value("dispatch.delay", 0.0) == 0.25
    for bad in ("nope=after:1",            # unknown site
                "dispatch.raise=sometimes:1",  # unknown mode
                "admit.reject=prob:1.5",   # prob out of range
                "dispatch.raise=after:0",  # count < 1
                "dispatch.raise=after:x",  # non-numeric
                "dispatch.raise"):         # no rule at all
        with pytest.raises(ValueError):
            FaultInjector(bad)
    # empty spec = nothing enabled, every hook a no-op
    off = FaultInjector("")
    assert not off.fire("dispatch.raise")
    off.check("dispatch.raise")  # must not raise


def test_injector_counted_modes_fire_deterministically():
    inj = FaultInjector("dispatch.raise=after:3")
    assert [inj.fire("dispatch.raise") for _ in range(5)] == \
        [False, False, True, False, False]
    inj = FaultInjector("admit.reject=first:2")
    assert [inj.fire("admit.reject") for _ in range(4)] == \
        [True, True, False, False]
    inj = FaultInjector("dispatch.delay=every:2")
    assert [inj.fire("dispatch.delay") for _ in range(4)] == \
        [False, True, False, True]
    inj.configure("dispatch.raise=after:1")
    with pytest.raises(FaultError):
        inj.check("dispatch.raise")
    assert inj.stats() == {"dispatch.raise": {"calls": 1, "fired": 1}}


def test_injector_prob_rules_are_seeded():
    a = FaultInjector("admit.reject=prob:0.5", seed=7)
    b = FaultInjector("admit.reject=prob:0.5", seed=7)
    seq_a = [a.fire("admit.reject") for _ in range(64)]
    seq_b = [b.fire("admit.reject") for _ in range(64)]
    assert seq_a == seq_b           # same seed -> same schedule
    assert True in seq_a and False in seq_a


# -- cancellation -------------------------------------------------------------
def test_paged_cancel_active_returns_pages_exactly():
    """Cancelling a mid-flight request retires it at the next chunk
    boundary with its pages back in the pool, while the survivor decodes
    token-for-token what a solo run produces."""
    rng = np.random.default_rng(0)
    pa, pb = _prompt(rng, 4), _prompt(rng, 6)
    solo = _paged()
    rb_solo = solo.submit(pb, 8)
    ref = list(solo.run().results[rb_solo])

    eng = _paged()
    ra = eng.submit(pa, 10)
    rb = eng.submit(pb, 8)
    eng.step()  # both admitted, first chunk decoded
    got_a = len(eng._requests[ra].tokens)
    assert got_a > 0 and eng.pool.active == 2
    assert eng.cancel(ra, "user hit stop") is True
    eng.step()  # boundary: the cancel takes effect before dispatch
    req_a = eng._requests[ra]
    assert req_a.status == "cancelled" and req_a.slot is None
    assert req_a.error == "user hit stop"
    assert len(req_a.tokens) == got_a  # kept what was generated
    assert eng.pool.active == 1
    assert eng.pool.verify() == []
    # exact page accounting: outstanding pages belong to rb alone
    assert eng.pool.page_allocs - eng.pool.page_frees == \
        eng.pool.pages_in_use
    rep = eng.run()
    assert list(rep.results[rb]) == ref
    assert rep.statuses == {ra: "cancelled", rb: "completed"}
    assert rep.errors == {ra: "user hit stop"}
    assert rep.counters["cancelled"] == 1 and rep.counters["completed"] == 1
    assert rep.health == "ok"
    assert eng.pool.pages_in_use == 0 and eng.pool.active == 0
    # double-cancel of a terminal request is a no-op, unknown rid raises
    assert eng.cancel(ra) is False
    with pytest.raises(KeyError):
        eng.cancel(999)


def test_cancel_queued_request_is_immediate():
    rng = np.random.default_rng(1)
    eng = _paged(slots=1)
    ra = eng.submit(_prompt(rng, 4), 6)
    rb = eng.submit(_prompt(rng, 4), 6)  # waits: one slot
    assert eng.cancel(rb) is True
    assert eng._requests[rb].status == "cancelled"
    assert eng.queue_depth == 0 or rb not in eng._queue
    rep = eng.run()
    assert rep.statuses[ra] == "completed"
    assert len(rep.results[ra]) == 6 and len(rep.results[rb]) == 0
    assert eng.pool.pages_in_use == 0


def test_continuous_cancel_frees_slot():
    rng = np.random.default_rng(2)
    eng = ServeEngine(CFG, slots=2, max_len=16, mode="continuous", seed=0)
    ra = eng.submit(_prompt(rng, 4), 10)
    rb = eng.submit(_prompt(rng, 4), 4)
    eng.step()
    assert eng.cancel(ra) is True
    eng.step()
    assert eng._requests[ra].status == "cancelled"
    assert eng.pool.active == 1 and eng.pool.verify() == []
    rep = eng.run()
    assert rep.statuses[rb] == "completed"
    assert (eng.pool.allocs, eng.pool.frees, eng.pool.active) == (2, 2, 0)


def test_lockstep_cancel_reaches_only_queued_requests():
    rng = np.random.default_rng(3)
    eng = ServeEngine(CFG, slots=2, max_len=12, mode="lockstep", seed=0)
    ra = eng.submit(_prompt(rng, 4), 4)
    rb = eng.submit(_prompt(rng, 4), 4)
    assert eng.cancel(rb) is True  # still queued: cancellable
    rep = eng.run()
    assert rep.statuses == {ra: "completed", rb: "cancelled"}
    assert eng.cancel(ra) is False  # already ran to completion


# -- deadlines ----------------------------------------------------------------
def test_deadline_expires_in_queue():
    rng = np.random.default_rng(4)
    eng = _paged(slots=1)
    rid = eng.submit(_prompt(rng, 4), 6, deadline_s=1e-6)
    time.sleep(0.01)
    rep = eng.run()
    assert rep.statuses[rid] == "deadline_exceeded"
    assert "before admission" in rep.errors[rid]
    assert len(rep.results[rid]) == 0
    assert rep.counters["deadline_exceeded"] == 1
    assert eng.pool.pages_in_use == 0


def test_deadline_expires_mid_flight_keeps_tokens():
    rng = np.random.default_rng(5)
    eng = _paged(slots=1, max_len=40, chunk_steps=1)
    rid = eng.submit(_prompt(rng, 4), 32)
    eng.step()
    got = len(eng._requests[rid].tokens)
    assert got > 0
    eng._requests[rid].deadline = 0.0  # expire it, deterministically
    eng.step()
    req = eng._requests[rid]
    assert req.status == "deadline_exceeded" and req.slot is None
    assert len(req.tokens) >= got
    assert eng.pool.pages_in_use == 0 and eng.pool.verify() == []
    rep = eng.run()
    assert rep.counters["deadline_exceeded"] == 1
    assert "after" in rep.errors[rid]


def test_deadline_validation():
    rng = np.random.default_rng(6)
    eng = _paged()
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(_prompt(rng, 4), 4, deadline_s=0)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.check_request(4, 4, deadline_s=-2)


# -- step-failure containment -------------------------------------------------
def test_dispatch_failure_contained_and_engine_degraded():
    """A dispatch that raises fails the in-flight requests with a
    structured error, keeps exact pool accounting, drops health to
    degraded — and the engine still serves fresh requests correctly."""
    rng = np.random.default_rng(7)
    pa = _prompt(rng, 4)
    solo = _paged()
    rs = solo.submit(pa, 6)
    ref = list(solo.run().results[rs])

    eng = _paged(faults=FaultInjector("dispatch.raise=after:2"))
    ra = eng.submit(pa, 8)
    eng.step()           # dispatch 1: fine
    emitted = eng.step()  # dispatch 2: injected FaultError
    assert emitted == []
    req = eng._requests[ra]
    assert req.status == "failed" and req.slot is None
    assert "FaultError" in req.error and "dispatch failed" in req.error
    assert eng.health == "degraded"
    assert eng.counters["engine_errors"] == 1
    assert eng.counters["failed"] == 1
    assert eng.pool.verify() == []
    assert eng.pool.pages_in_use == 0 and eng.pool.active == 0
    # degraded still serves: a fresh request decodes exactly right
    rb = eng.submit(pa, 6)
    rep = eng.run()
    assert list(rep.results[rb]) == ref
    assert rep.statuses[rb] == "completed"
    assert rep.health == "degraded"
    assert rep.counters == {"completed": 1, "cancelled": 0,
                            "deadline_exceeded": 0, "failed": 1,
                            "engine_errors": 1}


@pytest.mark.parametrize("injected", [True, False])
def test_serve_cli_exit_code_reports_contained_failures(injected):
    """Containment keeps the engine serving, so the CLI's exit code is
    where a real dispatch failure shows: non-zero unless it was an
    injected fault."""
    from repro.launch.serve import _exit_code

    rng = np.random.default_rng(9)
    if injected:
        eng = _paged(faults=FaultInjector("dispatch.raise=after:1"))
    else:
        eng = _paged(faults=FaultInjector())

        class _Broken:
            def raw(self, *args):
                raise RuntimeError("device lost")

        eng.cf = _Broken()
    ra = eng.submit(_prompt(rng, 4), 6)
    rep = eng.run()
    assert rep.statuses[ra] == "failed"
    assert rep.counters["engine_errors"] == 1
    assert _exit_code(eng) == (0 if injected else 1)


def test_lockstep_dispatch_failure_contained():
    rng = np.random.default_rng(8)
    eng = ServeEngine(CFG, slots=2, max_len=12, mode="lockstep", seed=0,
                      faults=FaultInjector("dispatch.raise=after:1"))
    ra = eng.submit(_prompt(rng, 4), 4)
    rep = eng.run()
    assert rep.statuses[ra] == "failed"
    assert "FaultError" in rep.errors[ra]
    assert rep.health == "degraded" and rep.counters["engine_errors"] == 1


def test_containment_failure_halts_engine(monkeypatch):
    """If even re-arming the pool fails, the engine halts: submit and
    step refuse instead of serving from unknown state."""
    rng = np.random.default_rng(9)
    eng = _paged(faults=FaultInjector("dispatch.raise=after:1"))
    ra = eng.submit(_prompt(rng, 4), 6)

    def boom(*a, **kw):
        raise RuntimeError("no memory")
    monkeypatch.setattr(eng.pool, "reset_buffers", boom)
    monkeypatch.setattr(eng.pool, "rebuild", boom)
    eng.step()
    assert eng.health == "halted"
    assert eng._requests[ra].status == "failed"
    with pytest.raises(RuntimeError, match="halted"):
        eng.submit(_prompt(rng, 4), 4)
    with pytest.raises(RuntimeError, match="halted"):
        eng.step()
    rb_missing = eng.run()  # report still works; queue already empty
    assert rb_missing.health == "halted"


def test_admit_reject_site_gates_can_admit():
    eng = _paged(faults=FaultInjector("admit.reject=first:1"))
    assert eng.can_admit(4, 4) is False   # injected rejection
    assert eng.can_admit(4, 4) is True    # back to normal
    stats = eng.faults.stats()["admit.reject"]
    assert stats == {"calls": 2, "fired": 1}
