"""Copy-on-write prefix sharing + in-graph chunked prefill (PR 9).

Two layers of proof.  Host-level: a property-style test drives the
``PagedKVPool`` through random admit/advance/share/COW/publish/free
sequences and asserts ``verify()`` stays clean after *every* operation,
with per-page refcounts exactly matching the live page-table references.
Engine-level: chunked prefill must be token-for-token identical to the
legacy dense prefill at every chunk size (including ragged tails), and
prefix sharing must be invisible to greedy outputs while actually
sharing (``shared_attaches``/``cow_copies`` move, peak pages drop).
"""
from collections import Counter

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.engine import PagedKVPool, ServeEngine

CFG = get_config("deepseek-7b").reduced()


class _T:
    """Stand-in for a compiled input type (shape/dtype/nbytes)."""

    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize


def _refs_match_tables(pool) -> None:
    """Per-page refcounts must equal the live page-table references."""
    held = Counter(pid for pages in pool._slot_pages for pid in pages)
    assert dict(pool._page_refs) == dict(held)
    # and the visible table agrees with the internal page lists
    for slot, pages in enumerate(pool._slot_pages):
        assert list(pool.page_table[slot, :len(pages)]) == pages


def test_prefix_pool_property_random_sequences():
    """Random admit/advance/free sequences over prompts with shared
    prefixes: the exact-accounting invariant (``verify()`` empty) and
    the refcount == live-references identity hold after every single
    pool operation, and the pool drains to zero."""
    rng = np.random.default_rng(42)
    ps = 4
    # a tiny prompt family sharing full-page prefixes, so attaches and
    # COW fire constantly: common is a shared 8-token (2-page) system
    # prompt; variants extend or exactly match it
    common = rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)
    family = [
        common.copy(),                                            # exact
        np.concatenate([common,
                        rng.integers(0, CFG.vocab, size=(3,))]).astype(
                            np.int32),                            # extends
        np.concatenate([common,
                        rng.integers(0, CFG.vocab, size=(6,))]).astype(
                            np.int32),                            # extends
        rng.integers(0, CFG.vocab, size=(7,)).astype(np.int32),   # unrelated
    ]
    pool = PagedKVPool(["k", "v"], [_T((2, 25, 1, ps, 2))] * 2,
                       slots=3, page_size=ps, max_pages=6)
    live = {}  # slot -> dict(prompt, pos, total, published)

    def _check():
        assert pool.verify() == []
        _refs_match_tables(pool)

    for _ in range(400):
        op = rng.choice(["admit", "advance", "advance", "free"])
        if op == "admit" and len(live) < pool.slots:
            prompt = family[rng.integers(len(family))]
            total = len(prompt) + int(rng.integers(1, 9))
            covered, reusable = pool.probe_shared(prompt)
            if not pool.can_admit(total, shared_pages=reusable):
                continue
            slot = pool.alloc(total, shared_pages=reusable)
            covered = pool.share_prefix(slot, prompt)
            live[slot] = dict(prompt=prompt,
                              pos=min(covered, len(prompt) - 1),
                              total=total, published=False)
            _check()
        elif op == "advance" and live:
            slot = int(rng.choice(sorted(live)))
            st = live[slot]
            hi = min(st["pos"] + int(rng.integers(1, 5)), st["total"])
            if hi <= st["pos"]:
                continue
            pool.ensure_pages(slot, hi - 1)
            _check()
            pool.prepare_writes(slot, st["pos"], hi - 1)
            pool.note_used(slot, hi)
            st["pos"] = hi
            _check()
            if st["pos"] >= len(st["prompt"]) and not st["published"]:
                pool.publish_prefix(slot, st["prompt"])
                st["published"] = True
                _check()
        elif op == "free" and live:
            slot = int(rng.choice(sorted(live)))
            pool.free(slot)
            del live[slot]
            _check()
    for slot in sorted(live):
        pool.free(slot)
        _check()
    p = pool.stats()
    assert p.pages_in_use == 0 and p.active == 0
    assert p.page_allocs == p.page_frees
    assert p.ref_allocs == p.ref_frees
    assert p.shared_attaches > 0 and p.cow_copies > 0, \
        "the prompt family must actually exercise sharing and COW"


def test_can_admit_discounts_shared_pages():
    """A shared-prefix request fits into a pool that could not hold it
    privately: ``probe_shared`` credits the attachable pages."""
    ps = 4
    prompt = np.arange(8, dtype=np.int32)
    # 6 physical pages: trash + 5 usable; publisher takes 3 (8 prompt
    # rows -> 2 pages + reservation for 4 decode rows)
    pool = PagedKVPool(["k"], [_T((2, 6, 1, ps, 2))],
                       slots=2, page_size=ps, max_pages=3)
    s = pool.alloc(12)
    pool.share_prefix(s, prompt)  # nothing indexed yet: no-op attach
    pool.ensure_pages(s, 7)
    pool.prepare_writes(s, 0, 7)
    pool.publish_prefix(s, prompt)
    covered, reusable = pool.probe_shared(prompt)
    assert covered == 8 and reusable == 1  # last page re-read under COW
    # privately the second request needs 3 pages but only 2 remain...
    assert not pool.can_admit(12)
    # ...yet it is admissible when its shared prefix page is credited
    assert pool.can_admit(12, shared_pages=reusable)
    s2 = pool.alloc(12, shared_pages=reusable)
    assert pool.share_prefix(s2, prompt) == 8
    assert pool.verify() == []
    pool.free(s2)
    pool.free(s)
    assert pool.stats().pages_in_use == 0 and pool.verify() == []


@pytest.fixture(scope="module")
def long_prompt_reference():
    """Continuous-mode greedy tokens for one 13-token prompt."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab, size=(13,)).astype(np.int32)
    eng = ServeEngine(CFG, slots=1, max_len=20, mode="continuous", seed=0)
    rid = eng.submit(prompt, 6)
    return prompt, [int(t) for t in eng.run().results[rid]]


@pytest.mark.parametrize("chunk", [1, 3, 7, 0])
def test_chunked_prefill_matches_dense_prefill(long_prompt_reference,
                                               chunk):
    """In-graph paged prefill is exact at every chunk size — including
    chunks that divide the prompt raggedly (13 = 7 + 6, 3*4 + 1) — and
    ``prefill_chunk=0`` restores the legacy dense prefill; all decode
    the continuous-mode token stream."""
    prompt, ref = long_prompt_reference
    eng = ServeEngine(CFG, slots=1, max_len=20, mode="paged", seed=0,
                      page_size=4, chunk_steps=2, prefill_chunk=chunk)
    rid = eng.submit(prompt, 6)
    rep = eng.run()
    assert [int(t) for t in rep.results[rid]] == ref
    assert rep.pool.pages_in_use == 0
    assert eng.pool.verify() == []


@pytest.mark.parametrize("prefill_chunk", [None, 0])
def test_shared_prefix_parity_and_counters(prefill_chunk):
    """Three requests with an identical page-aligned prompt: greedy
    outputs match a solo run exactly, sharing actually happens
    (attaches > 0, the re-processed last page COWs), the peak physical
    footprint undercuts the unshared run, and the drain returns every
    refcounted page — for both the chunked and the dense prefill path."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)
    kw = {} if prefill_chunk is None else dict(prefill_chunk=prefill_chunk)

    solo = ServeEngine(CFG, slots=3, max_len=16, mode="paged", seed=0,
                       page_size=4, chunk_steps=2, **kw)
    rid = solo.submit(prompt, 8)
    ref = [int(t) for t in solo.run().results[rid]]

    def _run(sharing):
        eng = ServeEngine(CFG, slots=3, max_len=16, mode="paged", seed=0,
                          page_size=4, chunk_steps=2,
                          prefix_sharing=sharing, **kw)
        rids = [eng.submit(prompt, 8) for _ in range(3)]
        rep = eng.run()
        assert all([int(t) for t in rep.results[r]] == ref for r in rids)
        assert rep.pool.pages_in_use == 0 and rep.pool.active == 0
        assert rep.pool.ref_allocs == rep.pool.ref_frees
        assert eng.pool.verify() == []
        return rep.pool

    shared, unshared = _run(True), _run(False)
    assert shared.shared_attaches >= 4 and shared.cow_copies >= 2
    assert unshared.shared_attaches == 0 and unshared.cow_copies == 0
    assert shared.peak_pages_in_use < unshared.peak_pages_in_use


def test_cancel_mid_prefill_releases_shared_pages():
    """A sharer cancelled mid-prefill-chunk (holding attached prefix
    pages) must decrement refcounts exactly once; the publisher keeps
    decoding and every page returns on drain."""
    rng = np.random.default_rng(5)
    base = rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)
    # the sharer's 36 own tokens are nine chunks: a batched dispatch
    # runs four of them a step, so two steps after it attaches one is
    # still to run
    longp = np.concatenate(
        [base, rng.integers(0, CFG.vocab, size=(36,)).astype(np.int32)])
    solo = ServeEngine(CFG, slots=2, max_len=48, mode="paged", seed=0,
                       page_size=4, prefill_chunk=4)
    rid = solo.submit(base, 8)
    ref = [int(t) for t in solo.run().results[rid]]

    eng = ServeEngine(CFG, slots=2, max_len=48, mode="paged", seed=0,
                      page_size=4, prefill_chunk=4)
    rp = eng.submit(base, 8)
    rl = eng.submit(longp, 4)
    for _ in range(3):  # publisher prefills + publishes; sharer attaches
        eng.step()
    req = eng._requests[rl]
    assert req.prefill_pos is not None, "sharer must be mid-prefill"
    assert eng.pool.stats().shared_attaches >= 2
    assert eng.cancel(rl, "test") is True
    eng.step()
    rep = eng.run()
    assert rep.statuses[rl] == "cancelled"
    assert [int(t) for t in rep.results[rp]] == ref
    p = rep.pool
    assert p.pages_in_use == 0 and p.ref_allocs == p.ref_frees
    assert p.page_allocs == p.page_frees
    assert eng.pool.verify() == []
