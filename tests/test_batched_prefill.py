"""Batched chunked prefill on the paged engine: a step's full prefill
chunks go out ``prefill_rows`` to a dispatch, so one read of the weights
serves several chunks.  Each row computes what the one-chunk program
computes for it, so the served streams, the routed-pair counters and the
pool's accounting are those of one dispatch per chunk; the plan fills
spare rows with further chunks of the prompts already in the dispatch."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.engine import EngineConfig, ServeEngine
from repro.launch.tracing import Tracer
from repro.models.lm import build_dense_paged_prefill

DENSE = get_config("deepseek-7b").reduced()
V3 = get_config("deepseek-v3-671b").reduced(experts_held=8)


def _serve(cfg, prompts, max_new, rows, **kw):
    eng = ServeEngine(cfg, EngineConfig(mode="paged", seed=3, **kw))
    eng.prefill_rows = rows
    rids = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
    rep = eng.run()
    assert all(rep.statuses[r] == "completed" for r in rids)
    assert eng.pool.pages_in_use == 0 and eng.pool.verify() == []
    return eng, rep, [list(map(int, rep.results[r])) for r in rids]


@pytest.mark.parametrize("cfg", [DENSE, V3], ids=["dense", "mla_moe"])
def test_batched_rows_serve_the_streams_of_one_chunk_a_dispatch(cfg):
    """Prompts of whole chunks and ragged tails, more requests than
    slots, one prompt long enough that its chunks fill spare rows: the
    greedy streams and the routed-pair counters equal those of one chunk
    a dispatch (one row)."""
    rng = np.random.default_rng(7)
    lens = (8, 13, 40, 5, 24, 17)
    prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
               for n in lens]
    max_new = [5, 3, 6, 4, 2, 5]
    kw = dict(slots=3, max_len=48, page_size=4, prefill_chunk=4)
    one, rep1, streams1 = _serve(cfg, prompts, max_new, 1, **kw)
    many, rep4, streams4 = _serve(cfg, prompts, max_new, 4, **kw)
    assert streams4 == streams1
    assert rep4.counters == rep1.counters
    assert set(one._pf_chunks) == {(4, 1), (1, None)}
    assert set(many._pf_chunks) == {(4, 4), (1, None)}


def test_batched_program_rows_match_the_one_chunk_program():
    """At the graph level: R chunks of different prompts at different
    base positions, one row pointing at the trash page, give each real
    row the logits and pool writes of the single-chunk program."""
    from repro.backend import Backend

    cfg, C, ps, max_len = DENSE, 4, 4, 16
    mp = max_len // ps
    n_pages = 1 + 3 * mp
    gs = build_dense_paged_prefill(cfg, max_len, C, page_size=ps,
                                   n_pages=n_pages)
    gr = build_dense_paged_prefill(cfg, max_len, C, page_size=ps,
                                   n_pages=n_pages, rows=3)
    assert gr.fn.name.endswith("_paged_prefill4x3")
    be = Backend.create("jax")
    fs, fr = be.compile(gs.fn), be.compile(gr.fn)
    params = gs.builder.init_params(0)
    pvals = [params[n] for n in gs.builder.param_names()]
    assert gr.builder.param_names() == gs.builder.param_names()
    rng = np.random.default_rng(1)
    pools = [rng.normal(size=t.shape).astype(np.float32).astype(t.dtype)
             for t in (p.out_types[0] for p in gs.fn.parameters[3:5])]
    tbl = np.zeros((3, mp), np.int32)
    tbl[0] = 1 + np.arange(mp)
    tbl[1] = 1 + mp + np.arange(mp)       # row 2 stays on the trash page
    tok = rng.integers(0, cfg.vocab, size=(3, C)).astype(np.int32)
    pos = np.array([4, 8, 0], np.int32)
    outs = fr(tok, pos, tbl, *pools, *pvals)
    got = [np.asarray(o) for o in outs]
    want_pools = pools
    for r in range(2):
        o = fs(tok[r:r + 1], np.int32(pos[r]), tbl[r:r + 1], *want_pools,
               *pvals)
        np.testing.assert_array_equal(np.asarray(o[0])[0],
                                      got[0][r])
        want_pools = [np.asarray(x) for x in o[1:]]
    for w, g in zip(want_pools, got[1:]):
        live = np.ones(w.shape[1], bool)
        live[0] = False                   # the trash page holds garbage
        np.testing.assert_array_equal(w[:, live], g[:, live])


def test_plan_fills_spare_rows_with_further_chunks():
    """One row per prefilling slot whose next chunk is whole, the ragged
    tails alone, and the last dispatch's spare rows given to further
    whole chunks of those slots, round the slots in order."""
    eng = ServeEngine(DENSE, EngineConfig(mode="paged", slots=4, max_len=64,
                                          page_size=4, prefill_chunk=4))
    assert eng.prefill_rows == 4

    class R:
        def __init__(self, n, at):
            self.prompt = np.zeros((n,), np.int32)
            self.prefill_pos = at

    a, b, c, d = R(40, 0), R(9, 4), R(6, 4), R(16, 8)
    rows, singles = eng._plan_prefill_rows([(0, a), (1, b), (2, c), (3, d)])
    assert [(s, lo) for s, _, lo in rows] == [(0, 0), (1, 4), (3, 8),
                                              (0, 4)]
    assert [s for s, _ in singles] == [2]
    rows, singles = eng._plan_prefill_rows([(0, a)])
    assert [lo for _, _, lo in rows] == [0, 4, 8, 12]
    rows, singles = eng._plan_prefill_rows([(1, b), (3, d)])
    assert [(s, lo) for s, _, lo in rows] == [(1, 4), (3, 8), (3, 12)]


def test_batched_prefill_spans():
    """A batched dispatch records one prep, dispatch and sync span,
    tagged with its first row's request; every request gets one
    first-token sample."""
    tr = Tracer()
    eng = ServeEngine(DENSE, EngineConfig(
        mode="paged", slots=2, max_len=32, page_size=4, prefill_chunk=4),
        tracer=tr)
    rng = np.random.default_rng(2)
    rids = [eng.submit(rng.integers(0, DENSE.vocab, size=(16,)), 3)
            for _ in range(2)]
    eng.run()
    spans = tr.spans_between(0.0, float("inf"))
    named = {}
    for s in spans:
        named.setdefault(s.name, []).append(s)
    # both prompts' eight chunks in two dispatches of four rows
    assert len(named["prefill.dispatch"]) == 2
    assert len(named["prefill.prep"]) == len(named["prefill.sync"]) == 2
    assert sorted(s.rid for s in named["prefill.sample"]) == sorted(rids)
