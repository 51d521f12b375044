"""Units of the serving benchmark: traffic, counts of work, records,
cells and the per-layer readers."""
import os
import sys

import numpy as np
import pytest

SERVE = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "benchmarks", "serve"))
sys.path.insert(0, SERVE)

from servebench import cells, flops, traffic  # noqa: E402
from servebench.record import Run  # noqa: E402

CLOSED = {"name": "t", "loop": "closed", "clients_per_slot": 2,
          "prompt": {"median": 100, "sigma": 0.6, "min": 20, "max": 300},
          "output": {"median": 40, "sigma": 0.6, "min": 8, "max": 120},
          "block": 16, "max_requests_per_client_second": 1.0}
OPEN = {"name": "o", "loop": "open", "rate": 4.0,
        "prefix": {"tenants": 4, "zipf_s": 1.1, "tokens": 64},
        "prompt": {"median": 30, "sigma": 0.8, "min": 4, "max": 120},
        "output": {"median": 16, "sigma": 0.8, "min": 2, "max": 60},
        "block": 20}


@pytest.mark.parametrize("mix", [CLOSED, OPEN], ids=["closed", "open"])
def test_every_seed_sends_the_same_work(mix):
    """Every block is one sequence of sizes (and of gaps, tenants), the
    same for every seed; the seed draws only the tokens."""
    m = mix["block"]
    a = traffic.make_requests(mix, 1, 500, 3 * m)
    b = traffic.make_requests(mix, 2 ** 33 + 7, 500, 3 * m)

    def shape(r):
        return len(r.prompt), r.max_new, r.tenant, r.due

    assert [shape(r) for r in a] == [shape(r) for r in b]
    assert [shape(r)[:3] for r in a[:m]] == [shape(r)[:3] for r in a[m:2 * m]]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the order is mixed, not sorted by length
    lens = [len(r.prompt) for r in a[:m]]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)
    if mix["loop"] == "open":
        assert a[-1].due == pytest.approx(3 * m / mix["rate"], rel=0.1)


def test_same_seed_same_requests():
    a = traffic.make_requests(OPEN, 9, 500, 30)
    b = traffic.make_requests(OPEN, 9, 500, 30)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, b))


def test_lengths_stay_in_range_and_follow_the_median():
    s = traffic.block_sizes(dict(CLOSED, block=64))
    assert s["prompt"].min() >= 20 and s["prompt"].max() <= 300
    assert abs(np.median(s["prompt"]) - 100) <= 5
    assert abs(np.median(s["output"]) - 40) <= 3


def test_shared_prefix_belongs_to_the_tenant():
    reqs = traffic.make_requests(OPEN, 4, 500, 40)
    by_tenant = {}
    for r in reqs:
        assert r.shared == 64
        head = tuple(r.prompt[:64])
        assert by_tenant.setdefault(r.tenant, head) == head
    counts = np.bincount([r.tenant for r in reqs[:20]], minlength=4)
    assert list(counts) == sorted(counts, reverse=True)   # Zipf order


def test_prefill_lengths_cover_every_tail():
    got = traffic.prefill_lengths(CLOSED, 32, 8)
    want = set()
    for P in traffic.block_sizes(CLOSED)["prompt"]:
        want |= {min(int(P), 32), int(P) % 32 or 32}
    assert set(got) == want
    assert traffic.prefill_lengths(OPEN, 32, 8) == list(range(1, 33))


def test_stream_outlasts_the_window():
    assert traffic.stream_length(OPEN, 4, 10) >= 4.0 * 10
    assert traffic.stream_length(CLOSED, 4, 10) >= 1.0 * 8 * 10


DENSE = {"name": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
         "n_kv_heads": 1, "d_ff": 16, "vocab": 11, "mlp": "swiglu",
         "counts": "flops"}
# every reader in metrics/, found when the tests are collected
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(SERVE, "metrics"))
                 if f.endswith(".py"))


def test_flop_and_byte_counts():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16
    assert flops.layer_matmul_params(DENSE) == 2 * (64 + 64 + 64 + 384)
    assert flops.head_params(DENSE) == 88
    assert flops.kv_bytes_per_token(DENSE) == 2 * 2 * 1 * 4 * 2
    assert flops.attn_flops(DENSE, 3) == 4 * 2 * 4 * 3 * 2
    assert flops.decode_token_flops(DENSE, 5) == \
        2 * (1152 + 88) + flops.attn_flops(DENSE, 6)
    # positions 2..4 attend 3 + 4 + 5 keys; one output head
    assert flops.prefill_flops(DENSE, 2, 5) == \
        2 * 1152 * 3 + 2 * 88 + flops.attn_flops(DENSE, 12)
    assert flops.decode_token_bytes(DENSE, 5) == 7 * 32


def _run(records, loop="closed", conf=DENSE):
    reqs = traffic.make_requests(CLOSED, 1, 500, 4)
    return Run(cell="c", conf=dict(conf), mix={"loop": loop},
               engine={"page_size": 8, "chunk_steps": 4}, t0=10.0,
               t_end=20.0, requests=reqs, records=records)


def _rec(idx, due, times, **kw):
    r = {"idx": idx, "due": due, "sent": due, "status": 200,
         "tokens": list(range(len(times))), "times": times,
         "terminal": "completed", "error": None, "cut": False}
    r.update(kw)
    return r


def test_end_to_end_metrics_from_records():
    run = _run([_rec(0, 10.0, [10.5, 11.0, 11.5, 21.0]),
                _rec(1, 9.0, [9.5, 10.2, 12.2], cut=True),
                _rec(2, 12.0, [], status=429, error="full")])
    assert run.tok_s() == pytest.approx(5 / 10.0)
    assert sorted(run.gaps_ms()) == pytest.approx([500.0, 500.0, 2000.0])
    assert run.end_to_end("itl_p95_ms") == pytest.approx(
        np.percentile([500.0, 500.0, 2000.0], 95))
    assert len(run.attempted()) == 3
    assert [r["idx"] for r in run.failed()] == [2]
    # served: completed, or cut by the close with its tokens so far
    assert [r["idx"] for r in run.served()] == [0, 1]


def test_open_loop_counts_unanswered_as_failed():
    run = _run([_rec(0, 10.0, [10.5, 11.0]),
                _rec(1, 19.0, [], cut=True)], loop="open")
    assert [r["idx"] for r in run.failed()] == [1]
    assert run.ttft_ms() == pytest.approx([500.0])


def test_ttft_by_half_of_the_window():
    # due 10..15 is the first half of 10..20; a warm-in request (due 9)
    # and one due after the close are left out
    run = _run([_rec(0, 10.0, [10.5]), _rec(1, 12.0, [13.5]),
                _rec(2, 16.0, [19.0]), _rec(3, 9.0, [9.1]),
                _rec(4, 21.0, [21.2])], loop="open")
    assert run.ttft_p50_by_half_ms() == pytest.approx([1000.0, 3000.0])


def test_needed_flops_counts_prefills_and_decoded_tokens():
    run = _run([_rec(0, 10.0, [10.5, 11.0, 25.0])])
    P = len(run.requests[0].prompt)
    want = flops.prefill_flops(DENSE, 0, P) + flops.decode_token_flops(
        DENSE, P)
    assert run.needed_flops() == pytest.approx(want)


def test_model_config_refuses_an_unlisted_change(tmp_path):
    good = cells.load_config("deepseek-7b-l4")
    cells.model_config(good)
    bad = dict(good, d_ff=1024)
    with pytest.raises(cells.CellError, match="d_ff"):
        cells.model_config(bad)


def test_benchmark_names_resolve_to_files():
    bench = cells.benchmark()
    for w in bench["workloads"]:
        cells.load_config(w["config"])
        cells.load_traffic(w["traffic"])
        for m in cells.metrics_for(w["name"], "per_layer", bench):
            assert m["name"] in cells.load_metric_readers([m["name"]])
    names = [m["name"] for m in bench["per_layer"]]
    assert len(cells.load_metric_readers(names)) == len(names)
    assert cells.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(cells.CellError):
        cells.peaks("cpu")


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_a_trace(name):
    reader = cells.load_metric_readers([name])[name]
    run = _run([_rec(0, 10.0, [10.5, 11.0])])
    assert reader.read(run) is None


@pytest.mark.parametrize("bad,match", [
    ({"counts": None}, "names no count module"),
    ({"counts": "../flops"}, "names no count module"),
    ({"counts": "no_such_counts"}, "has no file"),
    ({"counts": "traffic"}, "lacks")],
    ids=["unnamed", "a-path", "no-file", "not-a-count-module"])
def test_count_module_is_required(bad, match):
    conf = dict(DENSE, **bad)
    if bad["counts"] is None:
        del conf["counts"]
    with pytest.raises(cells.CellError, match=match):
        cells.counts(conf)
    with pytest.raises(cells.CellError, match=match):
        _run([_rec(0, 10.0, [10.5, 11.0])], conf=conf).needed_flops()


def test_dense_counts_read_as_before():
    """The dense count module, named by the configuration, gives the
    operations the harness counted when it called ``flops`` directly:
    the same sums in the same order, bit for bit."""
    for name in ("deepseek-7b-l4", "granite-34b-code-l4"):
        assert cells.counts(cells.load_config(name)) is flops
    conf = dict(cells.load_config("deepseek-7b-l4"))
    run = _run([_rec(0, 10.0, [10.5, 11.0, 11.5, 25.0]),
                _rec(1, 9.0, [9.5, 10.5]), _rec(2, 11.0, [12.0, 12.5])],
               conf=conf)
    want = 0.0
    for r in run.records:
        if r["times"] and run.inside(r["times"][0]):
            want += flops.prefill_flops(
                conf, 0, len(run.requests[r["idx"]].prompt))
    for req, i in run.decode_tokens(run.t0, run.t_end):
        want += flops.decode_token_flops(conf, len(req.prompt) + i - 1)
    assert run.needed_flops() == want
