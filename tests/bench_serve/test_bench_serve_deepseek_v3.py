"""The DeepSeek-V3 configuration's plain reference and count module
against the paged engine, on the CPU at a tiny size, and a tiny
MLA-MoE cell through ``bench.py``.

The engine prefills through its paged prefill chunks (some requests
behind a shared prefix, so they attach cached pages) and decodes through
its decode chunk, over the weights the benchmark makes from the seed.
Here the engine computes in float32 from the bfloat16 weights, so that
it and the float32 reference differ only by rounding: no router choice
sits close enough to a tie to flip (the routing margins here are
thousandths, the logits agree to about 1e-6 of their largest).
"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = os.path.join(HERE, "..", "..", "benchmarks", "serve")
sys.path.insert(0, os.path.abspath(SERVE))
sys.path.insert(0, HERE)

import bench_serve_tiny as tiny  # noqa: E402
import reference_deepseek_v3 as reference  # noqa: E402
from servebench import cells, check, harness, mla_moe_flops  # noqa: E402

from repro.backend import Backend  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models.lm import build_dense_paged_prefill  # noqa: E402

SEED = 2 ** 31 + 23
BIAS_SCALE = 0.1


def _conf(cfg) -> dict:
    conf = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    conf.update(router_bias_scale=BIAS_SCALE, scoring_func="sigmoid",
                engine={"slots": 3, "max_len": 128, "pages": None})
    return conf


def _prompts(vocab):
    rng = np.random.default_rng(5)
    shared = rng.integers(0, vocab, size=(40,))
    out = []
    for n in (7, 45, 70, 33):
        p = rng.integers(0, vocab, size=(n,))
        if n > 40:
            p[:40] = shared
        out.append(p.astype(np.int32))
    return out


@pytest.fixture(scope="module")
def served():
    cfg = get_config("deepseek-v3-671b").reduced(
        experts_held=8, param_dtype="bf16", compute_dtype="f32")
    conf = _conf(cfg)
    prompts = _prompts(cfg.vocab)
    eng = harness.build_engine(cfg, conf, SEED,
                               reference.make_weights(conf, SEED))
    rids = [eng.submit(p, 24) for p in prompts]
    rep = eng.run()
    assert all(rep.statuses[r] == "completed" for r in rids)
    assert rep.pool.shared_attaches > 0
    tokens = [list(map(int, rep.results[r])) for r in rids]
    weights = reference.make_weights(conf, SEED)
    return cfg, conf, eng, weights, prompts, tokens


def test_reference_draws_the_engines_weights(served):
    _, conf, eng, weights, _, _ = served
    assert sorted(weights) == sorted(eng.params)
    for name, arr in eng.params.items():
        assert np.asarray(weights[name]).dtype == np.asarray(arr).dtype
        np.testing.assert_array_equal(np.asarray(weights[name]),
                                      np.asarray(arr), err_msg=name)
    other = reference.make_weights(conf, SEED + 1)
    assert not np.array_equal(np.asarray(other["moe/moe_router_bias"]),
                              np.asarray(weights["moe/moe_router_bias"]))


def test_prefill_logits_agree_with_reference(served):
    """The paged prefill chunks' last-row logits, prompt by prompt in
    chunks of 32, against the reference's full forward: within 1e-4 of
    the row's largest logit (float32 rounding over three layers)."""
    cfg, conf, _, weights, prompts, _ = served
    ref = reference.Reference(conf, weights)
    be = Backend.create("jax")
    progs = {}
    for p in prompts:
        pools = None
        for lo in range(0, len(p), 32):
            C = min(32, len(p) - lo)
            if C not in progs:
                g = build_dense_paged_prefill(cfg, 128, C, page_size=8)
                progs[C] = (g, be.compile(g.fn))
            g, cf = progs[C]
            if pools is None:
                pools = [np.zeros(n.out_types[0].shape,
                                  np.dtype(n.out_types[0].dtype))
                         for n in g.builder.inputs[3:]]
            outs = cf(p[lo:lo + C].reshape(1, C), np.int32(lo),
                      np.arange(1, 17, dtype=np.int32).reshape(1, 16),
                      *pools, *[weights[n] for n in g.builder.param_names()])
            pools = [np.asarray(o) for o in outs[1:3]]
        got = np.asarray(outs[0]).reshape(-1)
        want = ref.logits(p, [len(p) - 1])[0]
        assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_served_tokens_agree_with_reference(served):
    """Prefill then decode through the pools: every served token's logit
    lies within 1e-4 of the reference's best, as a share of the row's
    largest (``check.served_gap``, the comparison behind ``correct``)."""
    _, conf, _, weights, prompts, tokens = served
    ref = reference.Reference(conf, weights)
    sample = [check.Sample(i, p, t) for i, (p, t) in
              enumerate(zip(prompts, tokens))]
    got = check.served_gap(ref, sample)
    assert got["tokens_checked"] == sum(len(t) for t in tokens)
    assert got["served_gap_max"] <= 1e-4, got


def test_fp8_control_fails_the_limit(served):
    _, conf, _, weights, prompts, tokens = served
    ref = reference.Reference(conf, weights)
    ctl = reference.Reference(conf, weights, precision="fp8")
    sample = [check.Sample(i, p, t) for i, (p, t) in
              enumerate(zip(prompts, tokens))]
    assert check.control_gap(ref, ctl, sample)["control_gap_max"] > 0.05


def test_counts_by_hand():
    """The configuration's operations and bytes, summed here by hand from
    the published widths."""
    conf = cells.load_config("deepseek-v3-ep32-l5")
    assert cells.counts(conf) is mla_moe_flops
    cells.model_config(conf)
    D, H, V = 7168, 128, 16160
    attn = (D * 1536 + 1536 * H * 192 + D * 576 + H * 512 * 128
            + H * 512 * 128 + H * 128 * D)
    dense = attn + 3 * D * 18432
    moe_fixed = attn + D * 256 + 3 * D * 2048
    expert = 3 * D * 2048
    per_token = dense + 4 * (moe_fixed + 0.25 * expert)
    head = D * V
    assert mla_moe_flops.pairs_per_token(conf) == 0.25
    assert mla_moe_flops.decode_token_flops(conf, 99) == pytest.approx(
        2 * (per_token + head) + 2 * H * (2 * 512 + 64) * 100 * 5, rel=1e-12)
    assert mla_moe_flops.prefill_flops(conf, 2, 5) == pytest.approx(
        2 * per_token * 3 + 2 * head + 2 * H * 1088 * (3 + 4 + 5) * 5,
        rel=1e-12)
    assert mla_moe_flops.weight_bytes(conf) == 2 * (
        dense + 4 * (moe_fixed + 8 * expert) + head)
    assert mla_moe_flops.decode_token_bytes(conf, 99) == 101 * 5760
    # every weight the decode chunk multiplies by: all but the embedding
    # table and the norm gains of the 3,156,434,944 parameters
    norms = D + 1536 + 512 + D
    assert mla_moe_flops.weight_bytes(conf) // 2 == \
        3156434944 - V * D - 5 * norms - D - 4 * 256


TINY_MLA = {"n_layers": 3, "first_dense": 1, "d_model": 64, "n_heads": 4,
            "q_lora": 32, "kv_lora": 16, "d_nope": 16, "d_rope": 8,
            "d_v": 16, "d_ff": 128, "expert_d_ff": 32, "n_experts": 16,
            "top_k": 4, "n_group": 4, "topk_group": 2, "experts_held": 8,
            "vocab": 257, "compute_dtype": "f32"}


def _add_tiny_mla(root):
    """A tiny MLA-MoE configuration and cell beside the tiny checkout's
    own: the real configuration's file with its widths cut and float32
    compute (see the module docstring), and a limit of its own."""
    serve = os.path.join(root, "benchmarks", "serve")
    with open(os.path.join(serve, "configs",
                           "deepseek-v3-ep32-l5.json")) as fh:
        conf = json.load(fh)
    conf.update(TINY_MLA, name="tiny-mla", router_bias_scale=BIAS_SCALE,
                engine={"slots": 4, "max_len": 256, "pages": None},
                check={"served_gap_max": 1e-3})
    conf["reduced"] = sorted(set(conf["reduced"]) | set(TINY_MLA))
    with open(os.path.join(serve, "configs", "tiny-mla.json"), "w") as fh:
        json.dump(conf, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["workloads"].append({
        "name": "tiny-mla", "config": "tiny-mla", "traffic": "tiny-closed",
        "chips": 1, "why": "rehearsal"})
    for m in bench["per_layer"]:
        if "tiny-closed" in m.get("workloads", ()):
            m["workloads"].append("tiny-mla")
    with open(path, "w") as fh:
        json.dump(bench, fh)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_mla_cell_runs_through_the_harness(tmp_path, trace):
    root = str(tmp_path)
    bench = tiny.make(root)
    _add_tiny_mla(root)
    rc, out, err = tiny.run(root, bench, "--workload", "tiny-mla", "--seed",
                            SEED + trace, "--seconds", 2, "--trace", trace,
                            "--platform", "cpu")
    res = tiny.result(out)
    assert rc == 0 and res is not None, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["failed"] == 0
    if trace:
        assert {"step_host_ms.tok", "dispatch_ms.tok",
                "decode_rows_mean.tok"} <= set(res["metrics"]), res
    else:
        assert res["metrics"]["tok_s"]["value"] > 0
        assert "setup_s" in res["metrics"]
