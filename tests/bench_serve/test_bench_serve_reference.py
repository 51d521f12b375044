"""The serving benchmark's plain reference against the paged engine, on
the CPU at the configurations' ``reduced()`` size.

The engine serves greedy requests (some behind a shared prefix, so they
attach cached pages) through chunked paged prefill and the decode chunk,
over the weights the benchmark makes from the seed; the reference, over
weights made anew from the same seed, must rank every served token at
the top of its logits up to a near-tie.  The control (the reference
computed in float8) must not.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

SERVE = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "serve")
sys.path.insert(0, os.path.abspath(SERVE))

import reference  # noqa: E402
from servebench import check, harness  # noqa: E402

from repro.configs import get_config  # noqa: E402

SEED = 2 ** 31 + 11


def _conf(cfg) -> dict:
    conf = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    conf["engine"] = {"slots": 3, "max_len": 128, "pages": None}
    return conf


def _serve(cfg, conf, prompts, max_new):
    eng = harness.build_engine(cfg, conf, SEED,
                               reference.make_weights(conf, SEED))
    rids = [eng.submit(p, max_new) for p in prompts]
    rep = eng.run()
    assert all(rep.statuses[r] == "completed" for r in rids)
    assert rep.pool.shared_attaches > 0
    return eng, [list(map(int, rep.results[r])) for r in rids]


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


@pytest.fixture(scope="module", params=["deepseek-7b", "granite-34b"])
def served(request):
    cfg = get_config(request.param).reduced(n_layers=2)
    prompts = _prompts(cfg.vocab)
    conf = _conf(cfg)
    eng, tokens = _serve(cfg, conf, prompts, 24)
    weights = reference.make_weights(conf, SEED)
    return cfg, conf, eng, weights, prompts, tokens


def test_reference_draws_the_engines_weights(served):
    """The engine serves what the benchmark made, and a second call for
    the same seed makes the same weights; another seed, others."""
    _, conf, eng, weights, _, _ = served
    assert sorted(weights) == sorted(eng.params)
    for name, arr in eng.params.items():
        np.testing.assert_array_equal(np.asarray(weights[name]),
                                      np.asarray(arr), err_msg=name)
    other = reference.make_weights(conf, SEED + 1)
    assert not np.array_equal(np.asarray(other["embed/table"]),
                              np.asarray(weights["embed/table"]))


def test_engine_refuses_weights_it_does_not_declare(served):
    cfg, conf, _, weights, _, _ = served
    short = {k: v for k, v in weights.items() if k != "embed/table"}
    with pytest.raises(Exception, match="embed/table"):
        harness.build_engine(cfg, conf, SEED, short)


def test_engine_agrees_with_reference(served):
    _, conf, _, weights, prompts, tokens = served
    ref = reference.Reference(conf, weights)
    sample = [check.Sample(i, p, t) for i, (p, t) in
              enumerate(zip(prompts, tokens))]
    got = check.served_gap(ref, sample)
    assert got["tokens_checked"] == sum(len(t) for t in tokens)
    # bf16 compute against the f32 reference: greedy picks may differ
    # only at near-ties, a few thousandths of the largest logit here
    assert got["served_gap_max"] <= 0.02, got


def test_fp8_control_fails_the_limit(served):
    _, conf, _, weights, prompts, tokens = served
    ref = reference.Reference(conf, weights)
    ctl = reference.Reference(conf, weights, precision="fp8")
    sample = [check.Sample(i, p, t) for i, (p, t) in
              enumerate(zip(prompts, tokens))]
    got = check.control_gap(ref, ctl, sample)
    assert got["control_gap_max"] > 0.05, got


def test_gaps_are_shares_of_the_row():
    lg = np.array([[1.0, 3.0, -4.0], [0.5, 0.25, 0.0]])
    np.testing.assert_allclose(check.gaps(lg, [1, 2]), [0.0, 1.0])
    np.testing.assert_allclose(check.gaps(lg, [2, 1]), [7.0 / 4.0, 0.5])
