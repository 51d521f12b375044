"""DeepSeek-V3 (``mla_moe``) on the paged serving path: the drop-free
router against a brute-force one, the expert-parallel share of the routed
layer against the uncut layer, YaRN against its formula, the paged
engine's latent pools, counters and refusals, and the lockstep engine
serving the same model."""
import itertools
import math

import numpy as np
import pytest

from repro.backend import Backend
from repro.configs import get_config
from repro.core import ops
from repro.launch.engine import EngineConfig, ServeEngine
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models.builder import ModelBuilder
from repro.models.components import yarn_freq
from repro.models.lm import build_dense_chunk, build_graphs
from repro.configs.base import ShapeConfig

V3 = get_config("deepseek-v3-671b")
CFG = V3.reduced(experts_held=8, param_dtype="bf16")


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _brute_route(logits, bias, *, top_k, n_group, topk_group, scaling):
    """Every allowed set of groups, then the best experts in it, by
    enumeration: the published gate, written out for one token at a
    time."""
    T, E = logits.shape
    s = _sigmoid(logits.astype(np.float64))
    c = s + bias
    size = E // n_group
    out = np.zeros((T, E))
    chosen = np.zeros((T, E), bool)
    for t in range(T):
        gscore = [np.sort(c[t, g * size:(g + 1) * size])[-2:].sum()
                  for g in range(n_group)]
        best = max(itertools.combinations(range(n_group), topk_group),
                   key=lambda gs: sum(gscore[g] for g in gs))
        allowed = [e for g in best for e in range(g * size, (g + 1) * size)]
        pick = sorted(allowed, key=lambda e: -c[t, e])[:top_k]
        chosen[t, pick] = True
        out[t, pick] = scaling * s[t, pick] / s[t, pick].sum()
    return out, chosen


def _route_program(T, E, **kw):
    lg = ops.parameter((T, E), "f32", "logits")
    bias = ops.parameter((E,), "f32", "bias")
    w, chosen = MOE.route(lg.out(), bias.out(), **kw)
    from repro.core.function import Function
    return Backend.create("jax").compile(Function([lg, bias], [w, chosen],
                                                  "route"))


ROUTE = dict(top_k=4, n_group=4, topk_group=2, scaling=2.5)


def test_router_matches_brute_force():
    rng = np.random.default_rng(0)
    T, E = 32, 16
    logits = rng.normal(size=(T, E)).astype(np.float32)
    bias = (rng.normal(size=(E,)) * 0.1).astype(np.float32)
    w, chosen = _route_program(T, E, **ROUTE)(logits, bias)
    want_w, want_c = _brute_route(logits, bias, **ROUTE)
    np.testing.assert_array_equal(np.asarray(chosen) > 0, want_c)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_bias_changes_the_choice_not_the_weights():
    """One token, 8 experts in 4 groups of 2, top 2 of 2 groups: expert 3
    scores below expert 2, and the bias lifts it above.  The choice
    follows ``s + bias``; the weights read ``s`` alone."""
    E = 8
    s = np.array([0.9, 0.1, 0.7, 0.6, 0.2, 0.1, 0.3, 0.2])
    logits = np.log(s / (1 - s)).astype(np.float32)[None, :]
    kw = dict(top_k=2, n_group=4, topk_group=2, scaling=1.0)
    run = _route_program(1, E, **kw)
    zero = np.zeros((E,), np.float32)
    lift = zero.copy()
    lift[3] = 0.15
    w0, c0 = (np.asarray(a)[0] for a in run(logits, zero))
    w1, c1 = (np.asarray(a)[0] for a in run(logits, lift))
    assert list(np.flatnonzero(c0)) == [0, 2]
    assert list(np.flatnonzero(c1)) == [0, 3]
    np.testing.assert_allclose(w1[[0, 3]], [0.9 / 1.5, 0.6 / 1.5],
                               rtol=1e-5)
    want_w, want_c = _brute_route(logits, lift, **kw)
    np.testing.assert_array_equal(c1 > 0, want_c[0])
    np.testing.assert_allclose(w1, want_w[0], rtol=1e-5, atol=1e-7)


def _routed_layer(D, E, Fe, T, *, held, first, n_shared):
    b = ModelBuilder("f32", "f32")
    x = b.input("x", (1, T, D), dtype="f32")
    specs = MOE.routed_specs(D, E, held, Fe, n_shared)
    w = {f"moe_{k}": b.raw_param(f"moe_{k}", s, lg)
         for k, (s, lg) in specs.items()}
    out, pairs = MOE.apply_routed(
        b, x, w, n_experts=E, held=held, first=first, n_shared=n_shared,
        **ROUTE)
    cf = Backend.create("jax").compile(b.finish([out, pairs], "routed"))
    return cf, b.param_names()


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their held experts' parts, with
    the shared expert counted once, add up to the layer that holds all 16;
    and every token's top-4 pairs land on exactly one chip each."""
    D, E, Fe, T, n = 24, 16, 8, 10, 4
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, T, D)).astype(np.float32)
    full, names = _routed_layer(D, E, Fe, T, held=E, first=0, n_shared=1)
    weights = {nm: (rng.normal(size=s.shape) / math.sqrt(s.shape[-2] if
                    len(s.shape) > 1 else 10)).astype(np.float32)
               for nm, s in zip(names, full.function.in_types[1:])}
    uncut, pairs_all = full(x, *[weights[nm] for nm in names])
    g, u, d = (weights[f"moe_ws_{k}"] for k in ("gate", "up", "down"))
    xt = x[0].astype(np.float64)
    shared = ((xt @ g) * _sigmoid(xt @ g) * (xt @ u)) @ d
    total, pairs = shared.copy(), np.zeros((T,), np.int64)
    for j in range(n):
        part, pnames = _routed_layer(D, E, Fe, T, held=E // n,
                                     first=j * E // n, n_shared=0)
        sl = slice(j * E // n, (j + 1) * E // n)
        args = [weights[nm][sl] if nm.startswith("moe_we_") else weights[nm]
                for nm in pnames]
        out, p = part(x, *args)
        total += np.asarray(out)[0]
        pairs += np.asarray(p)
    np.testing.assert_allclose(total, np.asarray(uncut)[0], rtol=2e-4,
                               atol=2e-5)
    assert (pairs == ROUTE["top_k"]).all()
    assert (np.asarray(pairs_all) == ROUTE["top_k"]).all()


def test_yarn_against_its_formula():
    """d_rope 64, base 10000, factor 40 over 4096 positions, beta 32 / 1:
    the ramp runs from pair floor(64 ln(4096/(64 pi)) / (2 ln 10000)) = 10
    to ceil(64 ln(4096/(2 pi)) / (2 ln 10000)) = 23."""
    d, base = 64, 10000.0
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    theta = base ** (-np.arange(32) * 2.0 / d)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = theta / 40 * ramp + theta * (1 - ramp)
    got = yarn_freq(d, base, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], theta[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], theta[23:] / 40, rtol=1e-6)
    scale = MLA.softmax_scale(128, 64, 40.0, 1.0)
    assert scale == pytest.approx(
        (0.1 * math.log(40) + 1) ** 2 / math.sqrt(192))
    assert round(scale, 6) == 0.135234
    assert MLA.softmax_scale(128, 64) == pytest.approx(1 / math.sqrt(192))


def test_published_router_and_yarn_fields():
    assert (V3.n_group, V3.topk_group, V3.routed_scaling,
            V3.held_experts) == (8, 4, 2.5, 256)
    assert (V3.yarn_factor, V3.yarn_original_len, V3.yarn_beta_fast,
            V3.yarn_beta_slow, V3.yarn_mscale) == (40.0, 4096, 32.0, 1.0,
                                                   1.0)
    assert (CFG.n_experts, CFG.top_k, CFG.n_group, CFG.topk_group) == \
        (16, 4, 4, 2)


def test_latent_pools_bytes_and_counters():
    """Two latent pools of one head; a page holds (kv_lora + d_rope) rows
    of every layer, so KV bytes per cached token are (kv_lora + d_rope) x
    2 bytes x layers; the engine counts routed pairs of decode rows and
    of prefill tokens."""
    eng = ServeEngine(CFG, EngineConfig(mode="paged", slots=2, max_len=32,
                                        seed=0))
    L, ps = CFG.n_layers, eng.page_size
    assert eng.pool.names == ["cache_ckv", "cache_kr"]
    assert [t.shape for t in eng.pool.types] == [
        (L, eng.n_pages, 1, ps, CFG.kv_lora),
        (L, eng.n_pages, 1, ps, CFG.d_rope)]
    per_token = (CFG.kv_lora + CFG.d_rope) * 2 * L
    assert eng.pool.bytes_per_page == per_token * ps
    rng = np.random.default_rng(3)
    # each request fills whole pages, so reserved bytes = cached bytes
    rids = [eng.submit(rng.integers(0, CFG.vocab, size=(12,)), 4)
            for _ in range(2)]
    rep = eng.run()
    assert all(rep.statuses[r] == "completed" for r in rids)
    nm = CFG.n_layers - CFG.first_dense
    c = rep.counters
    assert c["expert_layer_steps"] == 2 * 3 * nm
    assert c["expert_layer_steps_prefill"] == 2 * 12 * nm
    for pairs, steps in ((c["expert_pairs"], c["expert_layer_steps"]),
                         (c["expert_pairs_prefill"],
                          c["expert_layer_steps_prefill"])):
        assert 0 < pairs <= CFG.top_k * steps
    assert eng.live_stats()["counters"] == c
    assert rep.kv_bytes_per_active_token >= per_token
    dense = ServeEngine(get_config("deepseek-7b").reduced(),
                        EngineConfig(mode="paged", slots=2, max_len=32))
    assert "expert_pairs" not in dense.counters


def test_refusals_name_the_cause():
    with pytest.raises(ValueError, match="heads per page=1"):
        ServeEngine(CFG, EngineConfig(mode="paged", slots=2, max_len=32,
                                      tp=2))
    with pytest.raises(ValueError, match="prefill_chunk=0"):
        ServeEngine(CFG, EngineConfig(mode="paged", slots=2, max_len=32,
                                      prefill_chunk=0))
    with pytest.raises(NotImplementedError, match="mode='paged'"):
        ServeEngine(CFG, EngineConfig(mode="continuous", slots=2,
                                      max_len=32))
    xl = get_config("xlstm-350m").reduced()
    with pytest.raises(NotImplementedError, match="'dense', 'mla_moe'"):
        ServeEngine(xl, EngineConfig(mode="paged", slots=2, max_len=32))
    with pytest.raises(NotImplementedError, match="mla_moe"):
        build_graphs(CFG, ShapeConfig("s", "serve", 32, 2), 2)
    with pytest.raises(NotImplementedError, match="page_size"):
        build_dense_chunk(CFG, 32, 2, 4)


def test_lockstep_serves_the_paged_model():
    """Lockstep prefill + decode run the same routed layer, rotary tables
    and softmax scale as the paged programs, over the same weights: one
    model, so the greedy streams agree."""
    cfg = V3.reduced(experts_held=8)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=(13,)).astype(np.int32)
               for _ in range(2)]
    streams = {}
    for mode in ("lockstep", "paged"):
        eng = ServeEngine(cfg, EngineConfig(mode=mode, slots=2, max_len=32,
                                            seed=3))
        rids = [eng.submit(p, 6) for p in prompts]
        rep = eng.run()
        streams[mode] = [list(map(int, rep.results[r])) for r in rids]
    assert streams["lockstep"] == streams["paged"]
