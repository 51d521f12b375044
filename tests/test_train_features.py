"""Distributed-training features: microbatch gradient accumulation,
LR schedules, and the shard_map data-parallel path with gradient
compression (the multi-node pattern, exercised on one host)."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import ops
from repro.core.autodiff import GradBuilder, zeros_of
from repro.core.function import Function
from repro.core.passes import CompressAllReduce
from repro.models.lm import build_graphs
from repro.models.train_graph import (init_opt_state, lr_schedule,
                                      make_train_step)
from repro.backend import Backend

JT = Backend.create("jax")


def _run_step(ts, params, m, v, toks, lbls, step=0):
    ex = JT.compile(ts.fn)
    args = [toks, lbls, np.int32(step)] + \
        [params[k] for k in ts.param_names] + \
        [m[k] for k in ts.param_names] + [v[k] for k in ts.param_names]
    return ex(*args)


def test_microbatch_matches_full_batch():
    cfg = get_config("deepseek-7b").reduced()
    B, S, n = 8, 16, 4
    rng = np.random.default_rng(0)
    g1 = build_graphs(cfg, ShapeConfig("train", "train", S, B), B)
    ts1 = make_train_step(g1, cfg)
    g2 = build_graphs(cfg, ShapeConfig("train", "train", S, B // n), B // n)
    ts2 = make_train_step(g2, cfg, n_micro=n)
    params = g1.builder.init_params(0)
    m, v = init_opt_state(g1.builder, cfg, params)
    toks = rng.integers(0, cfg.vocab, size=(B, S)).astype(np.int32)
    lbls = rng.integers(0, cfg.vocab, size=(B, S)).astype(np.int32)
    o1 = _run_step(ts1, params, m, v, toks, lbls)
    o2 = _run_step(ts2, g2.builder.init_params(0), m, v, toks, lbls)
    assert abs(float(o1[0]) - float(o2[0])) < 1e-5
    for x, y in zip(o1[1:], o2[1:]):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=5e-4, rtol=1e-3)


def test_microbatch_trains():
    cfg = get_config("deepseek-7b").reduced()
    B, S, n = 8, 16, 2
    g = build_graphs(cfg, ShapeConfig("train", "train", S, B // n), B // n)
    ts = make_train_step(g, cfg, n_micro=n)
    params = g.builder.init_params(0)
    m, v = init_opt_state(g.builder, cfg, params)
    rng = np.random.default_rng(1)
    flat = [params[k] for k in ts.param_names] + \
        [m[k] for k in ts.param_names] + [v[k] for k in ts.param_names]
    ex = JT.compile(ts.fn)
    losses = []
    for step in range(20):
        toks = rng.integers(0, cfg.vocab, size=(B, S)).astype(np.int32)
        lbls = (toks * 31 + 17) % cfg.vocab
        outs = ex(toks, lbls, np.int32(step), *flat)
        losses.append(float(outs[0]))
        flat = list(outs[1:])
    assert losses[-1] < losses[0]


def test_lr_schedules():
    import dataclasses
    cfg = get_config("minicpm-2b")  # wsd
    step_p = ops.parameter((), "i32", "step")
    for sched in ("wsd", "cosine", "constant"):
        c = dataclasses.replace(cfg, schedule=sched, warmup=10,
                                total_steps=100, lr=1.0)
        lr = lr_schedule(c, ops.convert(step_p.out(), "f32"))
        fn = Function([step_p], [lr])
        ex = JT.compile(fn)
        vals = [float(ex(np.int32(s))[0]) for s in
                (0, 5, 9, 10, 50, 89, 95, 99)]
        assert vals[0] < vals[1] < vals[2] + 1e-6, (sched, vals)  # warmup rises
        assert max(vals) <= 1.0 + 1e-6
        if sched == "wsd":
            assert abs(vals[4] - 1.0) < 1e-6      # stable phase at peak
            assert vals[6] < 1.0                  # decay began
        if sched == "cosine":
            assert vals[7] < vals[4] < vals[3] + 1e-6  # monotone decay
        if sched == "constant":
            assert abs(vals[4] - 1.0) < 1e-6


def test_shardmap_dp_with_grad_compression():
    """The multi-node DP pattern: per-device grad graph + AllReduce IR
    ops, optionally bf16-compressed by the pass, run under shard_map."""
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import ops
        from repro.core.autodiff import GradBuilder
        from repro.core.function import Function
        from repro.core.passes import CompressAllReduce
        from repro.backend import Backend, CompileOptions

        # per-device forward: local batch 4, then AllReduce(mean) grads
        x = ops.parameter((4, 8), "f32", "x")
        w = ops.parameter((8, 8), "f32", "w")
        y = ops.tanh(ops.matmul(x.out(), w.out()))
        loss = ops.reduce_mean(y * y)
        gb = GradBuilder()
        (gw,) = gb.backprop([loss], [ops.constant(1.0, dtype="f32")],
                            [w.out()])
        gw = ops.all_reduce(gw, "data", reduce_op="mean")
        fn = Function([x, w], [loss, gw])
        comp, stats = CompressAllReduce(wire_dtype="bf16").run(fn)

        run = Backend.create("jax").compile(
            fn, CompileOptions(mode="shardmap", static_jit=False,
                               level="O0")).raw
        mesh = jax.make_mesh((8,), ("data",))
        f = shard_map(lambda a, b: tuple(run(a, b)), mesh=mesh,
                      in_specs=(P("data", None), P(None, None)),
                      out_specs=(P(), P(None, None)), check_vma=False)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(32, 8)).astype(np.float32)
        W = rng.normal(size=(8, 8)).astype(np.float32)
        with mesh:
            loss_v, g = jax.jit(f)(X, W)

        # reference: global-batch gradient
        import jax.numpy as jnp
        def ref(W):
            return jnp.mean(jnp.square(jnp.tanh(X @ W)))
        g_ref = jax.grad(ref)(W)
        err = float(np.abs(np.asarray(g) - np.asarray(g_ref)).max())
        assert err < 1e-5, err
        print("DP-OK")
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          cwd=__file__.rsplit("/tests/", 1)[0])
    assert "DP-OK" in proc.stdout, proc.stderr[-2500:]
