"""Runtime substrate: checkpointing (atomicity, async, elastic restore),
fault tolerance, deterministic data pipeline, sharding policy."""
import json
import os
import time

import numpy as np
import pytest

from repro.runtime.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.runtime.data import DataConfig, Prefetcher, SyntheticLM
from repro.backend.sharding import ParamInfo, policy_for, policy_for_arch
from repro.runtime.fault import (Heartbeat, StragglerDetector, TransientError,
                                 retry_step)


# -- checkpoint ----------------------------------------------------------------
def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in (10, 20, 30):
        mgr.save(step, {"w": np.full((4,), step, np.float32),
                        "nested/x": np.arange(step)},
                 extra={"foo": step})
    assert mgr.latest_step() == 30
    step, tensors, extra = mgr.restore()
    assert step == 30 and extra["foo"] == 30
    np.testing.assert_array_equal(tensors["w"], np.full((4,), 30, np.float32))
    # keep=2: step 10 was garbage collected
    dirs = sorted(os.listdir(tmp_path))
    assert not any("0000000010" in d for d in dirs)


def test_checkpoint_atomic_no_partial(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": np.ones(3)})
    # a crashed save leaves only tmp dirs, never a bad step dir
    class Boom(Exception):
        pass
    try:
        orig = np.save
        def bad(*a, **k):
            raise Boom()
        np.save = bad
        with pytest.raises(Boom):
            mgr.save(2, {"w": np.ones(3)})
    finally:
        np.save = orig
    assert mgr.latest_step() == 1


def test_async_checkpointer_snapshot_isolation(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    ck = AsyncCheckpointer(mgr)
    w = np.zeros(8, np.float32)
    ck.save(5, {"w": w})
    w += 100.0  # mutate after snapshot; saved copy must be the old value
    ck.wait()
    _, tensors, _ = mgr.restore(5)
    np.testing.assert_array_equal(tensors["w"], np.zeros(8, np.float32))


# -- fault tolerance --------------------------------------------------------------
def test_retry_step_transient_then_ok():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("link flake")
        return "ok"

    assert retry_step(flaky, retries=5, backoff=0.0) == "ok"
    assert calls["n"] == 3

    def hopeless():
        raise TransientError("dead chip")

    with pytest.raises(TransientError):
        retry_step(hopeless, retries=2, backoff=0.0)


def test_straggler_detector():
    d = StragglerDetector(threshold=2.0, warmup=3)
    for i in range(10):
        assert not d.record(i, 1.0)
    assert d.record(10, 5.0)  # 5x the EMA
    assert not d.record(11, 1.0)  # EMA not poisoned by the straggler
    assert len(d.stragglers) == 1


def test_heartbeat(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path, interval=0.0)
    hb.beat(7, loss=1.5)
    assert Heartbeat.is_alive(path, timeout=60)
    with open(path) as f:
        assert json.load(f)["step"] == 7
    assert not Heartbeat.is_alive(str(tmp_path / "nope.json"))


# -- data pipeline ------------------------------------------------------------------
def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab_size=1000, seq_len=16, global_batch=8, seed=3,
                     n_shards=2, shard=0)
    a = SyntheticLM(cfg).batch(5)
    b = SyntheticLM(cfg).batch(5)  # fresh instance, same (seed, step, shard)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])
    other = SyntheticLM(DataConfig(1000, 16, 8, seed=3, n_shards=2, shard=1))
    assert not np.array_equal(a["tokens"], other.batch(5)["tokens"])
    assert a["tokens"].shape == (4, 16)  # global 8 over 2 shards


def test_prefetcher_resume_at_step():
    cfg = DataConfig(vocab_size=100, seq_len=4, global_batch=2, seed=0)
    src = SyntheticLM(cfg)
    pf = Prefetcher(src, start_step=7)
    step, batch = pf.next()
    pf.close()
    assert step == 7
    np.testing.assert_array_equal(batch["tokens"], src.batch(7)["tokens"])


# -- sharding policy -----------------------------------------------------------------
class _FakeMesh:
    axis_names = ("pod", "data", "model")

    class devices:
        shape = (2, 16, 16)


def test_policy_divisibility_and_used_axes():
    pol = policy_for("default")
    mesh = _FakeMesh()
    # (vocab, embed): vocab -> model(16); embed -> data(16)
    spec = pol.spec_for(ParamInfo("emb", (152064, 8192), None,
                                  ("vocab", "embed")), mesh)
    assert spec[0] == "model" and spec[1] == "data"
    # dim not divisible by the axis -> axis dropped
    spec2 = pol.spec_for(ParamInfo("w", (100, 8192), None,
                                   ("vocab", "embed")), mesh)
    assert spec2[0] is None
    # same mesh axis never used twice in one tensor
    spec3 = pol.spec_for(ParamInfo("w2", (1024, 1024), None,
                                   ("ffn", "heads")), mesh)
    used = [s for s in spec3 if s is not None]
    assert len(set(used)) == len(used)


def test_arch_profiles():
    v3 = policy_for_arch("deepseek-v3-671b")
    mesh = _FakeMesh()
    spec = v3.spec_for(ParamInfo("we", (256, 7168, 2048), None,
                                 ("experts", "embed", "expert_ffn")), mesh)
    assert spec[0] == ("data", "model")  # 256-way expert parallelism
    mix = policy_for_arch("mixtral-8x22b")
    spec2 = mix.spec_for(ParamInfo("we", (8, 6144, 16384), None,
                                   ("experts", "embed", "expert_ffn")), mesh)
    assert spec2[0] is None and spec2[2] == "model"  # per-expert TP instead
    # ZeRO-3-over-pods for the 671B profile
    spec3 = v3.spec_for(ParamInfo("w", (7168, 18432), None,
                                  ("embed", "ffn")), mesh)
    assert spec3[0] == ("pod", "data")


# -- JAX compile cache placement -------------------------------------------------
@pytest.mark.parametrize("from_env", [True, False])
def test_jax_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    without it the cache is one fixed, git-ignored directory of the
    checkout, never a temporary or per-process name."""
    import jax

    from repro.runtime import jax_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
            assert jax_cache.use_jax_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv(jax_cache.ENV, raising=False)
            got = jax_cache.use_jax_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(repo, ".gitignore")) as fh:
                assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
