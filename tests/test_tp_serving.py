"""Tensor-parallel paged serving (PR 10): ``EngineConfig(tp=2)`` shards
the paged KV pool over KV heads via the partition pass + shard_map.

The contract under test, on a 2-device CPU mesh (subprocess, so the
main test process keeps its single-device view):

  * greedy decode of these short requests is token-for-token identical
    to ``tp=1`` — the column-parallel profile never splits a
    contraction (longer streams may part at a near-tie, where the
    per-device programs round differently: ``chip_smoke.py --tp``);
  * each device holds ``n_kv_heads/tp`` heads of every page:
    ``EngineReport.kv_bytes_per_device`` is exactly half the global
    pool bytes, and the partition stats show the inserted AllGathers
    (and zero AllReduces);
  * the host-side pool is oblivious to tp: prefix sharing / COW / cancel
    accounting (tests/test_prefix.py's workloads) moves identically at
    tp=2 and drains to zero.
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    from repro.configs import get_config
    from repro.launch.engine import EngineConfig, ServeEngine

    CFG = get_config("deepseek-7b").reduced()

    def eng(tp, **kw):
        base = dict(mode="paged", slots=3, max_len=16, seed=0,
                    page_size=4, chunk_steps=2)
        base.update(kw)
        return ServeEngine(CFG, EngineConfig(tp=tp, **base))

    rng = np.random.default_rng(11)

    # -- 1) greedy parity + per-device KV accounting --------------------
    prompts = [rng.integers(0, CFG.vocab, size=(n,)).astype(np.int32)
               for n in (4, 7, 9)]

    def run(tp):
        e = eng(tp, slots=4, max_len=24)
        rids = [e.submit(p, 6) for p in prompts]
        rep = e.run()
        assert e.pool.pages_in_use == 0 and e.pool.verify() == []
        return e, rep, [[int(t) for t in rep.results[r]] for r in rids]

    e1, rep1, toks1 = run(1)
    e2, rep2, toks2 = run(2)
    assert toks2 == toks1, "tp=2 greedy must be token-identical to tp=1"
    assert rep1.tp == 1 and rep2.tp == 2
    assert rep1.kv_bytes_per_device == e1.pool.total_bytes
    assert rep2.kv_bytes_per_device * 2 == e2.pool.total_bytes
    st = e2.cf.report.stats.get("partition")
    assert st is not None, "partitioned compile must report its stats"
    assert st.get("params_sharded", 0) >= 1
    assert st.get("all_gather", 0) >= 1
    assert st.get("all_reduce", 0) == 0, "exact profile: no split sums"
    assert e2.live_stats().get("tp") == 2
    # placed once with the partition pass's shardings: every device holds
    # its own half of each KV buffer and of each column-sharded weight
    for buf in e2.pool.buffers:
        shards = buf.addressable_shards
        assert len({s.device for s in shards}) == 2
        assert all(s.data.nbytes * 2 == buf.nbytes for s in shards)
    wq = e2._jparam_map["layers/attn_wq"]
    assert wq.committed and "model" in tuple(wq.sharding.spec)
    assert all(s.data.nbytes * 2 == wq.nbytes for s in wq.addressable_shards)
    print("TP-PARITY-OK")

    # -- 2) prefix sharing / COW: host accounting oblivious to tp -------
    prompt = rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)
    solo = eng(2)
    rid = solo.submit(prompt, 8)
    ref = [int(t) for t in solo.run().results[rid]]

    def shared_run(tp):
        e = eng(tp)
        rids = [e.submit(prompt, 8) for _ in range(3)]
        rep = e.run()
        assert all([int(t) for t in rep.results[r]] == ref for r in rids)
        p = rep.pool
        assert p.pages_in_use == 0 and p.active == 0
        assert p.ref_allocs == p.ref_frees
        assert e.pool.verify() == []
        return p

    p2, p1 = shared_run(2), shared_run(1)
    assert p2.shared_attaches >= 4 and p2.cow_copies >= 2
    assert (p2.shared_attaches, p2.cow_copies,
            p2.page_allocs, p2.page_frees) == \
           (p1.shared_attaches, p1.cow_copies,
            p1.page_allocs, p1.page_frees), "sharing must not see tp"
    print("TP-PREFIX-OK")

    # -- 3) cancel mid-prefill releases shared pages under tp=2 ---------
    base = rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)
    longp = np.concatenate(
        [base, rng.integers(0, CFG.vocab, size=(8,)).astype(np.int32)])
    kw = dict(slots=2, max_len=24, chunk_steps=4, prefill_chunk=4)
    solo = eng(2, **kw)
    rid = solo.submit(base, 8)
    ref = [int(t) for t in solo.run().results[rid]]

    e = eng(2, **kw)
    rp = e.submit(base, 8)
    rl = e.submit(longp, 4)
    for _ in range(3):  # publisher prefills + publishes; sharer attaches
        e.step()
    assert e._requests[rl].prefill_pos is not None, "sharer mid-prefill"
    assert e.pool.stats().shared_attaches >= 2
    assert e.cancel(rl, "tp test") is True
    e.step()
    rep = e.run()
    assert rep.statuses[rl] == "cancelled"
    assert [int(t) for t in rep.results[rp]] == ref
    p = rep.pool
    assert p.pages_in_use == 0 and p.ref_allocs == p.ref_frees
    assert p.page_allocs == p.page_frees
    assert e.pool.verify() == []
    print("TP-CANCEL-OK")
""")


def test_tp2_serving_parity_prefix_cancel():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=__file__.rsplit("/tests/", 1)[0])
    out = proc.stdout
    assert "TP-PARITY-OK" in out, proc.stderr[-4000:]
    assert "TP-PREFIX-OK" in out, proc.stderr[-4000:]
    assert "TP-CANCEL-OK" in out, proc.stderr[-4000:]


def test_partition_bench_spawns_no_jax_child_on_an_accelerator(monkeypatch):
    """E17's forced-CPU child runs only on a CPU host: a parent that holds
    a chip refuses instead of starting a child that cannot reach it."""
    import importlib.util
    import os

    import jax
    import pytest

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: pytest.fail(
        "started a JAX child from a process that holds the chip"))
    with pytest.raises(RuntimeError, match="chip_smoke.py --tp 4"):
        bench.bench_partition()
