"""Serve deepseek-7b at its published widths on a TPU, end to end.

Everything runs in this one process, because a chip belongs to one
process at a time:

1. ``get_config("deepseek-7b")`` at its published widths (d_model 4096,
   32 MHA heads of 128, d_ff 11008, vocab 102400), depth cut from 30 to
   4 layers: the f32 weights of all 30 layers (27.6 GB) exceed one v5e's
   16 GB, and 4 leave room for the decode and prefill programs.  Weights
   and prompts are random, drawn from :data:`SEED`.
2. A paged ``ServeEngine``: 8 slots, max_len 2048, the default page
   size, chunk steps and prefill chunk.
3. Set-up: one request of one prefill chunk compiles the prefill-chunk
   and decode-chunk programs; ``compile_s`` is the XLA compile (or
   persistent-cache read) time JAX reports up to its end.  Its
   prompt is the reference check: the dense prefill graph compiled for
   the device must return finite logits that agree with the numpy
   interpreter's, and the engine's first token must be the
   interpreter's top token up to that tolerance.
4. Serving: 8 seeded requests with prompts of 128 to 1024 tokens, in
   whole prefill chunks, half of them behind one shared 256-token prefix
   (copy-on-write page sharing, chunked prefill); 32 greedy tokens each.
5. The same engine behind ``running_server``: 4 streaming clients repeat
   4 of those prompts and must stream exactly the engine's tokens.

Every request must end ``completed`` with no engine error and health
``ok``, and the page pool must drain clean.  ``--tp 4`` runs only the
tensor-parallel phase on four chips: the same workload served at tp=1
on device 0, then at tp=4 with a quarter of the KV pool on each device.
The two programs round differently, so a greedy stream may part from
tp=1's, but only at a near-tie: both tokens near the top logit for the
common prefix, both in the dense prefill compiled for the device and in
the numpy interpreter run in f32.

The script exits non-zero when JAX finds no TPU, when the repository's
``src/`` is not beside it, or when a check fails.  The last line of its
standard output is JSON: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  Times and rates it prints are smoke
readings, not benchmark results.

Run:  python chip_smoke.py            (one chip)
      python chip_smoke.py --tp 4     (four chips)
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

N_LAYERS = 4
SLOTS, MAX_LEN, GEN = 8, 2048, 32
N_REQUESTS, SHARED_PREFIX = 8, 256
PROMPT_MIN, PROMPT_MAX = 128, 1024
N_CLIENTS = 4
SEED = 0
# Logit tolerances, as shares of the largest logit's magnitude, each
# twice the largest reading on a TPU v5e.  REF_TOL: device bf16 logits
# against the interpreter's (0.0101).  Where tp=1 and tp=4 greedy
# streams part, how far below the top logit the two tokens may sit:
# TIE_TOL in the device's dense prefill (0.0058), F32_TIE_TOL in the
# interpreter computing in f32 (0.0097; bf16 rounding moved device
# logits up to 0.0172 away from f32 there).  A wrong layer, mask or
# weight moves logits far more.
REF_TOL = 0.02
TIE_TOL = 0.01
F32_TIE_TOL = 0.02


class SmokeError(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def model_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config("deepseek-7b"), n_layers=N_LAYERS)


def make_engine(cfg, tp: int = 1):
    from repro.launch.engine import EngineConfig, ServeEngine

    return ServeEngine(cfg, EngineConfig(mode="paged", slots=SLOTS,
                                         max_len=MAX_LEN, seed=SEED, tp=tp))


def make_prompts(vocab: int, chunk: int):
    """Prompt lengths are whole prefill chunks, so every prefill dispatch
    runs the one chunk program the set-up request compiled.  The first
    half share a 256-token prefix and are longer than it: a sharer
    resumes prefill on a chunk boundary after the attached pages."""
    rng = np.random.default_rng(SEED)
    shared = rng.integers(0, vocab, size=(SHARED_PREFIX,))
    prompts = []
    for i in range(N_REQUESTS):
        sharer = i < N_REQUESTS // 2
        lo = (SHARED_PREFIX + chunk if sharer else PROMPT_MIN) // chunk
        n = int(rng.integers(lo, PROMPT_MAX // chunk + 1)) * chunk
        p = rng.integers(0, vocab, size=(n,))
        if sharer:
            p[:SHARED_PREFIX] = shared
        prompts.append(p.astype(np.int32))
    return prompts


def check_report(engine, rep, rids) -> None:
    """Every request completed with all its tokens, no dispatch failed,
    and the page pool drained with balanced accounting."""
    bad = {r: rep.statuses[r] for r in rids if rep.statuses[r] != "completed"}
    check(not bad, f"requests not completed: {bad} {rep.errors}")
    check(all(len(rep.results[r]) == GEN for r in rids),
          "a request generated fewer tokens than asked")
    check(rep.counters["engine_errors"] == 0,
          f"engine errors: {rep.counters}")
    check(rep.health == "ok", f"engine health {rep.health!r}")
    problems = engine.pool.verify()
    check(problems == [], f"pool.verify(): {problems}")
    p = rep.pool
    check(p.pages_in_use == 0 and p.active == 0,
          f"pool not drained: {p.pages_in_use} pages, {p.active} slots")
    check(p.ref_allocs == p.ref_frees,
          f"page references leak: {p.ref_allocs} vs {p.ref_frees}")


def _prefill_graph(cfg, length: int):
    from repro.configs.base import ShapeConfig
    from repro.models.lm import build_graphs

    return build_graphs(cfg, ShapeConfig("prefill", "prefill", length, 1), 1)


def device_logits(engine, cfg, seq: np.ndarray) -> np.ndarray:
    """Next-token logits after ``seq`` from the dense prefill graph (not
    the engine's paged chunks), compiled for the device and run on the
    engine's weights where the engine placed them."""
    from repro.backend import Backend

    g = _prefill_graph(cfg, len(seq))
    on_device = dict(zip(engine.graphs.builder.param_names(),
                         engine.jparams))
    cf = Backend.create("jax").compile(g.fn)
    out = cf.raw(seq.reshape(1, -1),
                 *[on_device[n] for n in g.builder.param_names()])
    return np.asarray(out[0], np.float32).reshape(-1)


def interpreter_logits(engine, cfg, seq: np.ndarray) -> np.ndarray:
    """The same logits from the numpy interpreter on the host, with the
    engine's own weights, in ``cfg``'s compute dtype."""
    from repro.backend import Backend

    g = _prefill_graph(cfg, len(seq))
    out = Backend.create("interpreter").compile(g.fn)(
        seq.reshape(1, -1),
        *[engine.params[n] for n in g.builder.param_names()])
    return np.asarray(out[0], np.float32).reshape(-1)


def gap(logits: np.ndarray, *tokens: int) -> float:
    """How far the lowest of ``tokens`` sits below the top logit, as a
    share of the largest logit's magnitude."""
    low = min(float(logits[t]) for t in tokens)
    return (float(logits.max()) - low) / float(np.abs(logits).max())


def reference_check(engine, cfg, prompt: np.ndarray, first: int) -> dict:
    """Device logits for ``prompt`` against the numpy interpreter's."""
    dev = device_logits(engine, cfg, prompt)
    t0 = time.perf_counter()
    ref = interpreter_logits(engine, cfg, prompt)
    host_s = time.perf_counter() - t0
    check(dev.shape == ref.shape == (cfg.vocab,),
          f"logits shape {dev.shape}, reference {ref.shape}")
    check(bool(np.isfinite(dev).all()), "device logits are not finite")
    err = float(np.abs(dev - ref).max()) / float(np.abs(ref).max())
    check(err <= REF_TOL,
          f"device logits differ from the interpreter by {err} of the "
          f"largest logit (tolerance {REF_TOL})")
    first_gap = gap(ref, first)
    check(first_gap <= REF_TOL,
          f"engine's first token {first} is {first_gap} of the largest "
          f"logit below the interpreter's top token {int(ref.argmax())}")
    return {"logits_rel_err": err, "first_token_gap": first_gap,
            "interpreter_seconds": host_s}


async def _stream_all(url: str, prompts, tags):
    from repro.launch import loadgen

    return await asyncio.gather(*[
        loadgen.stream_generate(url, {"prompt": [int(t) for t in p],
                                      "max_new": GEN, "tag": tag})
        for p, tag in zip(prompts, tags)])


def run_smoke(cfg) -> dict:
    """Phases 2-5 on the default device; returns what to print."""
    import jax

    # XLA compile seconds, or persistent-cache reads where the cache hits
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _serve(cfg, compile_s)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _serve(cfg, compile_s) -> dict:
    import jax

    from repro.launch.server import running_server

    out = {}
    t0 = time.perf_counter()
    engine = make_engine(cfg)
    out["load_s"] = time.perf_counter() - t0

    # set-up: compiles the prefill-chunk and decode-chunk programs
    chunk = engine.prefill_chunk
    ref_prompt = np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab, size=(chunk,)).astype(np.int32)
    t0 = time.perf_counter()
    rid0 = engine.submit(ref_prompt, GEN)
    rep = engine.run()
    out["setup_request_s"] = time.perf_counter() - t0
    out["compile_s"] = compile_s[0]
    check_report(engine, rep, [rid0])
    t0 = time.perf_counter()
    out.update(reference_check(engine, cfg, ref_prompt,
                               int(rep.results[rid0][0])))
    out["reference_s"] = time.perf_counter() - t0
    misses = engine.cache_stats().misses

    # serving, straight through the engine
    prompts = make_prompts(cfg.vocab, chunk)
    t0 = time.perf_counter()
    rids = [engine.submit(p, GEN) for p in prompts]
    rep = engine.run()
    out["serve_s"] = time.perf_counter() - t0
    check_report(engine, rep, rids)
    check(rep.pool.shared_attaches > 0, "no prefix page was shared")
    out["serve_tok_s"] = len(rids) * GEN / out["serve_s"]
    out["engine_decode_tok_s"] = rep.decode_tok_s
    out["shared_attaches"] = rep.pool.shared_attaches
    engine_tokens = {tuple(p): [int(t) for t in rep.results[r]]
                     for p, r in zip(prompts, rids)}

    # the same engine behind the HTTP front door, streaming clients
    half = N_REQUESTS // 2
    picks = list(range(N_CLIENTS // 2)) + \
        list(range(half, half + N_CLIENTS - N_CLIENTS // 2))
    repeat = [prompts[i] for i in picks]
    tags = [f"client{i}" for i in range(len(repeat))]
    t0 = time.perf_counter()
    with running_server(engine, max_wait_queue=N_CLIENTS) as srv:
        results = asyncio.run(_stream_all(srv.base_url, repeat, tags))
    out["http_s"] = time.perf_counter() - t0
    for p, tag, res in zip(repeat, tags, results):
        check(res.status == 200 and res.error is None
              and res.terminal == "completed",
              f"{tag}: HTTP {res.status} {res.terminal} {res.error}")
        check(res.tokens == engine_tokens[tuple(p)],
              f"{tag}: streamed tokens differ from the engine's")
    check(srv.drain_ok, "server drain left engine state behind")
    srep = srv.engine_report
    check(srep is not None and srep.counters["engine_errors"] == 0
          and srep.health == "ok",
          f"server engine: {srep and srep.counters} "
          f"{srep and srep.health}")
    check(engine.pool.verify() == [] and engine.pool.pages_in_use == 0,
          "pool not clean after the server drained")
    out["compiles_while_serving"] = engine.cache_stats().misses - misses
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return out


def _kv_split(engine, tp: int) -> None:
    """Each KV buffer lives on ``tp`` devices, a 1/tp shard on each."""
    for buf in engine.pool.buffers:
        shards = buf.addressable_shards
        check(len({s.device for s in shards}) == tp,
              f"KV buffer on {len({s.device for s in shards})} devices")
        check(all(s.data.nbytes * tp == buf.nbytes for s in shards),
              "KV shards are not 1/tp of the buffer")


def run_tp(cfg, tp: int) -> dict:
    """The same workload at tp=1 on device 0, then at ``tp``."""
    import jax

    devices = jax.devices()[:tp]
    check(len(devices) == tp, f"--tp {tp} needs {tp} devices, "
                              f"JAX sees {len(jax.devices())}")
    out = {}
    tokens = {}
    for width in (1, tp):
        t0 = time.perf_counter()
        engine = make_engine(cfg, tp=width)
        prompts = make_prompts(cfg.vocab, engine.prefill_chunk)
        rids = [engine.submit(p, GEN) for p in prompts]
        rep = engine.run()
        out[f"tp{width}_s"] = time.perf_counter() - t0
        check_report(engine, rep, rids)
        tokens[width] = [[int(t) for t in rep.results[r]] for r in rids]
        check(rep.kv_bytes_per_device * width == engine.pool.total_bytes,
              f"kv_bytes_per_device {rep.kv_bytes_per_device} at "
              f"tp={width}, pool {engine.pool.total_bytes}")
        if width == 1:
            del engine, rep
            gc.collect()
            continue
        _kv_split(engine, tp)
        in_use = []
        for d in devices:
            stats = d.memory_stats()
            check(stats is not None or d.platform == "cpu",
                  f"{d} reports no memory_stats()")
            in_use.append((stats or {}).get("bytes_in_use"))
        # the pool alone puts a quarter of its bytes on every device
        share = engine.pool.total_bytes // tp
        check(all(b is None or b >= share for b in in_use),
              f"bytes_in_use per device {in_use} below the KV share "
              f"{share}: the pool is not split")
        out["bytes_in_use_per_device"] = in_use
        out["kv_bytes_per_device"] = rep.kv_bytes_per_device
        out["kv_bytes_total"] = engine.pool.total_bytes
    out["identical_requests"] = sum(a == b for a, b in
                                    zip(tokens[1], tokens[tp]))
    out["parted_at"] = near_ties(engine, cfg, prompts, tokens[1],
                                 tokens[tp])
    return out


def near_ties(engine, cfg, prompts, stream_a, stream_b) -> dict:
    """Where two greedy streams of one prompt part, both tokens must be
    near the top logit for the common prefix in two witnesses: within
    :data:`TIE_TOL` in the dense prefill compiled for the device (the
    engine's weights and compute dtype), and within :data:`F32_TIE_TOL`
    in the numpy interpreter computing in f32.  Then the parting is a tie that rounding may break either way,
    not a wrong program.  Returns request -> (first differing token,
    device gap, f32 interpreter gap)."""
    f32 = dataclasses.replace(cfg, compute_dtype="f32")
    parted = {}
    for i, (p, a, b) in enumerate(zip(prompts, stream_a, stream_b)):
        k = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if k is None:
            continue
        seq = np.concatenate([p, np.asarray(a[:k], np.int32)])
        gaps = (gap(device_logits(engine, cfg, seq), a[k], b[k]),
                gap(interpreter_logits(engine, f32, seq), a[k], b[k]))
        check(gaps[0] <= TIE_TOL and gaps[1] <= F32_TIE_TOL,
              f"request {i} parts at token {k} ({a[k]} vs {b[k]}), "
              f"{gaps} (device, f32 interpreter) of the largest logit "
              f"below the top: not a tie")
        parted[i] = (k,) + gaps
    return parted


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips (4 on one v5e host)")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: FAIL: the repository's src/repro is not beside "
              f"{__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.runtime.jax_cache import use_jax_compile_cache

    cache_dir = use_jax_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"chip_smoke: FAIL: JAX found no device: {exc}",
              file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX's first device is "
              f"{dev.platform})", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    cfg = model_config()
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.head_dim} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab}, {cfg.n_layers} of 30 layers, params "
          f"{cfg.param_dtype}, compute {cfg.compute_dtype}", flush=True)
    try:
        out = run_tp(cfg, args.tp) if args.tp > 1 else run_smoke(cfg)
    except SmokeError as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr)
        return 1
    for k, v in out.items():
        print(f"{k}: {v}")
    if args.tp == 1:
        print(f"smoke reading, not a benchmark: {out['serve_tok_s']} tok/s "
              f"over {N_REQUESTS} requests x {GEN} tokens")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
