"""Serving CLI: a thin driver over :class:`repro.launch.engine.ServeEngine`.

The engine owns the hot loop (donated device-resident KV caches,
continuous batching, the KV pool); this module just parses flags, builds
a synthetic workload, and prints the report.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
      --reduced --batch 4 --prompt-len 16 --gen 32 --mode continuous

``--mode paged`` serves through the paged KV pool (``--page-size``,
``--chunk-steps``, ``--pages``) with in-graph sampling: ``--temperature``
/ ``--top-k`` apply to every request (0 = greedy, the default — the
cross-mode parity baseline).  ``--shared-prefix-len N`` makes the first
N prompt tokens identical across requests (a shared system prompt), the
workload the copy-on-write prefix-sharing pool collapses;
``--no-prefix-sharing`` is the unshared baseline leg and
``--prefill-chunk`` sizes the in-graph chunked prefill dispatches
(0 = legacy dense prefill).  ``--report-leg`` names the report so two
same-mode runs can coexist in the serving matrix.

``--smoke`` asserts the run is sane (tok/s > 0, pool stats consistent,
every request fully generated) — used by the CI serving smoke step.
``--report-json FILE`` dumps the EngineReport (results, pool stats,
kv_bytes_per_active_token) for the CI serving matrix's parity check
(``scripts/check_serving_matrix.py``).

``--serve-http`` skips the synthetic workload and instead runs the
asyncio front door (:mod:`repro.launch.server`) over the engine —
streaming ``POST /v1/generate``, ``GET /v1/metrics``, ``GET /healthz`` —
until SIGTERM/SIGINT, then drains gracefully and (with
``--report-json``) writes the served-request report for the CI server
leg.  ``--device`` pins the engine's compiled graphs and KV pool to one
accelerator (``Backend.create("jax", device=...)``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import List, Optional

import numpy as np


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="KV pool slots")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=None,
                    help="KV capacity per slot (default: prompt-len + gen; "
                         "provisioning headroom beyond the workload is "
                         "where the paged pool's savings show)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default=None,
                    choices=("lockstep", "donated", "continuous", "paged"),
                    help="engine mode (default: continuous; paged when "
                         "--serve-http)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged mode: token rows per KV page (default 8)")
    ap.add_argument("--chunk-steps", type=int, default=None,
                    help="paged mode: decode steps fused per dispatch, "
                         "admission only at chunk boundaries (default 4)")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged mode: physical page-pool size (default: "
                         "worst case, slots * ceil(max_len/page_size) + 1)")
    ap.add_argument("--tp", type=int, default=1,
                    help="paged mode: tensor-parallel width — shard the "
                         "chunk/prefill graphs and the KV pool's kv_heads "
                         "dim over a tp-device mesh (greedy outputs match "
                         "tp=1 up to near-ties; on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="paged mode: disable copy-on-write prefix page "
                         "sharing (the unshared baseline leg)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged mode: prompt tokens per in-graph prefill "
                         "dispatch (default 4 pages; 0 = legacy dense "
                         "prefill + host-side scatter)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="synthetic workload: first N prompt tokens are "
                         "identical across requests (shared system "
                         "prompt; 0 = fully independent prompts)")
    ap.add_argument("--report-leg", default=None,
                    help="leg name recorded in --report-json (default: "
                         "the engine mode) so two same-mode reports can "
                         "coexist in the serving matrix")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="paged mode: sampling temperature for every "
                         "request (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="paged mode: top-k cutoff (0 = full vocabulary)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert tok/s > 0 and pool stats are sane")
    ap.add_argument("--report-json", metavar="FILE", default=None,
                    help="dump the EngineReport as JSON (CI serving-matrix "
                         "artifact; parity-checked across modes)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile-cache dir (default: "
                         "$REPRO_CACHE_DIR if set, else disabled)")
    ap.add_argument("--autotune", action="store_true",
                    help="resolve attn_impl/attn_chunk via the autotuner "
                         "(record persisted into --cache-dir)")
    ap.add_argument("--min-disk-hits", type=int, default=None, metavar="N",
                    help="assert >= N persistent-cache disk hits (CI: the "
                         "second run of an unchanged graph must warm-start)")
    ap.add_argument("--serve-http", action="store_true",
                    help="run the asyncio HTTP front door instead of a "
                         "synthetic workload (drains on SIGTERM/SIGINT)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8777,
                    help="--serve-http listen port (0 = ephemeral)")
    ap.add_argument("--max-wait-queue", type=int, default=8,
                    help="--serve-http: accepted-but-unadmitted request "
                         "bound; beyond it new requests get 429")
    ap.add_argument("--device", default=None,
                    help="pin the engine to one accelerator, e.g. 'cpu:0' "
                         "(jax device placement)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault-injection spec, e.g. "
                         "'dispatch.raise=after:3,admit.reject=prob:0.2' "
                         "(see repro.launch.faults; also $REPRO_FAULTS)")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="seed for probabilistic fault rules")
    ap.add_argument("--request-timeout", type=float, default=None,
                    help="per-request deadline in seconds (synthetic "
                         "workload: passed as deadline_s to every submit)")
    ap.add_argument("--max-body-bytes", type=int, default=1 << 20,
                    help="--serve-http: request bodies beyond this get 413")
    args = ap.parse_args(argv)

    from ..runtime.jax_cache import use_jax_compile_cache
    use_jax_compile_cache()
    if args.faults is not None:
        from .faults import configure
        configure(args.faults, args.faults_seed)

    from ..configs import get_config
    from .engine import EngineConfig, ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n_req = args.requests or args.batch
    P, G = args.prompt_len, args.gen
    max_len = args.max_len or (P + G)
    if max_len < P + G:
        raise SystemExit(f"--max-len {max_len} < prompt-len + gen ({P + G})")

    mode = args.mode or ("paged" if args.serve_http else "continuous")
    if args.serve_http and mode not in ("continuous", "paged"):
        raise SystemExit(
            f"--serve-http needs a step()-capable engine "
            f"(--mode continuous|paged), got {mode!r}")
    if cfg.family != "dense" and mode != "lockstep":
        if mode == "paged":
            # an explicit paged request must not silently fall back to a
            # mode that ignores its page/sampling flags
            raise SystemExit(
                f"--mode paged needs the dense family's serve graphs; "
                f"{cfg.name} ({cfg.family}) only serves via "
                f"--mode lockstep")
        print(f"[serve] {cfg.name} ({cfg.family}): no serve/chunk graphs "
              f"yet, falling back to --mode lockstep")
        mode = "lockstep"
    if mode != "paged" and (args.temperature or args.top_k):
        # never silently decode greedy when the user asked for sampling
        raise SystemExit(
            f"--temperature/--top-k need --mode paged (in-graph sampling); "
            f"mode {mode!r} decodes greedily")
    if mode != "paged" and any(v is not None for v in
                               (args.page_size, args.chunk_steps,
                                args.pages, args.prefill_chunk)):
        raise SystemExit(
            f"--page-size/--chunk-steps/--pages/--prefill-chunk need "
            f"--mode paged; mode {mode!r} uses fixed per-slot cache rows")
    if mode != "paged" and args.no_prefix_sharing:
        raise SystemExit(
            f"--no-prefix-sharing needs --mode paged; mode {mode!r} "
            f"never shares KV pages")
    if not 0 <= args.shared_prefix_len <= P:
        raise SystemExit(
            f"--shared-prefix-len {args.shared_prefix_len} must be in "
            f"[0, --prompt-len {P}]")
    try:
        econf = EngineConfig(
            mode=mode, slots=args.batch, max_len=max_len, seed=args.seed,
            page_size=args.page_size, chunk_steps=args.chunk_steps,
            pages=args.pages, device=args.device, tp=args.tp,
            prefix_sharing=(False if args.no_prefix_sharing else None),
            prefill_chunk=args.prefill_chunk,
            cache_dir=args.cache_dir, autotune=args.autotune)
        engine = ServeEngine(cfg, econf)
    except ValueError as e:
        raise SystemExit(str(e))
    if args.serve_http:
        return _serve_http(engine, args, cfg, mode, max_len)
    sampling = {}
    if mode == "paged" and (args.temperature or args.top_k):
        sampling = dict(temperature=args.temperature, top_k=args.top_k)
    rng = np.random.default_rng(args.seed)
    S = args.shared_prefix_len
    # with S == 0 this is byte-identical to the historical recipe (one
    # rng, one sequential draw per request) so existing matrix legs and
    # their recorded token streams are unchanged
    shared = rng.integers(0, cfg.vocab, size=(S,)) if S else None
    prompts = []
    for _ in range(n_req):
        if S == P:
            prompts.append(shared.copy())
        elif S:
            prompts.append(np.concatenate(
                [shared, rng.integers(0, cfg.vocab, size=(P - S,))]))
        else:
            prompts.append(rng.integers(0, cfg.vocab, size=(P,)))
    rids = [engine.submit(prompts[i], G,
                          deadline_s=args.request_timeout,
                          **(dict(sampling, key=i) if sampling else {}))
            for i in range(n_req)]
    rep = engine.run()

    print(f"[serve:{rep.mode}] {n_req} reqs x {G} tokens "
          f"(prompt {P}, {args.batch} slots) in {rep.wall_seconds:.2f}s "
          f"({rep.tok_s:.1f} tok/s e2e, {rep.decode_tok_s:.1f} tok/s decode, "
          f"p50 {rep.p50_ms:.2f}ms p95 {rep.p95_ms:.2f}ms/token, "
          f"ttft p50 {rep.ttft_p50_ms:.1f}ms p95 {rep.ttft_p95_ms:.1f}ms, "
          f"{rep.steps} steps, late admissions {rep.late_admissions})")
    if rep.pool is not None:
        p = rep.pool
        if mode == "paged":
            print(f"[kv-pool:paged] slots={p.slots} pages={p.pages} "
                  f"page_size={p.page_size} bytes/page={p.bytes_per_page} "
                  f"in_use={p.pages_in_use} peak={p.peak_pages_in_use} "
                  f"frag={p.fragmentation:.3f} "
                  f"page_allocs={p.page_allocs} page_frees={p.page_frees} "
                  f"cow={p.cow_copies} attach={p.shared_attaches} "
                  f"arena={p.decode_arena_bytes}B")
            if rep.tp > 1:
                print(f"[kv-pool:tp] tp={rep.tp} "
                      f"bytes/device={rep.kv_bytes_per_device} "
                      f"(global {p.total_bytes}B)")
            if rep.kv_bytes_per_active_token is not None:
                # None: no decode dispatch ran (e.g. --gen 1 finishes
                # every request straight out of prefill)
                print(f"[kv-bytes/active-token] "
                      f"{rep.kv_bytes_per_active_token:.1f}")
        else:
            print(f"[kv-pool] slots={p.slots} bytes/slot={p.bytes_per_slot} "
                  f"total={p.total_bytes} allocs={p.allocs} frees={p.frees} "
                  f"peak_active={p.peak_active} "
                  f"arena={p.decode_arena_bytes}B")
    st = engine.cache_stats()
    print(f"[compile-cache] hits={st.hits} misses={st.misses} size={st.size} "
          f"disk_hits={st.disk_hits} disk_misses={st.disk_misses} "
          f"disk_evictions={st.disk_evictions} "
          f"autotune_hits={st.autotune_hits} "
          f"autotune_sweeps={st.autotune_sweeps}")
    for rid in rids[:2]:
        print(f"  req{rid}: {rep.results[rid][:12].tolist()} ...")

    if args.smoke:
        assert rep.tok_s > 0, "tok/s must be positive"
        assert all(len(rep.results[r]) == G for r in rids), \
            "every request must generate all tokens"
        if rep.pool is not None:
            p = rep.pool
            assert p.allocs == n_req and p.frees == n_req, \
                f"allocs/frees must match requests ({p.allocs}/{p.frees})"
            assert p.total_bytes > 0
            if mode == "paged":
                assert p.active == 0 and p.pages_in_use == 0, \
                    "paged pool must return every page when requests finish"
                assert p.page_allocs == p.page_frees, \
                    f"page leak: {p.page_allocs} allocs vs " \
                    f"{p.page_frees} frees"
                # each active request wastes at most one partial page
                bound = -(-n_req * (P + G) // p.page_size) + p.slots
                assert p.peak_pages_in_use <= bound, \
                    f"peak pages {p.peak_pages_in_use} > bound {bound}"
                assert p.ref_allocs == p.ref_frees, \
                    f"page-reference leak: {p.ref_allocs} ref allocs vs " \
                    f"{p.ref_frees} ref frees"
                bad = engine.pool.verify()
                assert not bad, f"pool.verify() found: {bad}"
            else:
                assert p.active == 0 and p.occupancy == 0.0, \
                    "pool must drain when all requests finish"
                assert p.bytes_per_slot > 0
        print("[smoke] ok")
    if args.report_json:
        doc = dataclasses.asdict(rep)
        doc["leg"] = args.report_leg or mode
        doc["results"] = {str(r): rep.results[r].tolist() for r in rids}
        doc["workload"] = {"requests": n_req, "prompt_len": P, "gen": G,
                           "slots": args.batch, "max_len": max_len,
                           "seed": args.seed,
                           "temperature": args.temperature,
                           "top_k": args.top_k,
                           "shared_prefix_len": S,
                           "prefix_sharing": engine.prefix_sharing,
                           "prefill_chunk": engine.prefill_chunk,
                           "tp": engine.tp}
        if mode == "paged":
            doc["pool_verify"] = engine.pool.verify()
        with open(args.report_json, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"[report] wrote {args.report_json}")
    if args.min_disk_hits is not None:
        assert st.disk_hits >= args.min_disk_hits, (
            f"expected >= {args.min_disk_hits} persistent-cache disk hits, "
            f"got {st.disk_hits} (misses={st.disk_misses}) — the warm run "
            f"did not reuse the on-disk compile cache")
        if args.autotune:
            assert st.autotune_sweeps == 0, (
                f"warm run re-swept {st.autotune_sweeps} graphs — tuning "
                f"records were not reused")
        print(f"[disk-cache] ok ({st.disk_hits} hits, "
              f"{st.autotune_sweeps} sweeps)")
    return _exit_code(engine)


def _exit_code(engine) -> int:
    """1 when a request failed or a dispatch raised while no fault was
    injected on purpose: the engine contains such failures to keep
    serving, so the exit code is where a broken device path shows."""
    failed = engine.counters["failed"]
    errors = engine.counters["engine_errors"]
    if (failed or errors) and not engine.faults.rules:
        print(f"[serve] ERROR: {failed} request(s) failed, {errors} "
              f"engine error(s) with no fault injected", flush=True)
        return 1
    return 0


def _serve_http(engine, args, cfg, mode, max_len) -> int:
    """The --serve-http path: front door up, drain on SIGTERM/SIGINT,
    then print/emit the served-request report."""
    from .server import ServeHTTPServer

    srv = ServeHTTPServer(engine, host=args.host, port=args.port,
                          max_wait_queue=args.max_wait_queue,
                          max_body_bytes=args.max_body_bytes)
    srv.serve_forever(on_ready=lambda: print(
        f"[serve-http:{mode}] {cfg.name} listening on {srv.base_url} "
        f"(slots={args.batch} max_len={max_len} "
        f"wait_queue={args.max_wait_queue})", flush=True))

    snap = srv.stats.snapshot()
    print(f"[serve-http] drained: {snap['requests_completed']} completed / "
          f"{snap['requests_accepted']} accepted "
          f"(429s {snap['rejected_429']}, 503s {snap['rejected_503']}), "
          f"{snap['tokens_streamed']} tokens streamed, "
          f"ttft p50 {snap['ttft_p50_ms']:.1f}ms "
          f"p95 {snap['ttft_p95_ms']:.1f}ms, "
          f"tok p50 {snap['tok_p50_ms']:.1f}ms "
          f"p95 {snap['tok_p95_ms']:.1f}ms, "
          f"sustained {snap['sustained_tok_s']:.1f} tok/s, "
          f"drain_ok={srv.drain_ok}")
    if args.report_json:
        doc = srv.report_doc()
        doc["leg"] = args.report_leg or doc.get("mode") or "server"
        doc["workload"] = {"requests": args.requests or args.batch,
                           "prompt_len": args.prompt_len, "gen": args.gen,
                           "slots": args.batch, "max_len": max_len,
                           "seed": args.seed,
                           "temperature": args.temperature,
                           "top_k": args.top_k}
        with open(args.report_json, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"[report] wrote {args.report_json}")
    if not srv.drain_ok:
        print("[serve-http] ERROR: drain left engine state behind "
              "(see report)", flush=True)
        return 1
    return _exit_code(engine)


if __name__ == "__main__":
    raise SystemExit(main())
