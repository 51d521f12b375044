"""Serving benchmark: one cell of ``BENCHMARK.json`` on one TPU v5e.

    python3 benchmarks/serve/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``).  This process holds the chip: it makes the
configuration's weights on the device from the seed, builds the paged
``ServeEngine`` over them, warms up every program the mix can dispatch,
and serves ``POST /v1/generate`` through ``ServeHTTPServer``.  The load
generator runs in a child process that never imports JAX.  Set-up
(``setup_s``) is everything from process start to the first due
request.  The load then runs for the mix's ``warm_in_s`` before the
window opens, so that the window sees the mix's steady state; the window
lasts ``--seconds``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the middle of the window and reports its per-layer metrics, each read by
``metrics/<metric>.py`` from the reduced device trace, the engine's
gauges and counters, and the program's host spans.  The device trace is
reduced on a TPU only; a traced run elsewhere (a test's, on the CPU)
reports what the spans and counters give.  After the window, the
program's state is freed and a sample of the finished requests is
compared with the plain reference (``reference.py``), over weights it
makes anew from the seed; each number compared is printed beside its
limit, on standard error and under ``checks`` in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks``.  Without a TPU (or a platform asked for
by ``--platform``), or with fewer chips than the cell needs, it exits 1
and prints no result; without the program (``src/``) beside it, 2.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from servebench import cells, harness, traffic  # noqa: E402
from servebench.client import GRACE_S  # noqa: E402
from servebench.record import Run, percentile  # noqa: E402

T_PROCESS = harness.process_start()


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu",
                    help="the JAX platform the run must find (tests ask "
                         "for cpu by name)")
    return ap.parse_args(argv)


def _devices(platform: str, chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"bench: JAX found no device: {exc}")
    if devs[0].platform != platform:
        raise SystemExit(f"bench: no {platform} device (JAX's first device "
                         f"is {devs[0].platform})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def main(argv=None, control: bool = False) -> int:
    """One run.  ``control`` (``control.py`` only, never the benchmark's
    own runs) then judges the float8 control on the same sample by the
    same checks, and prints its result line last."""
    args = parse(argv)
    bench = cells.benchmark()
    wl = cells.workload(args.workload, bench)
    conf = cells.load_config(wl["config"])
    cells.counts(conf)      # a configuration names its count module
    mix = cells.load_traffic(wl["traffic"])
    e2e = cells.metrics_for(wl["name"], "end_to_end", bench)
    layer = cells.metrics_for(wl["name"], "per_layer", bench)
    readers = cells.load_metric_readers([m["name"] for m in layer]) \
        if args.trace else {}
    try:
        cells.add_program_path()
    except cells.CellError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    harness.use_checkout_cache()
    import importlib

    from servebench import check
    # the configuration names its plain reference, a module beside bench.py
    reference = importlib.import_module(conf["reference"])

    n_req = traffic.stream_length(mix, conf["engine"]["slots"], args.seconds)
    client = harness.Client(mix, args.seed, conf["vocab"],
                            conf["engine"]["slots"], n_req)
    try:
        devs = _devices(args.platform, int(wl["chips"]))
    except SystemExit as exc:
        client.stop()
        print(str(exc), file=sys.stderr)
        return 1
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    try:
        return _run(args, wl, conf, mix, e2e, layer, readers, client,
                    devs, reference, check, control)
    finally:
        client.stop()


def _run(args, wl, conf, mix, e2e, layer, readers, client, devs,
         reference, check, control) -> int:
    import jax
    from repro.launch.server import ServeHTTPServer

    dev = devs[0]
    compiles = harness.CompileCounter()
    cfg = cells.model_config(conf)
    t = time.perf_counter()
    weights = jax.block_until_ready(reference.make_weights(conf, args.seed))
    log(f"weights: made on the device in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    engine = harness.build_engine(cfg, conf, args.seed, weights)
    del weights
    knobs = harness.engine_knobs(engine)
    log(f"engine: {json.dumps(knobs)} built in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    lengths = harness.warm_up(engine, mix, conf["vocab"], args.seed)
    log(f"warm-up: {len(lengths)} prefill-chunk lengths + decode chunk in "
        f"{time.perf_counter() - t:.3f} s, {len(compiles.ends)} compiles "
        f"or cache reads ({compiles.seconds:.3f} s)")
    if args.trace:
        harness.annotate_entry_points(engine)
    srv = ServeHTTPServer(engine, max_wait_queue=1 << 20).start_in_thread()
    client.wait_ready()
    # set-up ends at the first due request; the load then runs warm_in_s
    # before the window opens
    start = time.perf_counter() + 0.1
    t0 = start + float(mix.get("warm_in_s", 0.0))
    setup_s = start - T_PROCESS
    log(f"set-up: {setup_s:.3f} s; then {t0 - start:.3f} s of load before "
        f"the window opens (not in setup_s)")
    client.go(srv.base_url, start, t0 + args.seconds)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(tempfile.mkdtemp(prefix="servebench-"),
                                 "trace")
    seen = harness.run_window(engine, t0, args.seconds, trace_dir)
    t_end = t0 + args.seconds
    records = client.result(timeout=args.seconds + 2 * GRACE_S)
    in_window = compiles.between(start, t_end)
    srv.shutdown()
    drained = bool(srv.drain_ok) and engine.pool.pages_in_use == 0
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    # the reduction reads the TPU's device planes, and the peaks are the
    # TPU's
    on_tpu = dev.platform == "tpu"
    run = Run(cell=wl["name"], conf=conf, mix=mix, engine=knobs, t0=t0,
              t_end=t_end,
              requests=traffic.make_requests(mix, args.seed, conf["vocab"],
                                             max(r["idx"] for r in records)
                                             + 1 if records else 0),
              records=records, counters={"start": seen["start"],
                                         "end": seen["end"]},
              samples=seen["samples"], trace_t0=seen.get("trace_t0"),
              trace_t1=seen.get("trace_t1"),
              peaks=cells.peaks(dev.device_kind) if args.trace and on_tpu
              else None,
              spans=engine.tracer.spans_between(seen["trace_t0"], t_end)
              if args.trace else None)
    lat = run.lateness_ms()
    log(f"load generator: {len(run.attempted())} requests sent, lateness "
        f"p50 {percentile(lat, 50)} ms, p99 {percentile(lat, 99)} ms, max "
        f"{max(lat) if lat else None} ms")
    log(f"window: {in_window} compiles from the load's start to the "
        f"window's close; drained clean: {drained}")
    if mix["loop"] == "open":
        first, second = run.ttft_p50_by_half_ms()
        log(f"ttft p50 of the requests due in the window's first half "
            f"{first} ms, in its second half {second} ms")

    metrics = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if args.trace:
        from servebench import tracereduce

        if on_tpu:
            run.trace = tracereduce.reduce(
                tracereduce.load_xplane(trace_dir))
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            breakdown = {
                "device_ops": [list(x) for x in run.trace.device_ops],
                "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
        shutil.rmtree(os.path.dirname(trace_dir), ignore_errors=True)
        for m in layer:
            v = readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            v = setup_s if m["name"] == "setup_s" \
                else run.end_to_end(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state, then the reference on the chip, over
    # weights made anew from the seed
    del srv, engine
    gc.collect()
    log(f"program state freed: {(dev.memory_stats() or {}).get('bytes_in_use')} "
        f"bytes in use")
    t = time.perf_counter()
    sample = check.draw_sample(run, args.seed, int(mix["check_requests"]))
    ref = reference.Reference(conf, reference.make_weights(conf, args.seed))
    got = check.served_gap(ref, sample)
    log(f"reference: {got['requests_checked']} requests, "
        f"{got['tokens_checked']} served tokens in "
        f"{time.perf_counter() - t:.3f} s; widest gap per request "
        f"{got['per_request']}")
    failed = run.failed()
    for r in failed[:5]:
        print(f"failed request {r['idx']}: status {r['status']} terminal "
              f"{r['terminal']} error {r['error']}", file=sys.stderr)
    common = {
        "failed_requests": {"value": len(failed), "limit": 0},
        "compiles_in_window": {"value": in_window, "limit": 0},
        "pages_left_after_drain": {"value": 0 if drained else 1, "limit": 0},
    }
    result = {"attempted": len(run.attempted()), "failed": len(failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    _report(dict(result), _judge(got, conf, mix, common), "check")
    if control:
        # the control serves, at each served position, the token that the
        # reference computed in float8 puts first; the same checks judge it
        t = time.perf_counter()
        ctl = check.control_gap(ref, reference.Reference(conf, ref.w, "fp8"),
                                sample)
        log(f"control (float8): {ctl['tokens_checked']} positions in "
            f"{time.perf_counter() - t:.3f} s")
        _report(dict(result), _judge(
            {"served_gap_max": ctl["control_gap_max"],
             "tokens_checked": ctl["tokens_checked"]}, conf, mix, common),
            "control check")
    return 0


def _judge(got: dict, conf: dict, mix: dict, common: dict) -> dict:
    """Each number compared, beside its limit and whether it holds."""
    checks = {
        "served_gap_max": {"value": got["served_gap_max"],
                           "limit": conf["check"]["served_gap_max"]},
        "tokens_checked": {"value": got["tokens_checked"],
                           "limit": int(mix["check_tokens_min"]),
                           "at_least": True},
    }
    checks.update({k: dict(v) for k, v in common.items()})
    for c in checks.values():
        c["ok"] = (c["value"] >= c["limit"]) if c.pop("at_least", False) \
            else (c["value"] <= c["limit"])
    return checks


def _report(result: dict, checks: dict, label: str) -> None:
    """The checks as the last lines on standard error, then the result
    line, ``correct`` from the checks and ``checks`` last."""
    for name, c in checks.items():
        print(f"{label} {name}: {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    result = dict({"correct": all(c["ok"] for c in checks.values())},
                  **result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
