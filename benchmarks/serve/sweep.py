"""Find the highest arrival rate an open-loop cell sustains.

    python3 benchmarks/serve/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 1,2,3,4

One process, one engine and one server, as ``bench.py`` sets them up;
then one window of ``--seconds`` per rate, each with a fresh load
generator sending the cell's mix at that rate.  For each rate it prints
the requests due and answered, the time to first token (median and 95th
percentile, and the median in each half of the window: a backlog that
grows shows as a later half slower than the earlier), the inter-token
95th percentile and how late the generator ran.  A rate is sustained
when every request due in the window got its first token and the later
half's median is within twice the earlier half's.  The cell's traffic
file then fixes 0.8 x the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from servebench import cells, harness, traffic  # noqa: E402
from servebench.record import Run  # noqa: E402

# served over asked-for output tokens per second that still keeps up
KEEP_UP = 0.9
# the later half's median time to first token over the earlier's
HALVES = 1.25


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    wl = cells.workload(args.workload)
    conf = cells.load_config(wl["config"])
    base = cells.load_traffic(wl["traffic"])
    if base["loop"] != "open":
        raise SystemExit("sweep: the cell's mix is not an open loop")
    cells.add_program_path()
    harness.use_checkout_cache()
    import importlib

    import jax
    from repro.launch.server import ServeHTTPServer

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        raise SystemExit(f"sweep: no {args.platform} device")
    reference = importlib.import_module(conf["reference"])
    engine = harness.build_engine(cells.model_config(conf), conf, args.seed,
                                  reference.make_weights(conf, args.seed))
    knobs = harness.engine_knobs(engine)
    harness.warm_up(engine, base, conf["vocab"], args.seed)
    srv = ServeHTTPServer(engine, max_wait_queue=1 << 20).start_in_thread()
    rows = []
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(base, rate=rate)
            n = traffic.stream_length(mix, conf["engine"]["slots"],
                                      args.seconds)
            client = harness.Client(mix, args.seed, conf["vocab"],
                                    conf["engine"]["slots"], n)
            try:
                client.wait_ready()
                start = time.perf_counter() + 0.1
                t0 = start + float(mix.get("warm_in_s", 0.0))
                client.go(srv.base_url, start, t0 + args.seconds)
                harness.run_window(engine, t0, args.seconds)
                records = client.result(timeout=args.seconds + 200)
            finally:
                client.stop()
            run = Run(cell=wl["name"], conf=conf, mix=mix, engine=knobs,
                      t0=t0, t_end=t0 + args.seconds,
                      requests=traffic.make_requests(mix, args.seed,
                                                     conf["vocab"], n),
                      records=records)
            halves = run.ttft_p50_by_half_ms()
            row = {"rate": rate, "due": len(run.attempted()),
                   "failed": len(run.failed()),
                   "ttft_p50_ms": run.end_to_end("ttft_p50_ms"),
                   "ttft_p95_ms": run.end_to_end("ttft_p95_ms"),
                   "ttft_p50_first_half_ms": halves[0],
                   "ttft_p50_second_half_ms": halves[1],
                   "itl_p95_ms": run.end_to_end("itl_p95_ms"),
                   "tok_s": run.tok_s(),
                   "asked_tok_s": sum(
                       run.requests[r["idx"]].max_new
                       for r in run.attempted() if r["due"] >= t0)
                   / args.seconds,
                   "lateness_max_ms": max(run.lateness_ms(), default=None)}
            a, b = row["ttft_p50_first_half_ms"], row["ttft_p50_second_half_ms"]
            row["sustained"] = bool(
                row["failed"] == 0 and a and b and b <= HALVES * a
                and row["tok_s"] >= KEEP_UP * row["asked_tok_s"])
            rows.append(row)
            print(json.dumps(row), flush=True)
            while engine.live_stats()["active_slots"] or engine.queue_depth:
                time.sleep(0.2)
    finally:
        srv.shutdown()
    best = max([r["rate"] for r in rows if r["sustained"]], default=None)
    print(json.dumps({"highest_sustained_rate": best,
                      "cell_rate": None if best is None else 0.8 * best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
