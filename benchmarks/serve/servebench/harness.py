"""Set-up, warm-up and the measured window, shared by ``bench.py`` and
``sweep.py``.

The benchmark process holds the chip: it builds the paged ``ServeEngine``
(``EngineConfig(mode="paged", tp=1)``, scheduling knobs at the engine's
defaults) over the weights the benchmark made, warms up every program the cell's traffic can dispatch, and
puts a ``ServeHTTPServer`` in front of it.  The load generator is a child
process (``client.py``) that never imports JAX.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import cells, traffic

CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")
CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "client.py")
# live_stats() sampling period through a traced window
SAMPLE_S = 0.05


def process_start() -> float:
    """This process's start as a ``time.perf_counter()`` instant (from
    ``/proc``; the script's own start where that is missing)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        return now - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return now


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that two checkouts share
    nothing.  Call before JAX is imported; every program is cached, the
    quick ones too, so that a second run compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


class CompileCounter:
    """Counts XLA compilations (or persistent-cache reads, which JAX
    reports alike) by the instant they ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.ends: List[float] = []
        self.seconds = 0.0

        def on_duration(event, secs, **_):
            if event == self.EVENT:
                self.ends.append(time.perf_counter())
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.ends if lo <= t <= hi)


def build_engine(cfg, conf: dict, seed: int, weights: Dict[str, object]):
    """The paged engine, serving ``weights`` (the benchmark's, made on
    the device from the seed) in place of its own seeded host init.  A
    weight the engine declares that ``weights`` lacks, or holds at
    another shape or type, is an error."""
    from repro.launch.engine import EngineConfig, ServeEngine
    from repro.models.builder import ModelBuilder

    given = dict(weights)

    def serve_given(builder, seed=0):
        specs = builder.params
        if set(specs) != set(given):
            raise cells.CellError(
                f"{conf['name']}: the engine declares weights "
                f"{sorted(set(specs) - set(given))} the benchmark does not "
                f"make, and not {sorted(set(given) - set(specs))}")
        for name, s in specs.items():
            w = given[name]
            if tuple(w.shape) != tuple(s.shape) \
                    or np.dtype(w.dtype) != np.dtype(s.dtype):
                raise cells.CellError(
                    f"{conf['name']}: {name} is {s.shape} {s.dtype} in the "
                    f"engine, {w.shape} {w.dtype} as made")
        return {name: given[name] for name in specs}

    e = conf["engine"]
    own = ModelBuilder.init_params
    ModelBuilder.init_params = serve_given
    try:
        return ServeEngine(cfg, EngineConfig(
            mode="paged", slots=e["slots"], max_len=e["max_len"],
            pages=e.get("pages"), seed=int(seed), tp=1))
    finally:
        ModelBuilder.init_params = own
        given.clear()


def engine_knobs(engine) -> dict:
    """The scheduling knobs in force (the engine's defaults)."""
    return {"slots": engine.slots, "max_len": engine.max_len,
            "pages": engine.pool.n_pages - 1,
            "page_size": engine.page_size,
            "chunk_steps": engine.chunk_steps,
            "prefill_chunk": engine.prefill_chunk,
            "prefix_sharing": engine.prefix_sharing}


def warm_up(engine, mix: dict, vocab: int, seed: int) -> List[int]:
    """Dispatch every prefill-chunk length the mix can make the engine
    run, and the decode chunk, so that the window compiles nothing.
    Returns the prefill lengths warmed."""
    lengths = traffic.prefill_lengths(mix, engine.prefill_chunk,
                                      engine.page_size)
    rng = np.random.default_rng([int(seed), 2])
    rids = [engine.submit(rng.integers(0, vocab, size=(n,)), 1)
            for n in lengths]
    rids.append(engine.submit(rng.integers(0, vocab, size=(1,)),
                              engine.chunk_steps + 1))
    rep = engine.run()
    bad = {r: rep.statuses[r] for r in rids if rep.statuses[r] != "completed"}
    if bad or engine.pool.pages_in_use:
        raise RuntimeError(f"warm-up failed: {bad} {rep.errors}, "
                           f"{engine.pool.pages_in_use} pages held")
    return lengths


class Client:
    """The load-generator child process."""

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int,
                 n: int):
        args = {"mix": mix, "seed": int(seed), "vocab": int(vocab),
                "slots": int(slots), "n": int(n)}
        self.proc = subprocess.Popen(
            [sys.executable, CLIENT, json.dumps(args)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def wait_ready(self, timeout: float = 300.0) -> None:
        line = self.proc.stdout.readline()
        if line.strip() != "ready":
            self.stop()
            raise RuntimeError(f"load generator did not start: {line!r}")

    def go(self, url: str, start: float, end: float) -> None:
        """Start the load at ``start``; the window closes at ``end``."""
        self.proc.stdin.write(json.dumps({"url": url, "start": start,
                                          "end": end}) + "\n")
        self.proc.stdin.flush()

    def result(self, timeout: float) -> List[dict]:
        out, _ = self.proc.communicate(timeout=timeout)
        if self.proc.returncode != 0:
            raise RuntimeError(f"load generator exited "
                               f"{self.proc.returncode}")
        return json.loads(out.strip().splitlines()[-1])["records"]

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def annotate_entry_points(engine) -> None:
    """Host spans around the engine's public entry points, for a traced
    run: the server's engine thread calls ``engine.step`` and
    ``engine.submit`` through the instance, so these wrappers see every
    call."""
    from jax.profiler import TraceAnnotation

    step, submit = engine.step, engine.submit

    def traced_step():
        with TraceAnnotation("engine.step"):
            return step()

    def traced_submit(*a, **kw):
        with TraceAnnotation("engine.submit"):
            return submit(*a, **kw)

    engine.step = traced_step
    engine.submit = traced_submit


def _gauges(engine) -> dict:
    s = engine.live_stats()
    return {k: s.get(k) for k in ("active_slots", "queue_depth",
                                  "pages_in_use", "shared_attaches",
                                  "cow_copies")}


def run_window(engine, t0: float, seconds: float,
               trace_dir: Optional[str] = None) -> Dict[str, object]:
    """Hold the main thread through the window: read the engine's
    counters as it opens and closes and, in a traced run, sample its
    gauges and trace the middle of the window.  Returns what it saw."""
    from jax.profiler import TraceAnnotation

    out: Dict[str, object] = {"samples": []}
    t_end = t0 + seconds
    stop = threading.Event()
    sampler = None
    if trace_dir is not None:
        def sample():
            while not stop.is_set():
                now = time.perf_counter()
                if t0 <= now <= t_end:
                    out["samples"].append(dict(_gauges(engine), t=now))
                stop.wait(SAMPLE_S)

        sampler = threading.Thread(target=sample, name="gauges", daemon=True)
        sampler.start()
    _sleep_until(t0)
    out["start"] = _gauges(engine)
    if trace_dir is not None:
        import jax

        span = min(seconds / 2.0, 10.0)
        _sleep_until(t0 + (seconds - span) / 2.0)
        jax.profiler.start_trace(trace_dir)
        with TraceAnnotation("servebench.window"):
            out["trace_t0"] = time.perf_counter()
            _sleep_until(out["trace_t0"] + span)
            out["trace_t1"] = time.perf_counter()
        jax.profiler.stop_trace()
    _sleep_until(t_end)
    out["end"] = _gauges(engine)
    stop.set()
    if sampler is not None:
        sampler.join(timeout=10)
    return out
