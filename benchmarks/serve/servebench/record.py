"""What one run recorded, and the end-to-end metrics taken from it.

A :class:`Run` is what the per-layer readers (``metrics/<name>.py``) get:
the cell's configuration and mix, the engine's knobs, the window, the
requests, the client's per-request records, the engine's gauges sampled
through the window, and, of a traced run, the reduced device trace and
the program's host spans.  All times are ``time.perf_counter()``
instants; the client and the program's spans share that clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from . import cells


def percentile(values, q: float) -> Optional[float]:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Run:
    cell: str
    conf: dict                  # configs/<config>.json
    mix: dict                   # traffic/<mix>.json
    engine: dict                # slots, max_len, page_size, chunk_steps, ...
    t0: float                   # window opens (first due request)
    t_end: float                # window closes
    requests: list              # traffic.Request, by idx
    records: List[dict]         # client records (client.py)
    counters: Dict[str, dict] = dataclasses.field(default_factory=dict)
    samples: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[object] = None      # tracereduce.TraceSummary
    trace_t0: Optional[float] = None    # traced window, perf_counter
    trace_t1: Optional[float] = None
    peaks: Optional[dict] = None
    # the program tracer's spans from the traced window's start to the
    # window's close (``Tracer.spans_between``); None untraced, or where
    # the tracer's ring dropped one of them
    spans: Optional[list] = None

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def inside(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end

    # -- requests ----------------------------------------------------------
    def attempted(self) -> List[dict]:
        """Requests sent before the window closed, the warm-in's
        included (an open loop's: due before it closed)."""
        if self.mix["loop"] == "open":
            return [r for r in self.records if r["due"] < self.t_end]
        return [r for r in self.records if r["sent"] is not None]

    def sent_in_window(self) -> List[dict]:
        return [r for r in self.attempted() if r["sent"] is not None
                and self.inside(r["sent"])]

    def failed(self) -> List[dict]:
        """Refused, errored or never answered.  A request the window's
        close cut while it streamed is not a failure; one due in an open
        loop's window that got no first token is."""
        out = []
        for r in self.attempted():
            if r["cut"]:
                if self.mix["loop"] == "open" and not r["times"]:
                    out.append(r)
                continue
            if r["status"] != 200 or r["error"] is not None \
                    or r["terminal"] != "completed":
                out.append(r)
        return out

    def served(self) -> List[dict]:
        """Requests that streamed tokens and did not fail: completed, or
        cut by the window's close."""
        failed = {id(r) for r in self.failed()}
        return [r for r in self.attempted() if r["times"]
                and id(r) not in failed and r["error"] is None
                and (r["cut"] or r["terminal"] == "completed")]

    # -- end-to-end metrics ------------------------------------------------
    def tok_s(self) -> float:
        n = sum(1 for r in self.records for t in r["times"] if self.inside(t))
        return n / self.seconds

    def gaps_ms(self) -> List[float]:
        out = []
        for r in self.records:
            ts = r["times"]
            for a, b in zip(ts, ts[1:]):
                if self.inside(a) and self.inside(b):
                    out.append((b - a) * 1e3)
        return out

    def ttft_ms(self) -> List[float]:
        """Time to first token from when each request due in the window
        was due."""
        return [(r["times"][0] - r["due"]) * 1e3 for r in self.attempted()
                if r["times"] and r["due"] >= self.t0]

    def ttft_p50_by_half_ms(self) -> List[Optional[float]]:
        """Median time to first token of the requests due in each half of
        the window: a backlog that grows shows as a slower later half."""
        mid = (self.t0 + self.t_end) / 2.0
        return [percentile([(r["times"][0] - r["due"]) * 1e3
                            for r in self.attempted()
                            if r["times"] and r["due"] >= self.t0
                            and (r["due"] < mid) == first], 50)
                for first in (True, False)]

    def lateness_ms(self) -> List[float]:
        return [(r["sent"] - r["due"]) * 1e3 for r in self.attempted()]

    def end_to_end(self, name: str) -> Optional[float]:
        if name == "tok_s":
            return self.tok_s()
        if name == "itl_p95_ms":
            return percentile(self.gaps_ms(), 95)
        if name == "ttft_p50_ms":
            return percentile(self.ttft_ms(), 50)
        if name == "ttft_p95_ms":
            return percentile(self.ttft_ms(), 95)
        raise KeyError(f"no end-to-end metric {name!r}")

    # -- work done, for the per-layer readers --------------------------------
    def decode_tokens(self, lo: float, hi: float):
        """(request, output index) of every decoded token (not the first,
        which prefill makes) that reached its client in [lo, hi]."""
        for r in self.records:
            for i, t in enumerate(r["times"]):
                if i and lo <= t <= hi:
                    yield self.requests[r["idx"]], i

    def needed_flops(self) -> float:
        """Operations the window's work needs: prefills whose first token
        arrived in the window (their uncached positions) and decoded
        tokens that arrived in it, as the configuration's count module
        counts them."""
        conf, counts = self.conf, cells.counts(self.conf)
        total = 0.0
        for r in self.records:
            if r["times"] and self.inside(r["times"][0]):
                req = self.requests[r["idx"]]
                total += counts.prefill_flops(conf, 0, len(req.prompt))
        for req, i in self.decode_tokens(self.t0, self.t_end):
            total += counts.decode_token_flops(conf, len(req.prompt) + i - 1)
        return total
