"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(with nothing but JAX) into a list of plain events, keeping only what
the reduction needs: on each TPU device plane the ``XLA Modules`` line
(one event per program run) and the ``XLA Ops`` line (one per
operation), and on the host the benchmark's own annotations
(``servebench.*`` and ``engine.*``).  :func:`reduce` turns the events of
one traced window into a :class:`TraceSummary`.  Both are plain
functions of their input, tested on a small recorded trace.

An event is ``[plane, line, name, start_ns, duration_ns]``, all
timestamps on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, str, str, int, int]

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "servebench.window"
HOST_SPANS = ("engine.step", "engine.submit")
# the program names its two serving programs ``..._paged_chunk<K>`` and
# ``..._paged_prefill<C>`` (models/lm.py); a module event carries the
# name with a ``jit_`` prefix and the run's id
PROGRAMS = {"decode_chunk": "paged_chunk", "prefill_chunk": "paged_prefill"}


def _keep(plane: str, line: str, name: str) -> bool:
    if plane.startswith(DEVICE_PREFIX):
        return line in (MODULES_LINE, OPS_LINE)
    if plane == HOST_PLANE:
        return name == WINDOW_SPAN or name in HOST_SPANS
    return False


def load_xplane(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in pd.planes:
        pname = plane.name
        if not (pname.startswith(DEVICE_PREFIX) or pname == HOST_PLANE):
            continue
        for line in plane.lines:
            lname = line.name
            for ev in line.events:
                if _keep(pname, lname, ev.name):
                    out.append((pname, lname, ev.name, int(ev.start_ns),
                                int(ev.duration_ns)))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(iv: Sequence[Tuple[int, int]], lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class ProgramTime:
    runs: int            # module runs that started inside the window
    device_s: float      # their summed device duration
    mean_ms: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # union of op intervals, averaged
    devices: int                        # over the device planes
    programs: Dict[str, ProgramTime]
    device_ops: List[Tuple[str, float]]  # top ops by summed duration
    idle_gaps: List[Tuple[str, float]]   # idle seconds by host activity

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(events: Sequence[Event]) -> Tuple[int, int]:
    """The traced window: the benchmark's ``servebench.window`` span."""
    spans = [(s, s + d) for p, _, n, s, d in events
             if p == HOST_PLANE and n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


WAITING = "no engine step running (engine thread waiting)"
IN_STEP = "inside engine.step (unattributed)"


def _attribute(gap: Tuple[int, int], host: List[Tuple[str, int, int]],
               starts: List[int], out: Dict[str, float]) -> None:
    """Split an idle gap by what the host was doing in it, into ``out``
    (seconds per activity).  ``host`` holds the engine thread's spans,
    sorted by start (``starts``); they do not overlap.  ``engine.step``
    is the scheduler's step (admission, page tables, dispatch and the
    sync on its result): what inside it kept the device idle only spans
    inside the program could name, so it stays unattributed there."""
    s, e = gap
    covered = 0
    j = max(bisect.bisect_right(starts, s) - 1, 0)
    while j < len(host) and host[j][1] < e:
        name, hs, he = host[j]
        c = min(e, he) - max(s, hs)
        if c > 0:
            label = IN_STEP if name == "engine.step" else name
            out[label] = out.get(label, 0.0) + c / 1e9
            covered += c
        j += 1
    if e - s > covered:
        out[WAITING] = out.get(WAITING, 0.0) + (e - s - covered) / 1e9


def _program(module: str) -> str:
    """``jit_ngraph_<graph>(<run id>)`` -> ``<graph>``."""
    name = module.split("(", 1)[0]
    for prefix in ("jit_ngraph_", "jit_"):
        if name.startswith(prefix):
            return name[len(prefix):]
    return name


def _op_names(ops, modules) -> List[str]:
    """Short names for op events: the program that ran the op and the
    op's HLO id (the event name is the whole HLO instruction)."""
    starts = [s for s, _, _ in modules]
    out = []
    for s, _, name in ops:
        op = name.split(" = ", 1)[0]
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1]:
            op = f"{modules[i][2]}:{op}"
        out.append(op)
    return out


def reduce(events: Sequence[Event], top: int = 10) -> TraceSummary:
    lo, hi = window_of(events)
    window_s = (hi - lo) / 1e9
    planes = sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    host = sorted(((n, s, s + d) for p, _, n, s, d in events
                   if p == HOST_PLANE and n in HOST_SPANS),
                  key=lambda h: h[1])
    starts = [h[1] for h in host]
    busy = 0.0
    per_op: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    programs = {k: [0, 0.0] for k in PROGRAMS}
    for plane in planes:
        ops = [(s, s + d, n) for p, l, n, s, d in events
               if p == plane and l == OPS_LINE]
        modules = sorted((s, s + d, _program(n)) for p, l, n, s, d in events
                         if p == plane and l == MODULES_LINE)
        merged = _union([(s, e) for s, e, _ in ops])
        inside = _clip(merged, lo, hi)
        busy += sum(e - s for s, e in inside) / 1e9
        for (s, e, _), n in zip(ops, _op_names(ops, modules)):
            c = min(e, hi) - max(s, lo)
            if c > 0:
                per_op[n] = per_op.get(n, 0.0) + c / 1e9
        edges = [lo] + [x for iv in inside for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                _attribute((s, e), host, starts, gaps)
        for p, l, n, s, d in events:
            if p != plane or l != MODULES_LINE or not lo <= s < hi:
                continue
            for key, pat in PROGRAMS.items():
                if pat in n:
                    programs[key][0] += 1
                    programs[key][1] += d / 1e9
    n_dev = len(planes)
    progs = {k: ProgramTime(int(r), t, (t / r * 1e3) if r else 0.0)
             for k, (r, t) in programs.items()}
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_sorted = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=window_s, busy_s=busy / n_dev, devices=n_dev,
        programs=progs,
        device_ops=[(n, t / n_dev) for n, t in ops_sorted],
        idle_gaps=[(n, t / n_dev) for n, t in gaps_sorted])
