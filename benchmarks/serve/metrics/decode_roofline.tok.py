"""Kernels: the decode chunk's share of its roofline, in %.

Over the traced window: the least time the chip could take for the
decode steps it ran, over the device time the decode-chunk runs took.
The least time is the larger of operations / peak bf16 FLOP/s and bytes
/ peak HBM bytes/s (``peaks.json``).  Counted from the configuration by
its count module (``cells.counts``), not from the program; for a dense
configuration (``servebench.flops``): each step reads every weight it
multiplies by once at bf16; each decoded token reads the live KV rows
it attends and writes its own.  The decoded tokens are those that
reached their clients inside the traced window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import cells  # noqa: E402


def read(run):
    if not run.trace or run.trace_t0 is None:
        return None
    p = run.trace.programs["decode_chunk"]
    if not p.runs or p.device_s <= 0:
        return None
    conf, steps = run.conf, p.runs * run.engine["chunk_steps"]
    counts = cells.counts(conf)
    ops = 0.0
    moved = float(steps) * counts.weight_bytes(conf)
    for req, i in run.decode_tokens(run.trace_t0, run.trace_t1):
        pos = len(req.prompt) + i - 1
        ops += counts.decode_token_flops(conf, pos)
        moved += counts.decode_token_bytes(conf, pos)
    ideal = max(ops / run.peaks["bf16_flops"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / p.device_s
