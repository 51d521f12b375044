"""Compiled programs: mean device time of one decode-chunk run (the
``..._paged_chunk<K>`` program, ``chunk_steps`` tokens per row), summed
over its ``XLA Modules`` events in the traced window."""


def read(run):
    p = run.trace.programs["decode_chunk"] if run.trace else None
    return p.mean_ms if p and p.runs else None
