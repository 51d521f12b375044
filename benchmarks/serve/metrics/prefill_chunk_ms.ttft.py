"""Compiled programs: mean device time of one prefill-chunk run (the
``..._paged_prefill<C>`` programs, at most ``prefill_chunk`` prompt
tokens of one request), from its ``XLA Modules`` events in the traced
window."""


def read(run):
    p = run.trace.programs["prefill_chunk"] if run.trace else None
    return p.mean_ms if p and p.runs else None
