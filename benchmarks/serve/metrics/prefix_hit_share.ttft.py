"""KV pool: share of the prompt tokens sent in the window that attached
cached prefix pages instead of being prefilled: growth of the pool's
``shared_attaches`` counter over the window, times the page size, over
the prompt tokens of the requests sent in the window, in %."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    if not a or not b or a.get("shared_attaches") is None:
        return None
    tokens = sum(len(run.requests[r["idx"]].prompt) for r in run.sent_in_window())
    if not tokens:
        return None
    hit = (b["shared_attaches"] - a["shared_attaches"]) \
        * run.engine["page_size"]
    return 100.0 * hit / tokens
