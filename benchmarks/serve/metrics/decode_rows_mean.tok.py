"""Scheduler: mean active slots (decoding or prefilling rows), from the
engine's ``live_stats()["active_slots"]`` sampled every 50 ms through the
window.  More rows per step carry more tokens per decode chunk."""


def read(run):
    vals = [s["active_slots"] for s in run.samples
            if s.get("active_slots") is not None]
    return sum(vals) / len(vals) if vals else None
