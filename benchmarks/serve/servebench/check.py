"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
one with the most served tokens, goes through the plain reference
(``reference.py``): one forward over each prompt with its served tokens.
At every served position the number compared is how far the served
token's logit lies below the reference's best, as a share of the row's
largest logit magnitude; the run reads the widest such gap.  The tokens
are greedy, so a sound program serves the reference's best token up to
near-ties that rounding decides.

The control (``control.py``) reads, at the same positions, the gap of
the token that the reference computed in float8 puts first.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np


@dataclasses.dataclass
class Sample:
    idx: int                 # request index in the stream
    prompt: np.ndarray
    served: List[int]

    @property
    def tokens(self) -> np.ndarray:
        """What the reference reads: the prompt, then every served token
        but the last."""
        return np.concatenate([self.prompt,
                               np.asarray(self.served[:-1], np.int32)])

    @property
    def rows(self) -> np.ndarray:
        """The positions whose logits chose the served tokens."""
        P = len(self.prompt)
        return np.arange(P - 1, P - 1 + len(self.served))


def draw_sample(run, seed: int, n: int) -> List[Sample]:
    """``n`` served requests: the one with the most served tokens and
    ``n - 1`` more drawn from the seed.  A request the window's close
    cut counts with the tokens it was served; every one of them came
    from the timed path."""
    done = sorted(run.served(), key=lambda r: r["idx"])
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["tokens"]), -r["idx"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 3])
    pick = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False))]
    return [Sample(r["idx"], run.requests[r["idx"]].prompt,
                   list(r["tokens"])) for r in pick]


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far the logit of ``tokens[i]`` lies below row ``i``'s best, as
    a share of the row's largest logit magnitude."""
    lg = np.asarray(ref_logits, np.float64)
    t = np.asarray(tokens, np.int64)
    return (lg.max(-1) - lg[np.arange(len(t)), t]) / np.abs(lg).max(-1)


def served_gap(reference, sample: List[Sample]) -> dict:
    """The widest gap of a served token, over the sample."""
    widest, n = 0.0, 0
    per = []
    for s in sample:
        g = gaps(reference.logits(s.tokens, s.rows), s.served)
        per.append(float(g.max()))
        widest = max(widest, float(g.max()))
        n += len(g)
    return {"served_gap_max": widest, "tokens_checked": n,
            "requests_checked": len(sample), "per_request": per}


def control_gap(reference, control, sample: List[Sample]) -> dict:
    """The widest gap of the token the control puts first, at the served
    positions."""
    widest, n = 0.0, 0
    for s in sample:
        ref = reference.logits(s.tokens, s.rows)
        ctl = control.logits(s.tokens, s.rows)
        g = gaps(ref, ctl.argmax(-1))
        widest = max(widest, float(g.max()))
        n += len(g)
    return {"control_gap_max": widest, "tokens_checked": n}
