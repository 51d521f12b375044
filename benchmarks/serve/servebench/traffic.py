"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix (``traffic/<mix>.json``) is data only:

* ``loop``: ``"closed"`` (``clients_per_slot`` x the engine's slots
  clients, each sending its next request when the last one ends) or
  ``"open"`` (Poisson arrivals at ``rate`` requests/s);
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length in tokens, clipped;
* ``prefix`` (optional): ``{"tenants", "zipf_s", "tokens"}``: every
  request starts with its tenant's system prompt of ``tokens`` tokens,
  the tenant drawn Zipf(``zipf_s``), and ``prompt`` then sizes the user
  turn that follows it;
* ``block``: requests are made in blocks of this many.  Every block is
  the same sequence of sizes (stratified quantiles of the distributions,
  ordered and paired by permutations fixed by the mix, not the seed),
  and for an open loop of gaps between arrivals and of tenants.  The
  seed draws the token ids (and the tenants' system prompts) only.  So
  every seed sends the same work on the same schedule, which keeps the
  spread between seeds down to the system's own, and any prefix of the
  stream holds all but at most one partial block of the mix;
* ``warm_in_s``: seconds of load before the window opens, so that the
  window sees the mix's steady state (counted in set-up);
* ``temperature``: 0 for greedy (the correctness check needs greedy);
* ``check_requests``: how many finished requests the check compares.

Prompt lengths are token-exact.  Token ids are uniform over the
vocabulary.  No JAX here: the load generator imports this module.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

# a fixed stream for the pairing permutations: part of the mix, the same
# for every seed
_MIX_STREAM = 0x5EB5E


@dataclasses.dataclass
class Request:
    idx: int                 # position in the stream (sent in this order)
    prompt: np.ndarray       # (P,) int32 token ids
    max_new: int
    due: Optional[float]     # open loop: seconds after the window opens
    tenant: Optional[int] = None
    shared: int = 0          # leading tokens shared with the tenant


def _quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified lognormal lengths: the mid-quantiles
    (i + 0.5) / n, clipped and rounded to whole tokens."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _zipf_counts(tenants: int, s: float, n: int) -> np.ndarray:
    """Tenant counts for a block of ``n`` requests: Zipf(s) shares
    rounded by largest remainder, so they sum to ``n``."""
    p = 1.0 / np.arange(1, tenants + 1) ** s
    p = p / p.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts


def block_sizes(mix: dict) -> Dict[str, np.ndarray]:
    """The one block of sizes that every block of every seed is, in the
    mix's fixed order (``prompt``, ``output``, and for an open loop
    ``gap`` seconds and ``tenant``)."""
    m = int(mix["block"])
    fixed = np.random.default_rng(_MIX_STREAM)
    out = {"prompt": fixed.permutation(_quantile_lengths(mix["prompt"], m)),
           "output": fixed.permutation(_quantile_lengths(mix["output"], m))}
    if mix["loop"] == "open":
        rate = float(mix["rate"])
        out["gap"] = np.array([-math.log(1.0 - (i + 0.5) / m) / rate
                               for i in range(m)])
        out["gap"] = fixed.permutation(out["gap"])
    if mix.get("prefix"):
        pre = mix["prefix"]
        counts = _zipf_counts(int(pre["tenants"]), float(pre["zipf_s"]), m)
        out["tenant"] = fixed.permutation(np.repeat(np.arange(len(counts)),
                                                    counts))
    return out


def clients(mix: dict, slots: int) -> int:
    return int(mix["clients_per_slot"]) * int(slots)


def make_requests(mix: dict, seed: int, vocab: int, n: int) -> List[Request]:
    """The first ``n`` requests of the stream for ``seed``."""
    sizes = block_sizes(mix)
    m = int(mix["block"])
    rng = np.random.default_rng([int(seed), 1])
    pre = mix.get("prefix")
    systems = None
    if pre:
        systems = rng.integers(0, vocab, size=(int(pre["tenants"]),
                                               int(pre["tokens"])),
                               dtype=np.int64).astype(np.int32)
    reqs: List[Request] = []
    t = 0.0
    while len(reqs) < n:
        for j in range(m):
            if len(reqs) == n:
                break
            body = rng.integers(0, vocab, size=int(sizes["prompt"][j]),
                                dtype=np.int64).astype(np.int32)
            tenant = None
            shared = 0
            if systems is not None:
                tenant = int(sizes["tenant"][j])
                body = np.concatenate([systems[tenant], body])
                shared = systems.shape[1]
            due = None
            if mix["loop"] == "open":
                t += float(sizes["gap"][j])
                due = t
            reqs.append(Request(len(reqs), body, int(sizes["output"][j]),
                                due, tenant, shared))
    return reqs


def stream_length(mix: dict, slots: int, seconds: float) -> int:
    """How many requests to make so that the load cannot run out: an
    open loop's arrivals over the warm-in and the window (whole blocks),
    a closed loop's ``max_requests_per_client_second`` bound."""
    m = int(mix["block"])
    seconds = seconds + float(mix.get("warm_in_s", 0.0))
    if mix["loop"] == "open":
        n = float(mix["rate"]) * seconds
    else:
        n = float(mix["max_requests_per_client_second"]) \
            * clients(mix, slots) * seconds
    return int(math.ceil(n / m) + 1) * m


def prefill_lengths(mix: dict, chunk: int, page_size: int) -> List[int]:
    """Every prefill-chunk length the mix can make the engine dispatch.

    A prompt of P tokens prefills from its first uncovered token in
    chunks of ``chunk``, the last one P - start mod ``chunk`` long.  The
    start is 0 without a shared prefix.  With one it is any whole number
    of pages up to P, or P - 1 when the whole prompt is shared, as
    prefix pages come and go at run time."""
    shared = int((mix.get("prefix") or {}).get("tokens", 0))
    out = set()
    for body in set(block_sizes(mix)["prompt"].tolist()):
        P = shared + int(body)
        starts = {0}
        if shared:
            starts |= set(range(0, P, page_size)) | {P - 1}
        for s in starts:
            n = P - s
            out.add(min(n, chunk))
            out.add(n % chunk or chunk)
    return sorted(out)
