"""Mixture-of-Experts layers in IR: GShard-style capacity-based token
dispatch (top-k router -> scatter into per-expert buffers -> batched
expert FFN -> weighted combine), plus the DeepSeek-V3 variant (shared
expert + many small routed experts), and the drop-free expert-parallel
layer the serving graphs run (:func:`apply_routed`): a router over
every expert, the part of the result that the experts this chip holds
give, no capacity.

All of it is nGraph IR — TopK / CumSum / ScatterAdd / Gather / DotGeneral
— so the same graph runs on the interpreter and compiles through the JAX
transformer, where the ("experts",) sharding constraints let GSPMD place
expert-parallel all-to-alls.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

from ..core import ops
from ..core.node import Value
from .builder import ModelBuilder, fanin_init, normal_init
from .components import Specs, constrain


def moe_specs(d_model: int, n_experts: int, expert_d_ff: int,
              n_shared: int = 0, shared_d_ff: int = 0) -> Specs:
    specs: Specs = {
        "router": ((d_model, n_experts), ("embed", None)),
        "we_gate": ((n_experts, d_model, expert_d_ff),
                    ("experts", "embed", "expert_ffn")),
        "we_up": ((n_experts, d_model, expert_d_ff),
                  ("experts", "embed", "expert_ffn")),
        "we_down": ((n_experts, expert_d_ff, d_model),
                    ("experts", "expert_ffn", "embed")),
    }
    if n_shared:
        sd = shared_d_ff or expert_d_ff
        specs.update({
            "ws_gate": ((d_model, n_shared * sd), ("embed", "ffn")),
            "ws_up": ((d_model, n_shared * sd), ("embed", "ffn")),
            "ws_down": ((n_shared * sd, d_model), ("ffn", "embed")),
        })
    return specs


def moe_inits(prefix: str, n_shared: int = 0):
    out = {f"{prefix}router": normal_init(0.02)}
    for k in ("we_gate", "we_up", "we_down"):
        out[f"{prefix}{k}"] = fanin_init()
    if n_shared:
        for k in ("ws_gate", "ws_up", "ws_down"):
            out[f"{prefix}{k}"] = fanin_init()
    return out


def capacity_for(n_tokens: int, top_k: int, n_experts: int,
                 factor: float) -> int:
    c = math.ceil(n_tokens * top_k / n_experts * factor)
    return max(8, (c + 7) // 8 * 8)


def apply_moe(
    b: ModelBuilder,
    x: Value,  # (B, S, D) compute dtype
    w: Dict[str, Value],
    *,
    prefix: str = "moe_",
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[Value, Value]:
    """Returns (out (B,S,D), aux_loss scalar f32)."""
    B, S, D = x.shape
    T = B * S
    E, K = n_experts, top_k
    C = capacity_for(T, K, E, capacity_factor)

    xt = ops.reshape(x, (T, D))
    xt = constrain(xt, ("batch", None))

    # -- router (f32 math) -----------------------------------------------
    logits = ops.matmul(ops.convert(xt, "f32"), ops.convert(w[f"{prefix}router"], "f32"))
    probs = ops.softmax(logits, axis=-1)                       # (T, E)
    pk, idx = ops.top_k(probs, K)                               # (T, K)
    denom = ops.reduce_sum(pk, [-1], keepdims=True)
    pk = pk / ops.broadcast_to(denom + ops.constant(1e-9, dtype="f32"), pk.shape)

    # -- load-balancing aux loss (Switch/GShard form) -----------------------
    # fraction of tokens whose top-1 is e  *  mean router prob of e
    top1 = ops.slice_(idx, [0, 0], [T, 1])                      # (T, 1)
    top1_oh = ops.one_hot(ops.reshape(top1, (T,)), E, dtype="f32")  # (T, E)
    frac = ops.reduce_mean(top1_oh, [0])                        # (E,)
    mean_p = ops.reduce_mean(probs, [0])                        # (E,)
    aux = ops.reduce_sum(frac * mean_p) * ops.constant(float(E), dtype="f32")

    # -- dispatch positions: running count per expert in assignment order --
    idx_f = ops.reshape(idx, (T * K,))                          # (TK,)
    a_oh = constrain(ops.one_hot(idx_f, E, dtype="f32"), ("batch", None))
    pos_in_e = ops.cumsum(a_oh, axis=0, exclusive=True)         # (TK, E)
    pos_a = ops.reduce_sum(pos_in_e * a_oh, [-1])               # (TK,)
    pos_a = ops.convert(pos_a, "i32")
    keep = ops.less(pos_a, ops.broadcast_to(ops.constant(C, dtype="i32"),
                                            pos_a.shape))       # (TK,) bool
    pos_c = ops.minimum(pos_a, ops.constant(C - 1, dtype="i32"))
    slot = idx_f * ops.broadcast_to(ops.constant(C, dtype="i32"), idx_f.shape) + pos_c

    # -- scatter tokens into (E*C, D) expert buffers -------------------------
    # assignment a = (token t, choice k) reads token t: that is a
    # broadcast over K, not a gather (a gather by iota defeats GSPMD's
    # sharding propagation and replicates the (TK, D) tensor).
    # NOTE (EXPERIMENTS.md sec. Perf iter 6, refuted): splitting this
    # into K chained (T, D) scatters made peak memory WORSE — each
    # chained scatter's VJP materializes its own (E*C, D) zero buffer.
    gathered = ops.reshape(
        ops.broadcast_to(ops.reshape(xt, (T, 1, D)), (T, K, D)), (T * K, D))
    gathered = constrain(gathered, ("batch", None))
    keep_f = ops.convert(keep, x.dtype)
    upd = gathered * ops.broadcast_to(ops.reshape(keep_f, (T * K, 1)),
                                      gathered.shape)
    upd = constrain(upd, ("batch", None))
    buf = ops.scatter_add(
        ops.broadcast_to(ops.constant(0.0, dtype=x.dtype), (E * C, D)),
        slot, upd)
    buf = constrain(ops.reshape(buf, (E, C, D)), ("experts", None, None))

    # -- expert FFN (batched over E) ---------------------------------------
    g = ops.silu(ops.einsum("ecd,edf->ecf", buf, b.cast(w[f"{prefix}we_gate"])))
    u = ops.einsum("ecd,edf->ecf", buf, b.cast(w[f"{prefix}we_up"]))
    h = constrain(g * u, ("experts", None, "expert_ffn"))
    eout = ops.einsum("ecf,efd->ecd", h, b.cast(w[f"{prefix}we_down"]))
    eout = constrain(eout, ("experts", None, None))

    # -- combine -----------------------------------------------------------------
    back = ops.gather(ops.reshape(eout, (E * C, D)), slot, axis=0)  # (TK, D)
    back = constrain(back, ("batch", None))
    wgt = ops.convert(ops.reshape(pk, (T * K,)), x.dtype) * keep_f   # (TK,)
    back = back * ops.broadcast_to(ops.reshape(wgt, (T * K, 1)), back.shape)
    comb = ops.reduce_sum(ops.reshape(back, (T, K, D)), [1])         # (T, D)
    out = constrain(ops.reshape(comb, (B, S, D)), ("batch", None, None))
    return out, aux


def apply_shared_expert(b: ModelBuilder, x: Value, w: Dict[str, Value],
                        prefix: str = "moe_") -> Value:
    g = ops.silu(ops.matmul(x, b.cast(w[f"{prefix}ws_gate"])))
    u = ops.matmul(x, b.cast(w[f"{prefix}ws_up"]))
    return ops.matmul(g * u, b.cast(w[f"{prefix}ws_down"]))


# -- the drop-free expert-parallel layer (paged serving) -----------------------
def routed_specs(d_model: int, n_experts: int, held: int, expert_d_ff: int,
                 n_shared: int = 0) -> Specs:
    """The router keeps every expert's column (and the score-correction
    bias); only the ``held`` experts' weights are stored."""
    specs = moe_specs(d_model, held, expert_d_ff, n_shared, expert_d_ff)
    specs["router"] = ((d_model, n_experts), ("embed", None))
    specs["router_bias"] = ((n_experts,), (None,))
    return specs


# score-correction bias of the tiny presets' host init: large enough
# beside the sigmoid scores' spread that it changes which experts win
ROUTER_BIAS_SCALE = 0.1


def routed_inits(prefix: str, n_shared: int = 0):
    out = moe_inits(prefix, n_shared)
    out[f"{prefix}router"] = fanin_init()
    out[f"{prefix}router_bias"] = normal_init(ROUTER_BIAS_SCALE)
    return out


def route(logits: Value, bias: Value, *, top_k: int, n_group: int,
          topk_group: int, scaling: float) -> Tuple[Value, Value]:
    """The router on (T, E) f32 logits, as DeepSeek-V3's gate computes it.

    ``s`` = sigmoid of the logits; the choice reads ``s + bias`` (the
    score-correction bias, (E,)): a group's score is the sum of its two
    largest, the ``topk_group`` best of ``n_group`` equal groups of
    consecutive experts stay, and the top ``top_k`` experts among them
    are chosen.  The weights read ``s`` alone, renormalized over the
    chosen and scaled by ``scaling``.  Returns (weights (T, E) f32, zero
    where an expert is not chosen; chosen (T, E) f32 in {0, 1})."""
    T, E = logits.shape
    s = ops.sigmoid(logits)
    c = s + ops.broadcast_to(ops.reshape(bias, (1, E)), (T, E))
    if n_group > 1:
        G = n_group
        top2, _ = ops.top_k(ops.reshape(c, (T, G, E // G)), 2)
        _, gi = ops.top_k(ops.reduce_sum(top2, [-1]), topk_group)  # (T, tg)
        keep = ops.reduce_sum(ops.one_hot(gi, G), [1])              # (T, G)
        keep = ops.reshape(ops.broadcast_in_dim(keep, (T, G, E // G),
                                                (0, 1)), (T, E))
        c = ops.select(ops.greater(keep, ops.constant(0.0, dtype="f32")), c,
                       ops.broadcast_to(ops.constant(-1e30, dtype="f32"),
                                        (T, E)))
    _, idx = ops.top_k(c, top_k)                                    # (T, K)
    chosen = ops.reduce_sum(ops.one_hot(idx, E), [1])               # (T, E)
    sc = s * chosen
    denom = ops.reduce_sum(sc, [-1], keepdims=True)
    w = sc * ops.broadcast_to(ops.constant(scaling, dtype="f32")
                              / denom, (T, E))
    return w, chosen


def apply_routed(
    b: ModelBuilder,
    x: Value,  # (B, S, D) compute dtype, pre-normed
    w: Dict[str, Value],
    *,
    prefix: str = "moe_",
    n_experts: int,
    top_k: int,
    n_group: int = 1,
    topk_group: int = 1,
    scaling: float = 1.0,
    first: int = 0,
    held: Optional[int] = None,
    n_shared: int = 0,
) -> Tuple[Value, Value]:
    """Expert-parallel routed layer without drops: the router (f32,
    :func:`route`) runs over all ``n_experts``; this chip holds experts ``first ..
    first + held - 1`` and adds, for every token, each held chosen
    expert's output at its weight (the weights are over all chosen
    experts, held or not), plus the shared expert.  No capacity applies:
    every held expert computes every row, at weight zero where the row
    did not choose it, which at serving batch sizes costs no more weight
    reads than a gather.  The absent experts' part is not computed; the
    chips that hold them would add it.

    Returns (out (B, S, D), pairs (B*S,) i32: the (token, expert)
    choices that landed on held experts)."""
    B, S, D = x.shape
    T = B * S
    held = n_experts if held is None else held
    xt = ops.reshape(x, (T, D))
    logits = ops.matmul(ops.convert(xt, "f32"),
                        ops.convert(w[f"{prefix}router"], "f32"))
    wt, chosen = route(logits, ops.convert(w[f"{prefix}router_bias"], "f32"),
                       top_k=top_k, n_group=n_group, topk_group=topk_group,
                       scaling=scaling)
    wh = ops.slice_(wt, [0, first], [T, first + held])              # (T, Eh)
    pairs = ops.convert(ops.reduce_sum(
        ops.slice_(chosen, [0, first], [T, first + held]), [-1]), "i32")
    g = ops.silu(ops.einsum("td,edf->etf", xt,
                            b.cast(w[f"{prefix}we_gate"])))
    u = ops.einsum("td,edf->etf", xt, b.cast(w[f"{prefix}we_up"]))
    whe = ops.convert(ops.transpose(wh, (1, 0)), x.dtype)           # (Eh, T)
    h = g * u * ops.broadcast_in_dim(whe, g.shape, (0, 1))
    out = ops.einsum("etf,efd->td", h, b.cast(w[f"{prefix}we_down"]))
    out = ops.reshape(out, (B, S, D))
    if n_shared:
        out = out + apply_shared_expert(b, x, w, prefix)
    return constrain(out, ("batch", None, None)), pairs
