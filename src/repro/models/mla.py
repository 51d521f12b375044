"""Multi-head Latent Attention (DeepSeek-V3) in IR.

Train/prefill expand the low-rank projections to full per-head k/v and
use the Attention compound op (Dk = d_nope + d_rope, Dv = d_v).  Decode
runs *absorbed* attention over the compressed cache: the per-head
up-projections W_uk / W_uv are folded into the query / output, so the
cache holds only (c_kv: kv_lora) + (k_rope: d_rope) per token and the
score computation is MQA-shaped (Hkv = 1) in latent space — the MLA
memory win, expressed with the same Attention op.

The paged serving path (:func:`paged_latent_attention`) keeps the two
latent tensors in page pools of one head and attends them absorbed, in
the decode chunk and the prefill chunk alike.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

from ..core import ops
from ..core.node import Value
from .builder import ModelBuilder, fanin_init, normal_init, ones_init
from .components import (Specs, apply_rope, apply_rope_rows, constrain,
                         merge_heads, paged_gather, paged_write, rope_tables)


def mla_specs(d_model: int, n_heads: int, q_lora: int, kv_lora: int,
              d_nope: int, d_rope: int, d_v: int) -> Specs:
    H = n_heads
    return {
        "wq_a": ((d_model, q_lora), ("embed", None)),
        "q_norm_g": ((q_lora,), (None,)),
        "wq_b": ((q_lora, H * (d_nope + d_rope)), (None, "heads")),
        "wkv_a": ((d_model, kv_lora + d_rope), ("embed", None)),
        "kv_norm_g": ((kv_lora,), (None,)),
        "wk_b": ((kv_lora, H * d_nope), (None, "heads")),
        "wv_b": ((kv_lora, H * d_v), (None, "heads")),
        "wo": ((H * d_v, d_model), ("heads", "embed")),
    }


def mla_inits(prefix: str):
    out = {f"{prefix}{k}": fanin_init()
           for k in ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo")}
    out[f"{prefix}q_norm_g"] = ones_init()
    out[f"{prefix}kv_norm_g"] = ones_init()
    return out


def apply_mla(
    b: ModelBuilder,
    x: Value,  # (B, S, D) compute dtype, pre-normed
    w: Dict[str, Value],
    *,
    prefix: str,
    n_heads: int,
    q_lora: int,
    kv_lora: int,
    d_nope: int,
    d_rope: int,
    d_v: int,
    rope: Tuple[Value, Value],       # tables sized for this S (offset applied)
    cache_ckv: Optional[Value] = None,  # (B, Skv, kv_lora)
    cache_kr: Optional[Value] = None,   # (B, Skv, d_rope)
    pos: Optional[Value] = None,
    scale: Optional[float] = None,
    eps: float = 1e-6,
) -> Tuple[Value, Tuple[Value, ...]]:
    B, S, D = x.shape
    H = n_heads
    dq = d_nope + d_rope
    scale = 1.0 / math.sqrt(dq) if scale is None else scale

    # -- queries ----------------------------------------------------------
    cq = ops.rms_norm(ops.matmul(x, b.cast(w[f"{prefix}wq_a"])),
                      w[f"{prefix}q_norm_g"], eps)
    q = ops.matmul(cq, b.cast(w[f"{prefix}wq_b"]))           # (B,S,H*dq)
    q = ops.transpose(ops.reshape(q, (B, S, H, dq)), (0, 2, 1, 3))
    q_nope = ops.slice_(q, [0, 0, 0, 0], [B, H, S, d_nope])
    q_rope = apply_rope(ops.slice_(q, [0, 0, 0, d_nope], [B, H, S, dq]), *rope)

    # -- compressed kv -----------------------------------------------------
    kv_a = ops.matmul(x, b.cast(w[f"{prefix}wkv_a"]))        # (B,S,l+dr)
    ckv = ops.rms_norm(ops.slice_(kv_a, [0, 0, 0], [B, S, kv_lora]),
                       w[f"{prefix}kv_norm_g"], eps)         # (B,S,l)
    kr = ops.slice_(kv_a, [0, 0, kv_lora], [B, S, kv_lora + d_rope])
    kr = apply_rope(ops.reshape(kr, (B, 1, S, d_rope)), *rope)  # (B,1,S,dr)

    if cache_ckv is None:
        # -- expanded attention (train / prefill) --------------------------
        k_nope = ops.matmul(ckv, b.cast(w[f"{prefix}wk_b"]))  # (B,S,H*dn)
        k_nope = ops.transpose(ops.reshape(k_nope, (B, S, H, d_nope)),
                               (0, 2, 1, 3))
        v = ops.matmul(ckv, b.cast(w[f"{prefix}wv_b"]))       # (B,S,H*dv)
        v = ops.transpose(ops.reshape(v, (B, S, H, d_v)), (0, 2, 1, 3))
        k = ops.concat([k_nope,
                        ops.broadcast_to(kr, (B, H, S, d_rope))], axis=-1)
        q_cat = ops.concat([q_nope, q_rope], axis=-1)
        att = ops.attention(q_cat, k, v, causal=True, scale=scale)
        out = ops.matmul(merge_heads(att), b.cast(w[f"{prefix}wo"]))
        # prefill caches: the *latent* tensors (this is MLA's point)
        extras = (ckv, ops.reshape(kr, (B, S, d_rope)))
        return constrain(out, ("batch", None, None)), extras

    # -- absorbed decode over the latent cache -----------------------------
    Skv = cache_ckv.shape[1]
    zero = ops.constant(0, dtype="i32")
    cache_ckv = ops.dynamic_update_slice(
        cache_ckv, ops.convert(ckv, cache_ckv.dtype), [zero, pos, zero])
    cache_kr = ops.dynamic_update_slice(
        cache_kr, ops.convert(ops.reshape(kr, (B, S, d_rope)), cache_kr.dtype),
        [zero, pos, zero])
    # fold W_uk into q:  q_abs[l] = sum_d q_nope[d] * W_uk[l, h, d]
    wk3 = ops.reshape(b.cast(w[f"{prefix}wk_b"]), (kv_lora, H, d_nope))
    q_abs = ops.einsum("bhsd,lhd->bhsl", q_nope, wk3)        # (B,H,1,l)
    q_full = ops.concat([q_abs, q_rope], axis=-1)            # (B,H,1,l+dr)
    k_full = ops.concat([b.cast(cache_ckv), b.cast(cache_kr)], axis=-1)
    k_full = ops.reshape(k_full, (B, 1, Skv, kv_lora + d_rope))
    v_lat = ops.reshape(b.cast(cache_ckv), (B, 1, Skv, kv_lora))
    att = ops.attention(q_full, k_full, v_lat, causal=True, scale=scale,
                        q_offset=pos)                        # (B,H,1,l)
    # fold W_uv into the output
    wv3 = ops.reshape(b.cast(w[f"{prefix}wv_b"]), (kv_lora, H, d_v))
    o = ops.einsum("bhsl,lhv->bhsv", att, wv3)               # (B,H,1,dv)
    out = ops.matmul(merge_heads(o), b.cast(w[f"{prefix}wo"]))
    return constrain(out, ("batch", None, None)), (cache_ckv, cache_kr)


def softmax_scale(d_nope: int, d_rope: int, yarn_factor: float = 0.0,
                  yarn_mscale: float = 1.0) -> float:
    """1/sqrt(d_nope + d_rope), times YaRN's (0.1 mscale ln factor + 1)^2
    where the rotary frequencies are YaRN's."""
    scale = 1.0 / math.sqrt(d_nope + d_rope)
    if yarn_factor > 1.0:
        scale *= (0.1 * yarn_mscale * math.log(yarn_factor) + 1.0) ** 2
    return scale


def _softmax_last(x: Value) -> Value:
    """Softmax over the last axis, its row max materialized first.  Left
    fused with the subtraction, the row max of a (64, 128, 7168) score
    tensor is rewritten by the TPU compiler into a reduce-window the
    width of the axis at every position: quadratic in the keys, 37 ms
    for ``jax.nn.softmax`` on it on one v5e, where its memory traffic
    takes about 1 ms."""
    m = ops.optimization_barrier(ops.reduce_max(x, [-1], keepdims=True))
    e = ops.exp(x - ops.broadcast_to(m, x.shape))
    return e / ops.broadcast_to(ops.reduce_sum(e, [-1], keepdims=True),
                                x.shape)


def paged_latent_attention(
    b: ModelBuilder, x: Value, w: Dict[str, Value], *,
    prefix: str, n_heads: int, q_lora: int, kv_lora: int, d_nope: int,
    d_rope: int, d_v: int, rope: Tuple[Value, Value], pool_ckv: Value,
    pool_kr: Value, page_tbl: Value, pos: Value, page_size: int,
    layer: Value, scale: float, eps: float, chunk: bool = False,
) -> Tuple[Value, Tuple[Value, Value]]:
    """Latent attention through two page pools of one head.

    pool_ckv (L, P, 1, ps, kv_lora) and pool_kr (L, P, 1, ps, d_rope)
    are carried by the layer scan; layer: () i32.  Decode (``chunk``
    False): x (B, 1, Dm), pos (B,) per-row positions, rope the per-row
    tables.  Prefill chunk: x (1, C, Dm) at positions ``pos .. pos+C-1``
    (pos a scalar), rope the chunk's (C, half) tables; or B chunks, x
    (B, C, Dm) with pos (B,) base positions and (B, C, half) tables, every
    row written before any row gathers.

    Each token writes ``c_kv = RMSNorm(x W_kva[:, :kv_lora])`` and
    ``k_rope = RoPE(x W_kva[:, kv_lora:])`` in place
    (:func:`~.components.paged_write`), then its row's view is gathered
    back (:func:`~.components.paged_gather`) and attended absorbed:
    ``q_abs_h = W_kb_h^T q_nope_h``, f32 scores ``scale (q_abs_h . c_kv_t
    + q_rope_h . k_rope_t)`` masked to ``t <= position``, and ``o_h =
    W_vb_h sum_t p_t c_kv_t``.  Returns (out (B, S, Dm), (pool_ckv,
    pool_kr))."""
    B, S, _ = x.shape
    H, dq = n_heads, d_nope + d_rope
    roped = apply_rope if chunk else apply_rope_rows
    cq = ops.rms_norm(ops.matmul(x, b.cast(w[f"{prefix}wq_a"])),
                      w[f"{prefix}q_norm_g"], eps)
    q = ops.matmul(cq, b.cast(w[f"{prefix}wq_b"]))            # (B,S,H*dq)
    q = ops.transpose(ops.reshape(q, (B, S, H, dq)), (0, 2, 1, 3))
    q_nope = ops.slice_(q, [0, 0, 0, 0], [B, H, S, d_nope])
    q_rope = roped(ops.slice_(q, [0, 0, 0, d_nope], [B, H, S, dq]), *rope)
    kv_a = ops.matmul(x, b.cast(w[f"{prefix}wkv_a"]))         # (B,S,l+dr)
    ckv = ops.rms_norm(ops.slice_(kv_a, [0, 0, 0], [B, S, kv_lora]),
                       w[f"{prefix}kv_norm_g"], eps)
    kr = roped(ops.reshape(ops.slice_(kv_a, [0, 0, kv_lora],
                                      [B, S, kv_lora + d_rope]),
                           (B, 1, S, d_rope)), *rope)          # (B,1,S,dr)

    if chunk and pos.rank == 0:   # the chunk's C rows stand in for the batch
        wpos = ops.broadcast_to(pos, (S,)) + ops.iota((S,), 0, "i32")
        wtbl = ops.broadcast_to(page_tbl, (S, page_tbl.shape[1]))
        new_ckv = ops.reshape(ckv, (S, 1, 1, kv_lora))
        new_kr = ops.transpose(kr, (2, 1, 0, 3))               # (C,1,1,dr)
        qpos = ops.reshape(wpos, (1, S))
    elif chunk:  # B chunks, each at its own base position: B*C rows
        MP = page_tbl.shape[1]
        qpos = ops.broadcast_in_dim(pos, (B, S), (0,)) \
            + ops.iota((B, S), 1, "i32")
        wpos = ops.reshape(qpos, (B * S,))
        wtbl = ops.reshape(ops.broadcast_in_dim(page_tbl, (B, S, MP),
                                                (0, 2)), (B * S, MP))
        new_ckv = ops.reshape(ckv, (B * S, 1, 1, kv_lora))
        new_kr = ops.reshape(ops.transpose(kr, (0, 2, 1, 3)),
                             (B * S, 1, 1, d_rope))
    else:
        wpos, wtbl = pos, page_tbl
        new_ckv = ops.reshape(ckv, (B, 1, 1, kv_lora))
        new_kr = kr
        qpos = ops.reshape(pos, (B, 1))
    pool_ckv = paged_write(pool_ckv, new_ckv, wtbl, wpos, page_size, layer)
    pool_kr = paged_write(pool_kr, new_kr, wtbl, wpos, page_size, layer)
    gc = paged_gather(pool_ckv, page_tbl, layer)               # (B,1,T,l)
    Skv = gc.shape[2]
    gc = ops.reshape(gc, (B, Skv, kv_lora))
    gr = ops.reshape(paged_gather(pool_kr, page_tbl, layer), (B, Skv, d_rope))

    wk3 = ops.reshape(b.cast(w[f"{prefix}wk_b"]), (kv_lora, H, d_nope))
    q_abs = ops.einsum("bhsd,lhd->bhsl", q_nope, wk3)         # (B,H,S,l)
    f32 = "f32"
    scores = ops.add(
        ops.einsum("bhsl,btl->bhst", ops.convert(q_abs, f32),
                   ops.convert(gc, f32)),
        ops.einsum("bhsr,btr->bhst", ops.convert(q_rope, f32),
                   ops.convert(gr, f32)))
    scores = scores * ops.broadcast_to(ops.constant(scale, dtype=f32),
                                       scores.shape)
    kpos = ops.iota((B, S, Skv), 2, "i32")
    mask = ops.less_equal(kpos, ops.broadcast_in_dim(qpos, (B, S, Skv),
                                                     (0, 1)))
    maskb = ops.broadcast_in_dim(mask, scores.shape, (0, 2, 3))
    neg = ops.broadcast_to(ops.constant(-1e30, dtype=f32), scores.shape)
    p = _softmax_last(ops.select(maskb, scores, neg))
    o_lat = ops.einsum("bhst,btl->bhsl", ops.convert(p, gc.dtype), gc,
                       preferred_dtype=f32)                    # (B,H,S,l)
    wv3 = ops.reshape(b.cast(w[f"{prefix}wv_b"]), (kv_lora, H, d_v))
    o = ops.einsum("bhsl,lhv->bhsv", ops.convert(o_lat, x.dtype), wv3)
    out = ops.matmul(merge_heads(o), b.cast(w[f"{prefix}wo"]))
    return constrain(out, ("batch", None, None)), (pool_ckv, pool_kr)
