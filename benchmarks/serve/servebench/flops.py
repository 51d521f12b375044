"""Operations and bytes a dense configuration needs, from its sizes.

Counted from the configuration, not from the program: what the
mathematics needs, so that no change to the program can make these
counts stale.  Matrix products are 2 operations per multiply-add; the
embedding lookup is a gather, not a product.  Weights are read once per
step at the compute dtype (bfloat16, 2 bytes); keys and values are kept
at the compute dtype too.
"""
from __future__ import annotations

COMPUTE_BYTES = 2  # bfloat16


def layer_matmul_params(conf: dict) -> int:
    D, H, Hkv, F = (conf["d_model"], conf["n_heads"], conf["n_kv_heads"],
                    conf["d_ff"])
    Dh = conf.get("d_head") or D // H
    attn = D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D
    mlp = (3 if conf["mlp"] == "swiglu" else 2) * D * F
    return conf["n_layers"] * (attn + mlp)


def head_params(conf: dict) -> int:
    return conf["d_model"] * conf["vocab"]


def weight_bytes(conf: dict) -> int:
    """Bytes of every weight one decode step multiplies by."""
    return (layer_matmul_params(conf) + head_params(conf)) * COMPUTE_BYTES


def kv_bytes_per_token(conf: dict) -> int:
    """Key and value rows of one token over all layers."""
    Dh = conf.get("d_head") or conf["d_model"] // conf["n_heads"]
    return 2 * conf["n_layers"] * conf["n_kv_heads"] * Dh * COMPUTE_BYTES


def attn_flops(conf: dict, keys: int) -> float:
    """Scores and the weighted sum for one query over ``keys`` keys,
    all layers: 2 x 2 x heads x d_head x keys x layers."""
    D, H = conf["d_model"], conf["n_heads"]
    return 4.0 * H * (conf.get("d_head") or D // H) * keys * conf["n_layers"]


def decode_token_flops(conf: dict, pos: int) -> float:
    """One decode step's work for one row whose input token sits at
    ``pos`` (it attends ``pos + 1`` keys, itself included)."""
    return 2.0 * (layer_matmul_params(conf) + head_params(conf)) \
        + attn_flops(conf, pos + 1)


def prefill_flops(conf: dict, start: int, end: int) -> float:
    """Prefill of prompt positions ``start .. end - 1`` (earlier ones are
    cached); only the last position needs the output head."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return 2.0 * layer_matmul_params(conf) * n + 2.0 * head_params(conf) \
        + attn_flops(conf, keys)


def decode_token_bytes(conf: dict, pos: int) -> float:
    """KV bytes one decode row needs beyond the weights: the ``pos + 1``
    rows it attends (its own, just written, included) and the row it
    writes."""
    return (pos + 2) * kv_bytes_per_token(conf)
