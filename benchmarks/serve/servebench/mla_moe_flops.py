"""Operations and bytes a DeepSeek-V3 configuration (latent attention,
routed experts) needs, from its sizes.

Counted from the configuration, not from the program, as ``flops.py``
counts a dense one: matrix products are 2 operations per multiply-add;
the embedding lookup is a gather, not a product.  What is needed:

* attention absorbed at each position: the query and latent
  projections, ``q_abs_h = W_kb_h^T q_nope_h`` and ``o_h = W_vb_h o_lat``
  per head, the output projection, and for each key it attends the
  scores against its latent and rotary row (``kv_lora + d_rope`` per
  head) and the weighted sum of its latent row (``kv_lora`` per head);
* the dense layers' MLP; in each MoE layer the router over every expert,
  the shared expert, and the held experts at the pairs routed to them,
  ``top_k x experts_held / n_experts`` a token on average;
* the output head where a token is chosen.

Bytes: every weight one decode step multiplies by, at the compute dtype
(bfloat16, 2 bytes), all held experts among them, and per decoded row
the latent rows it attends and the one it writes.
"""
from __future__ import annotations

COMPUTE_BYTES = 2  # bfloat16


def _attn_params(conf: dict) -> int:
    """Per layer and token, absorbed."""
    D, H = conf["d_model"], conf["n_heads"]
    ql, l, dn, dr, dv = (conf["q_lora"], conf["kv_lora"], conf["d_nope"],
                         conf["d_rope"], conf["d_v"])
    return (D * ql + ql * H * (dn + dr) + D * (l + dr)
            + H * l * dn + H * l * dv + H * dv * D)


def _layer_counts(conf: dict):
    """(dense layers, MoE layers)."""
    nd = conf["first_dense"]
    return nd, conf["n_layers"] - nd


def _moe_fixed_params(conf: dict) -> int:
    """Per MoE layer and token: the router and the shared expert."""
    D = conf["d_model"]
    return D * conf["n_experts"] \
        + 3 * D * conf["n_shared_experts"] * conf["expert_d_ff"]


def _expert_params(conf: dict) -> int:
    return 3 * conf["d_model"] * conf["expert_d_ff"]


def pairs_per_token(conf: dict) -> float:
    """Routed pairs a token sends to the held experts in one MoE layer,
    on average."""
    return conf["top_k"] * conf["experts_held"] / conf["n_experts"]


def token_matmul_params(conf: dict) -> float:
    """Multiply-adds of one token's weight products over all layers,
    the head excluded."""
    nd, nm = _layer_counts(conf)
    dense = _attn_params(conf) + 3 * conf["d_model"] * conf["d_ff"]
    moe = _attn_params(conf) + _moe_fixed_params(conf) \
        + pairs_per_token(conf) * _expert_params(conf)
    return nd * dense + nm * moe


def head_params(conf: dict) -> int:
    return conf["d_model"] * conf["vocab"]


def attn_flops(conf: dict, keys: int) -> float:
    """Scores and the weighted sum for one query over ``keys`` keys, all
    layers: 2 x heads x (2 kv_lora + d_rope) x keys x layers."""
    return 2.0 * conf["n_heads"] * (2 * conf["kv_lora"] + conf["d_rope"]) \
        * keys * conf["n_layers"]


def weight_bytes(conf: dict) -> int:
    """Bytes of every weight one decode step multiplies by: every held
    expert, whichever rows chose it."""
    nd, nm = _layer_counts(conf)
    params = nd * (_attn_params(conf) + 3 * conf["d_model"] * conf["d_ff"]) \
        + nm * (_attn_params(conf) + _moe_fixed_params(conf)
                + conf["experts_held"] * _expert_params(conf)) \
        + head_params(conf)
    return params * COMPUTE_BYTES


def latent_bytes_per_token(conf: dict) -> int:
    """The latent and rotary rows of one token over all layers."""
    return (conf["kv_lora"] + conf["d_rope"]) * conf["n_layers"] \
        * COMPUTE_BYTES


def decode_token_flops(conf: dict, pos: int) -> float:
    """One decode step's work for one row whose input token sits at
    ``pos`` (it attends ``pos + 1`` keys, itself included)."""
    return 2.0 * (token_matmul_params(conf) + head_params(conf)) \
        + attn_flops(conf, pos + 1)


def prefill_flops(conf: dict, start: int, end: int) -> float:
    """Prefill of prompt positions ``start .. end - 1`` (earlier ones are
    cached); only the last position needs the output head."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return 2.0 * token_matmul_params(conf) * n + 2.0 * head_params(conf) \
        + attn_flops(conf, keys)


def decode_token_bytes(conf: dict, pos: int) -> float:
    """Latent bytes one decode row needs beyond the weights: the
    ``pos + 1`` rows it attends (its own, just written, included) and the
    row it writes."""
    return (pos + 2) * latent_bytes_per_token(conf)
