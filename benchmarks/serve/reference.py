"""The plain reference: a float32 ``jax.numpy`` forward of the dense
configurations, independent of the program.

It imports nothing of the program and takes nothing the program made.
The weights come from :func:`make_weights`: one jitted call on the
default device draws every weight of :func:`param_recipe` from the run's
seed (the embedding and output head normal at scale 0.02, every
projection at 1/sqrt(fan-in), norm gains 1 and biases 0), float32, the
type the configurations store.  The benchmark hands the served engine
the output of one such call before the window and gives the reference
the output of another after it, so neither takes what the other made.

One layer, on ``x`` of shape (T, d_model)::

    a = norm1(x);  q, k, v = a Wq, a Wk, a Wv      split into heads of d_head
    q, k = rope(q), rope(k)                          rotate-half, base rope_base
    x = x + softmax(q k^T / sqrt(d_head), causal) v Wo   kv head h // (H/Hkv)
    x = x + mlp(norm2(x))

with ``norm`` RMSNorm (x rsqrt(mean x^2 + eps) g) or LayerNorm
((x - mean) rsqrt(var + eps) g + b), and ``mlp`` SwiGLU
(silu(a Wg) * (a Wu)) Wd or GELU (erf form) gelu(a Win + b_in) Wout + b_out.
Logits are norm_f(x) Wu, or norm_f(x) E^T when the embedding E is tied.

Everything is float32 at ``Precision.HIGHEST``.  ``precision="fp8"``
rounds both operands of every matrix product to float8 (e4m3) first:
the control, one step below the bfloat16 the configurations compute in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# query rows attended at a time, and the lengths sequences are padded to
# (one compiled layer per length)
_Q_BLOCK = 512
_BUCKET = 512


def param_recipe(conf: dict) -> List[Tuple[str, Tuple[int, ...], str, int]]:
    """(name, shape, init, fan-in) of every weight, in declaration order.
    ``init`` is ``normal`` (scale 0.02), ``fanin`` (1/sqrt(fan-in)),
    ``ones`` or ``zeros``; only the first two draw from the stream."""
    L, D, V = conf["n_layers"], conf["d_model"], conf["vocab"]
    H, Hkv = conf["n_heads"], conf["n_kv_heads"]
    Dh, F = conf.get("d_head") or D // H, conf["d_ff"]
    ln = conf["norm"] == "layernorm"

    def norm(prefix):
        out = [(f"{prefix}g", (D,), "ones", 0)]
        if ln:
            out.append((f"{prefix}b", (D,), "zeros", 0))
        return out

    layer = norm("ln1_") + [
        ("attn_wq", (D, H * Dh), "fanin", D),
        ("attn_wk", (D, Hkv * Dh), "fanin", D),
        ("attn_wv", (D, Hkv * Dh), "fanin", D),
        ("attn_wo", (H * Dh, D), "fanin", H * Dh)]
    if conf.get("qkv_bias"):
        layer += [("attn_bq", (H * Dh,), "zeros", 0),
                  ("attn_bk", (Hkv * Dh,), "zeros", 0),
                  ("attn_bv", (Hkv * Dh,), "zeros", 0)]
    layer += norm("ln2_")
    if conf["mlp"] == "swiglu":
        layer += [("mlp_w_gate", (D, F), "fanin", D),
                  ("mlp_w_up", (D, F), "fanin", D),
                  ("mlp_w_down", (F, D), "fanin", F)]
    else:
        layer += [("mlp_w_in", (D, F), "fanin", D),
                  ("mlp_b_in", (F,), "zeros", 0),
                  ("mlp_w_out", (F, D), "fanin", F),
                  ("mlp_b_out", (D,), "zeros", 0)]
    out = [("embed/table", (V, D), "normal", 0)]
    out += [(f"layers/{n}", (L,) + s, i, f) for n, s, i, f in layer]
    out += [("final_norm/g", (D,), "ones", 0)]
    if ln:
        out.append(("final_norm/b", (D,), "zeros", 0))
    if not conf["tie_embeddings"]:
        out.append(("unembed/w", (D, V), "normal", 0))
    return out


def _seed_words(seed: int) -> np.ndarray:
    """The seed as two 32-bit words: seeds run past 2**32."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.lru_cache(maxsize=None)
def _weight_maker(recipe: Tuple[Tuple[str, Tuple[int, ...], str, int], ...]):
    def make(words):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0),
                                                    words[0]), words[1])
        out = {}
        for i, (name, shape, init, fan) in enumerate(recipe):
            if init in ("ones", "zeros"):
                out[name] = (jnp.ones if init == "ones" else jnp.zeros)(
                    shape, jnp.float32)
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = x * (0.02 if init == "normal"
                             else 1.0 / math.sqrt(fan))
        return out

    return jax.jit(make)


def make_weights(conf: dict, seed: int) -> Dict[str, jax.Array]:
    """The weights for ``seed``, float32, made on the default device in
    one jitted call; the same seed gives the same weights."""
    recipe = tuple(param_recipe(conf))
    return _weight_maker(recipe)(jnp.asarray(_seed_words(seed)))


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=lax.Precision.HIGHEST)


def _norm(x, g, b, kind: str, eps: float):
    if kind == "rms":
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, i, cos, sin, *, conf, precision):
    """Layer ``i`` of the stacked weights ``w`` on x (T, D)."""
    T, D = x.shape
    H, Hkv = conf["n_heads"], conf["n_kv_heads"]
    Dh = conf.get("d_head") or D // H
    rep = H // Hkv
    ln = conf["norm"] == "layernorm"
    kind = "layernorm" if ln else "rms"
    eps = conf["norm_eps"]

    def g(name):
        return lax.dynamic_index_in_dim(w[f"layers/{name}"], i, 0, False)

    a = _norm(x, g("ln1_g"), g("ln1_b") if ln else None, kind, eps)
    q = _mm(a, g("attn_wq"), precision)
    k = _mm(a, g("attn_wk"), precision)
    v = _mm(a, g("attn_wv"), precision)
    if conf.get("qkv_bias"):
        q, k, v = q + g("attn_bq"), k + g("attn_bk"), v + g("attn_bv")
    q = q.reshape(T, Hkv, rep, Dh).transpose(1, 2, 0, 3)   # (Hkv, rep, T, Dh)
    k = k.reshape(T, Hkv, Dh).transpose(1, 0, 2)           # (Hkv, T, Dh)
    v = v.reshape(T, Hkv, Dh).transpose(1, 0, 2)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    kpos = jnp.arange(T)
    blocks = []
    for q0 in range(0, T, _Q_BLOCK):
        qb = q[:, :, q0:q0 + _Q_BLOCK]
        s = jnp.einsum("hrqd,hkd->hrqk", _round(qb, precision),
                       _round(k, precision),
                       precision=lax.Precision.HIGHEST) / math.sqrt(Dh)
        qpos = q0 + jnp.arange(qb.shape[2])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        blocks.append(jnp.einsum("hrqk,hkd->hrqd", _round(p, precision),
                                 _round(v, precision),
                                 precision=lax.Precision.HIGHEST))
    att = jnp.concatenate(blocks, 2).transpose(2, 0, 1, 3).reshape(T, H * Dh)
    x = x + _mm(att, g("attn_wo"), precision)
    a = _norm(x, g("ln2_g"), g("ln2_b") if ln else None, kind, eps)
    if conf["mlp"] == "swiglu":
        hmid = jax.nn.silu(_mm(a, g("mlp_w_gate"), precision)) \
            * _mm(a, g("mlp_w_up"), precision)
        return x + _mm(hmid, g("mlp_w_down"), precision)
    hmid = jax.nn.gelu(_mm(a, g("mlp_w_in"), precision) + g("mlp_b_in"),
                       approximate=False)
    return x + _mm(hmid, g("mlp_w_out"), precision) + g("mlp_b_out")


def _head(x, w, *, conf, precision):
    ln = conf["norm"] == "layernorm"
    a = _norm(x, w["final_norm/g"], w["final_norm/b"] if ln else None,
              "layernorm" if ln else "rms", conf["norm_eps"])
    wu = w["embed/table"].T if conf["tie_embeddings"] else w["unembed/w"]
    return _mm(a, wu, precision)


def rope_tables(T: int, d_head: int, base: float):
    freq = base ** (-np.arange(d_head // 2, dtype=np.float64) * 2.0 / d_head)
    ang = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


class Reference:
    """The reference for one configuration and one set of weights, kept
    on the default device.  ``logits(tokens, rows)`` runs the forward
    over ``tokens`` and returns the logits of the rows asked for."""

    def __init__(self, conf: dict, weights: Dict[str, np.ndarray],
                 precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"precision must be f32 or fp8, got {precision}")
        self.conf = {k: conf[k] for k in (
            "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
            "mlp", "norm", "norm_eps", "rope_base", "tie_embeddings")}
        self.conf["d_head"] = conf.get("d_head") or \
            conf["d_model"] // conf["n_heads"]
        self.conf["qkv_bias"] = bool(conf.get("qkv_bias", False))
        self.w = {k: jnp.asarray(v) for k, v in weights.items()}
        self.precision = precision
        c = self.conf
        self._layer = jax.jit(lambda x, w, i, cos, sin: _layer(
            x, w, i, cos, sin, conf=c, precision=precision))
        self._head = jax.jit(lambda x, w: _head(x, w, conf=c,
                                                precision=precision))

    def logits(self, tokens: Sequence[int], rows: Sequence[int]) -> np.ndarray:
        tokens = np.asarray(tokens, np.int32)
        T = len(tokens)
        Tp = -(-T // _BUCKET) * _BUCKET
        padded = np.zeros((Tp,), np.int32)
        padded[:T] = tokens
        cos, sin = rope_tables(Tp, self.conf["d_head"],
                               self.conf["rope_base"])
        x = jnp.take(self.w["embed/table"], jnp.asarray(padded), axis=0)
        for i in range(self.conf["n_layers"]):
            x = self._layer(x, self.w, np.int32(i), cos, sin)
        rows = np.asarray(rows, np.int64)
        if rows.size and (rows.min() < 0 or rows.max() >= T):
            raise ValueError(f"rows outside 0..{T - 1}")
        out = []
        for r0 in range(0, len(rows), _BUCKET):
            idx = np.zeros((_BUCKET,), np.int32)
            part = rows[r0:r0 + _BUCKET]
            idx[:len(part)] = part
            lg = self._head(jnp.take(x, jnp.asarray(idx), axis=0), self.w)
            out.append(np.asarray(lg)[:len(part)])
        return np.concatenate(out, 0) if out else np.zeros(
            (0, self.conf["vocab"]), np.float32)
