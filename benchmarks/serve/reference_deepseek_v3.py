"""The plain reference for DeepSeek-V3 configurations: a float32
``jax.numpy`` forward, independent of the program.

It imports nothing of the program and takes nothing the program made.
:func:`make_weights` draws every weight of :func:`param_recipe` from the
run's seed in one jitted call on the default device, in bfloat16 (the
configuration's storage type), under the names and shapes the paged
engine declares: ``embed/table``, the dense layers' stacked weights
``dense/*``, the MoE layers' ``moe/*``, ``final_norm/g`` and
``unembed/w``.  The embedding and output head are normal at scale 0.02,
every projection and the router normal at 1/sqrt(fan-in), the
score-correction bias normal at the configuration's
``router_bias_scale``, norm gains 1.

One layer, on ``x`` of shape (T, d_model), every product in float32 at
``Precision.HIGHEST`` and the layer's weights upcast to float32 one layer
at a time::

    a = rmsnorm(x) g1
    cq = rmsnorm(a Wq_a) gq;   q = cq Wq_b -> heads (q_nope | q_rope)
    kv = a Wkv_a;  c = rmsnorm(kv[:kv_lora]) gkv;  k_rope = rope(kv[kv_lora:])
    k_h = [c Wk_b_h | k_rope],  v_h = c Wv_b_h     (expanded, not absorbed)
    x = x + concat_h softmax(scale q_h k_h^T, causal) v_h  Wo
    a = rmsnorm(x) g2
    x = x + mlp(a)      dense layers: (silu(a Wg) * (a Wu)) Wd
    x = x + moe(a)      MoE layers, below

``rope`` rotates the two halves of the d_rope columns (the program's
layout) by YaRN's frequencies: theta_i = base^(-2i/d), pairs that turn
more than beta_fast times over the original context keep theta_i, those
that turn fewer than beta_slow times take theta_i / factor, and a linear
ramp over i blends the two; ``scale`` is 1/sqrt(d_nope + d_rope) times
(0.1 mscale ln factor + 1)^2.

The MoE layer: ``s = sigmoid(a Wr)`` over all routed experts; the choice
reads ``s + bias``: each of ``n_group`` groups of consecutive experts
scores the sum of its two best, the ``topk_group`` best groups stay, and
the ``top_k`` best experts among them are chosen; weights ``w_i =
routed_scaling s_i / sum of the chosen s``.  Only the experts this chip
holds (the first ``experts_held``) add their part, ``sum_{i chosen and
held} w_i (silu(a Wg_i) * (a Wu_i)) Wd_i``, beside the shared expert;
the absent experts' part is left out, as the program leaves it out.

``precision="fp8"`` rounds both operands of every product to float8
(e4m3) first: the control, one step below the bfloat16 the configuration
computes in.
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
_HI = lax.Precision.HIGHEST


def _attn_recipe(conf: dict) -> List[Tuple[str, Tuple[int, ...], str, int]]:
    D, H = conf["d_model"], conf["n_heads"]
    ql, l, dn, dr, dv = (conf["q_lora"], conf["kv_lora"], conf["d_nope"],
                         conf["d_rope"], conf["d_v"])
    return [("ln1_g", (D,), "ones", 0),
            ("attn_wq_a", (D, ql), "fanin", D),
            ("attn_q_norm_g", (ql,), "ones", 0),
            ("attn_wq_b", (ql, H * (dn + dr)), "fanin", ql),
            ("attn_wkv_a", (D, l + dr), "fanin", D),
            ("attn_kv_norm_g", (l,), "ones", 0),
            ("attn_wk_b", (l, H * dn), "fanin", l),
            ("attn_wv_b", (l, H * dv), "fanin", l),
            ("attn_wo", (H * dv, D), "fanin", H * dv),
            ("ln2_g", (D,), "ones", 0)]


def param_recipe(conf: dict) -> List[Tuple[str, Tuple[int, ...], str, int]]:
    """(name, shape, init, fan-in) of every weight.  ``init`` is
    ``normal`` (scale 0.02), ``fanin`` (1/sqrt(fan-in)), ``bias``
    (``router_bias_scale``) or ``ones``."""
    D, V, F, Fe = (conf["d_model"], conf["vocab"], conf["d_ff"],
                   conf["expert_d_ff"])
    E, Eh, Fs = (conf["n_experts"], conf["experts_held"],
                 conf["n_shared_experts"] * conf["expert_d_ff"])
    nd, nm = conf["first_dense"], conf["n_layers"] - conf["first_dense"]
    dense = _attn_recipe(conf) + [("mlp_w_gate", (D, F), "fanin", D),
                                  ("mlp_w_up", (D, F), "fanin", D),
                                  ("mlp_w_down", (F, D), "fanin", F)]
    moe = _attn_recipe(conf) + [
        ("moe_router", (D, E), "fanin", D),
        ("moe_router_bias", (E,), "bias", 0),
        ("moe_we_gate", (Eh, D, Fe), "fanin", D),
        ("moe_we_up", (Eh, D, Fe), "fanin", D),
        ("moe_we_down", (Eh, Fe, D), "fanin", Fe)]
    if Fs:
        moe += [("moe_ws_gate", (D, Fs), "fanin", D),
                ("moe_ws_up", (D, Fs), "fanin", D),
                ("moe_ws_down", (Fs, D), "fanin", Fs)]
    out = [("embed/table", (V, D), "normal", 0)]
    out += [(f"dense/{n}", (nd,) + s, i, f) for n, s, i, f in dense if nd]
    out += [(f"moe/{n}", (nm,) + s, i, f) for n, s, i, f in moe if nm]
    out += [("final_norm/g", (D,), "ones", 0),
            ("unembed/w", (D, V), "normal", 0)]
    return out


def _seed_words(seed: int) -> np.ndarray:
    """The seed as two 32-bit words: seeds run past 2**32."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.lru_cache(maxsize=None)
def _weight_maker(recipe, bias_scale: float):
    def make(words):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0),
                                                    words[0]), words[1])
        out = {}
        for i, (name, shape, init, fan) in enumerate(recipe):
            if init == "ones":
                out[name] = jnp.ones(shape, jnp.bfloat16)
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            scale = {"normal": 0.02, "bias": bias_scale}.get(
                init, 1.0 / math.sqrt(max(fan, 1)))
            out[name] = (x * scale).astype(jnp.bfloat16)
        return out

    return jax.jit(make)


def make_weights(conf: dict, seed: int) -> Dict[str, jax.Array]:
    """The weights for ``seed``, bfloat16, made on the default device in
    one jitted call; the same seed gives the same weights."""
    recipe = tuple(param_recipe(conf))
    return _weight_maker(recipe, float(conf["router_bias_scale"]))(
        jnp.asarray(_seed_words(seed)))


def yarn_inv_freq(conf: dict) -> np.ndarray:
    """The (d_rope/2,) rotary frequencies, YaRN's where ``yarn_factor``
    is set."""
    d, base = conf["d_rope"], conf["rope_base"]
    theta = base ** (-np.arange(d // 2, dtype=np.float64) * 2.0 / d)
    factor = conf.get("yarn_factor", 0.0)
    if not factor or factor <= 1.0:
        return theta
    orig = conf["yarn_original_len"]

    def pair(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(conf["yarn_beta_fast"])), 0)
    high = min(math.ceil(pair(conf["yarn_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return theta / factor * ramp + theta * (1 - ramp)


def softmax_scale(conf: dict) -> float:
    scale = 1.0 / math.sqrt(conf["d_nope"] + conf["d_rope"])
    factor = conf.get("yarn_factor", 0.0)
    if factor and factor > 1.0:
        scale *= (0.1 * conf["yarn_mscale"] * math.log(factor) + 1.0) ** 2
    return scale


def rope_tables(T: int, conf: dict):
    ang = np.arange(T, dtype=np.float64)[:, None] \
        * yarn_inv_freq(conf)[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=_HI)


def _rms(x, g, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, g, cos, sin, *, conf, precision):
    """x (T, D) -> x + the latent attention of layer weights ``g``."""
    T = x.shape[0]
    H, l, dn, dr, dv = (conf["n_heads"], conf["kv_lora"], conf["d_nope"],
                        conf["d_rope"], conf["d_v"])
    eps = conf["norm_eps"]
    a = _rms(x, g("ln1_g"), eps)
    cq = _rms(_mm(a, g("attn_wq_a"), precision), g("attn_q_norm_g"), eps)
    q = _mm(cq, g("attn_wq_b"), precision).reshape(T, H, dn + dr)
    q = q.transpose(1, 0, 2)                                   # (H, T, dq)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], -1)
    kv = _mm(a, g("attn_wkv_a"), precision)
    c = _rms(kv[:, :l], g("attn_kv_norm_g"), eps)
    k_rope = _rope(kv[:, l:], cos, sin)                         # (T, dr)
    k_nope = _mm(c, g("attn_wk_b"), precision).reshape(T, H, dn)
    k = jnp.concatenate([k_nope.transpose(1, 0, 2),
                         jnp.broadcast_to(k_rope, (H, T, dr))], -1)
    v = _mm(c, g("attn_wv_b"), precision).reshape(T, H, dv).transpose(1, 0, 2)
    scale = softmax_scale(conf)
    kpos = jnp.arange(T)
    blocks = []
    for q0 in range(0, T, _Q_BLOCK):
        qb = q[:, q0:q0 + _Q_BLOCK]
        s = jnp.einsum("hqd,hkd->hqk", _round(qb, precision),
                       _round(k, precision), precision=_HI) * scale
        qpos = q0 + jnp.arange(qb.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        blocks.append(jnp.einsum("hqk,hkd->hqd", _round(p, precision),
                                 _round(v, precision), precision=_HI))
    att = jnp.concatenate(blocks, 1).transpose(1, 0, 2).reshape(T, H * dv)
    return x + _mm(att, g("attn_wo"), precision)


def route(logits, bias, conf: dict):
    """(weights (T, E): the chosen experts' weights, 0 elsewhere; chosen
    (T, E) bool) for router logits (T, E), as the published gate
    computes them (see the module docstring)."""
    T, E = logits.shape
    G, TG, K = conf["n_group"], conf["topk_group"], conf["top_k"]
    s = jax.nn.sigmoid(logits)
    c = s + bias[None, :]
    group = jnp.sum(lax.top_k(c.reshape(T, G, E // G), 2)[0], -1)  # (T, G)
    _, keep = lax.top_k(group, TG)
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], keep].set(True)
    c = jnp.where(jnp.repeat(kept, E // G, axis=1), c, -jnp.inf)
    _, idx = lax.top_k(c, K)
    chosen = jnp.zeros((T, E), bool).at[jnp.arange(T)[:, None], idx].set(True)
    sc = jnp.where(chosen, s, 0.0)
    return conf["routed_scaling"] * sc / jnp.sum(sc, -1, keepdims=True), \
        chosen


def _ffn(a, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(a, wg, precision)) * _mm(a, wu, precision),
               wd, precision)


def _moe(x, g, *, conf, precision):
    eps = conf["norm_eps"]
    Eh = conf["experts_held"]
    a = _rms(x, g("ln2_g"), eps)
    wts, _ = route(_mm(a, g("moe_router"), precision), g("moe_router_bias"),
                   conf)
    out = _ffn(a, g("moe_ws_gate"), g("moe_ws_up"), g("moe_ws_down"),
               precision) if conf["n_shared_experts"] else jnp.zeros_like(a)
    wg, wu, wd = g("moe_we_gate"), g("moe_we_up"), g("moe_we_down")
    for e in range(Eh):
        out = out + wts[:, e:e + 1] * _ffn(a, wg[e], wu[e], wd[e], precision)
    return x + out


def _layer(x, w, i, cos, sin, *, conf, precision, moe: bool):
    """Layer ``i`` of the stacked ``w`` (the ``dense/`` or ``moe/``
    weights, bfloat16), its weights upcast to float32 here."""
    prefix = "moe/" if moe else "dense/"

    def g(name):
        return lax.dynamic_index_in_dim(w[prefix + name], i, 0,
                                        False).astype(jnp.float32)

    x = _attention(x, g, cos, sin, conf=conf, precision=precision)
    if moe:
        return _moe(x, g, conf=conf, precision=precision)
    a = _rms(x, g("ln2_g"), conf["norm_eps"])
    return x + _ffn(a, g("mlp_w_gate"), g("mlp_w_up"), g("mlp_w_down"),
                    precision)


def _head(x, gf, wu, *, conf, precision):
    return _mm(_rms(x, gf.astype(jnp.float32), conf["norm_eps"]),
               wu.astype(jnp.float32), precision)


_CONF_KEYS = ("n_layers", "first_dense", "d_model", "n_heads", "q_lora",
              "kv_lora", "d_nope", "d_rope", "d_v", "d_ff", "expert_d_ff",
              "n_experts", "experts_held", "n_shared_experts", "top_k",
              "n_group", "topk_group", "routed_scaling", "vocab", "norm_eps",
              "rope_base", "yarn_factor", "yarn_original_len",
              "yarn_beta_fast", "yarn_beta_slow", "yarn_mscale",
              "router_bias_scale")


class Reference:
    """The reference for one configuration and one set of weights, kept
    on the default device.  ``logits(tokens, rows)`` runs the forward
    over ``tokens`` and returns the logits of the rows asked for."""

    def __init__(self, conf: dict, weights: Dict[str, np.ndarray],
                 precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"precision must be f32 or fp8, got {precision}")
        if conf.get("scoring_func") != "sigmoid":
            raise ValueError("this reference computes the sigmoid router "
                             f"only, not {conf.get('scoring_func')!r}")
        self.conf = {k: conf[k] for k in _CONF_KEYS}
        self.w = {k: jnp.asarray(v) for k, v in weights.items()}
        self.precision = precision
        c = self.conf
        self._dense = {k: v for k, v in self.w.items()
                       if k.startswith("dense/")}
        self._moe = {k: v for k, v in self.w.items() if k.startswith("moe/")}
        self._layer = {
            moe: jax.jit(functools.partial(_layer, conf=c,
                                           precision=precision, moe=moe))
            for moe in (False, True)}
        self._head = jax.jit(functools.partial(_head, conf=c,
                                               precision=precision))

    def logits(self, tokens: Sequence[int], rows: Sequence[int]) -> np.ndarray:
        tokens = np.asarray(tokens, np.int32)
        T = len(tokens)
        Tp = -(-T // _BUCKET) * _BUCKET
        padded = np.zeros((Tp,), np.int32)
        padded[:T] = tokens
        cos, sin = rope_tables(Tp, self.conf)
        x = jnp.take(self.w["embed/table"], jnp.asarray(padded),
                     axis=0).astype(jnp.float32)
        nd = self.conf["first_dense"]
        for i in range(self.conf["n_layers"]):
            moe = i >= nd
            x = self._layer[moe](x, self._moe if moe else self._dense,
                                 np.int32(i - nd if moe else i), cos, sin)
        rows = np.asarray(rows, np.int64)
        if rows.size and (rows.min() < 0 or rows.max() >= T):
            raise ValueError(f"rows outside 0..{T - 1}")
        out = []
        for r0 in range(0, len(rows), _BUCKET):
            idx = np.zeros((_BUCKET,), np.int32)
            part = rows[r0:r0 + _BUCKET]
            idx[:len(part)] = part
            lg = self._head(jnp.take(x, jnp.asarray(idx), axis=0),
                            self.w["final_norm/g"], self.w["unembed/w"])
            out.append(np.asarray(lg)[:len(part)])
        return np.concatenate(out, 0) if out else np.zeros(
            (0, self.conf["vocab"]), np.float32)
