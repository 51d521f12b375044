"""JAX/XLA transformer: compiles IR Functions to jitted JAX executables.

This is the analogue of the paper's CPU transformer (sec. 4): it walks the
IR and emits backend code (here: a traced JAX program), performing backend
kernel selection — compound ops (RMSNorm, Attention, ...) can be emitted
either as jnp compositions or as Pallas TPU kernels (``use_pallas``), the
way nGraph's CPU transformer selects MKL-DNN kernels.

Collective ops are lowered to ``jax.lax`` collectives when emitting a
per-device program (``mode='shardmap'``); in ``mode='pjit'`` the partitioner
(GSPMD) realizes communication from sharding constraints instead, and
explicit collective nodes are rejected — the transformer chooses how to
realize communication, exactly as the paper prescribes.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.function import Function
from ..core.node import Node
from ..core.types import as_dtype, is_float
from ..kernels import ops as kops
from .base import Transformer, register_transformer

EMIT: Dict[str, Callable] = {}


def _em(op: str):
    def deco(f):
        EMIT[op] = f
        return f
    return deco


class EmitCtx:
    def __init__(self, mode: str = "jit", mesh=None, use_pallas: bool = False,
                 remat_scan: bool = False, interpret_pallas: bool = False,
                 attn_impl: str = "auto", attn_chunk: int = 1024,
                 mm_bm: int = 256, mm_bn: int = 256, mm_bk: int = 512,
                 axis_rules=None):
        self.mode = mode  # 'jit' | 'shardmap' | 'pjit'
        self.mesh = mesh
        self.axis_rules = axis_rules  # logical name -> tuple of mesh axes
        self.use_pallas = use_pallas
        self.remat_scan = remat_scan
        self.interpret_pallas = interpret_pallas
        # matmul-family tile shapes (autotune-resolved; consumed by the
        # Pallas matmul / SwiGLU / NormMatmul realizations)
        self.mm_bm = mm_bm
        self.mm_bn = mm_bn
        self.mm_bk = mm_bk
        # attention realization: 'auto' picks chunked (online-softmax scan)
        # once Sq*Skv would materialize a big score tensor; 'naive'/'chunked'
        # force one implementation (the perf loop sweeps this knob).
        self.attn_impl = attn_impl
        self.attn_chunk = attn_chunk
        self._body_cache: Dict[int, Callable] = {}

    def body_callable(self, body: Function) -> Callable:
        key = id(body)
        if key not in self._body_cache:
            self._body_cache[key] = emit_callable(body, self)
        return self._body_cache[key]


def _f32up(x):
    dt = np.dtype(x.dtype)
    if is_float(dt) and dt.itemsize < 4:
        return x.astype(jnp.float32)
    return x


def _outcast(node: Node, x, i: int = 0):
    t = node.out_types[i]
    if np.dtype(x.dtype) != t.dtype:
        x = x.astype(t.dtype)
    return x


# -- leaves -------------------------------------------------------------------
@_em("Constant")
def _(node, args, ctx):
    return [jnp.asarray(node.attrs["value"])]


@_em("Iota")
def _(node, args, ctx):
    t = node.out_types[0]
    return [lax.broadcasted_iota(t.dtype, t.shape, node.attrs["dim"])]


# -- elementwise --------------------------------------------------------------
_UNARY = {
    "Negative": lambda x: -x,
    "Exp": jnp.exp, "Log": jnp.log, "Log1p": jnp.log1p, "Expm1": jnp.expm1,
    "Tanh": jnp.tanh, "Sigmoid": jax.nn.sigmoid,
    "Relu": lambda x: jnp.maximum(x, 0), "Abs": jnp.abs, "Sign": jnp.sign,
    "Sqrt": jnp.sqrt, "Rsqrt": lax.rsqrt, "Erf": lax.erf,
    "Sin": jnp.sin, "Cos": jnp.cos, "Floor": jnp.floor,
    "Gelu": functools.partial(jax.nn.gelu, approximate=False),
    "Silu": jax.nn.silu,
}
for _opname, _fn in _UNARY.items():
    def _mk(fn):
        def run(node, args, ctx):
            return [_outcast(node, fn(args[0]))]
        return run
    EMIT[_opname] = _mk(_fn)

_BINOP = {
    "Add": jnp.add, "Subtract": jnp.subtract, "Multiply": jnp.multiply,
    "Divide": lambda a, b: jnp.divide(a, b) if is_float(np.dtype(a.dtype))
    else jnp.floor_divide(a, b),
    "Power": jnp.power, "Maximum": jnp.maximum, "Minimum": jnp.minimum,
    "Less": jnp.less, "LessEqual": jnp.less_equal, "Greater": jnp.greater,
    "GreaterEqual": jnp.greater_equal, "Equal": jnp.equal,
    "NotEqual": jnp.not_equal, "And": jnp.logical_and, "Or": jnp.logical_or,
}
for _opname, _fn in _BINOP.items():
    def _mk2(fn):
        def run(node, args, ctx):
            return [_outcast(node, fn(args[0], args[1]))]
        return run
    EMIT[_opname] = _mk2(_fn)


@_em("Not")
def _(node, args, ctx):
    return [jnp.logical_not(args[0])]


@_em("Select")
def _(node, args, ctx):
    return [_outcast(node, jnp.where(args[0], args[1], args[2]))]


@_em("Convert")
def _(node, args, ctx):
    return [args[0].astype(node.attrs["dtype"])]


@_em("StopGradient")
def _(node, args, ctx):
    return [lax.stop_gradient(args[0])]


@_em("OptimizationBarrier")
def _(node, args, ctx):
    return [lax.optimization_barrier(args[0])]


# -- shape --------------------------------------------------------------------
@_em("Reshape")
def _(node, args, ctx):
    return [jnp.reshape(args[0], node.attrs["shape"])]


@_em("Transpose")
def _(node, args, ctx):
    return [jnp.transpose(args[0], node.attrs["perm"])]


@_em("BroadcastInDim")
def _(node, args, ctx):
    return [lax.broadcast_in_dim(args[0], node.attrs["shape"],
                                 node.attrs["broadcast_dims"])]


@_em("Slice")
def _(node, args, ctx):
    return [lax.slice(args[0], node.attrs["starts"], node.attrs["stops"],
                      node.attrs["strides"])]


@_em("Concat")
def _(node, args, ctx):
    return [lax.concatenate(args, node.attrs["axis"])]


@_em("Pad")
def _(node, args, ctx):
    cfg = [(l, h, 0) for l, h in zip(node.attrs["low"], node.attrs["high"])]
    val = jnp.asarray(node.attrs["value"], dtype=args[0].dtype)
    return [lax.pad(args[0], val, cfg)]


@_em("Reverse")
def _(node, args, ctx):
    return [lax.rev(args[0], node.attrs["axes"])]


# -- reductions -----------------------------------------------------------
def _emit_reduce(fn):
    def run(node, args, ctx):
        x = _f32up(args[0])
        out = fn(x, axis=node.attrs["axes"], keepdims=node.attrs["keepdims"])
        return [_outcast(node, out)]
    return run


EMIT["ReduceSum"] = _emit_reduce(jnp.sum)
EMIT["ReduceMax"] = _emit_reduce(jnp.max)
EMIT["ReduceMin"] = _emit_reduce(jnp.min)


@_em("CumSum")
def _(node, args, ctx):
    x = _f32up(args[0])
    ax = node.attrs["axis"]
    out = jnp.cumsum(x, axis=ax)
    if node.attrs["exclusive"]:
        out = out - x
    return [_outcast(node, out)]


@_em("ArgMax")
def _(node, args, ctx):
    return [jnp.argmax(args[0], axis=node.attrs["axis"]).astype(jnp.int32)]


@_em("TopK")
def _(node, args, ctx):
    v, i = lax.top_k(args[0], node.attrs["k"])
    return [v, i.astype(jnp.int32)]


# -- contraction ----------------------------------------------------------
@_em("DotGeneral")
def _(node, args, ctx):
    a, b = args
    dn = (tuple(node.attrs["contracting"]), tuple(node.attrs["batch"]))
    t = node.out_types[0]
    # plain matmul-shaped dots route through the Pallas tiled kernel when
    # the shape tiles cleanly; everything else (batched einsums, one-hot
    # contractions) keeps the generic XLA lowering
    if ctx.use_pallas and b.ndim == 2 and a.ndim >= 2 and \
            dn == (((a.ndim - 1,), (0,)), ((), ())) and \
            np.dtype(a.dtype) == np.dtype(b.dtype) == t.dtype and \
            is_float(np.dtype(a.dtype)):
        rows = a.size // a.shape[-1]
        if kops.matmul_supported(rows, a.shape[-1], b.shape[1]):
            out = kops.matmul(a.reshape(rows, a.shape[-1]), b,
                              bm=ctx.mm_bm, bn=ctx.mm_bn, bk=ctx.mm_bk,
                              interpret=ctx.interpret_pallas)
            return [_outcast(node, out.reshape(t.shape))]
    out = lax.dot_general(a, b, dimension_numbers=dn,
                          preferred_element_type=t.dtype)
    return [out]


# -- indexing ---------------------------------------------------------------
@_em("Gather")
def _(node, args, ctx):
    return [jnp.take(args[0], args[1], axis=node.attrs["axis"])]


@_em("ScatterAdd")
def _(node, args, ctx):
    op, idx, upd = args
    return [op.at[idx].add(upd.astype(op.dtype))]


@_em("ScatterSet")
def _(node, args, ctx):
    # Every dim up to the last indexed axis becomes an index: those the op
    # does not index, by an iota over their (local) extent.  The window
    # XLA scatters is then only the trailing dims.  On the (L, P, Hkv, ps,
    # D) page pool (axes 0, 1, 3) a (Hkv, D) window with ps inserted
    # between makes the TPU's layout assignment carry the pool in another
    # layout than the program's parameters, and copy the whole pool into
    # and out of every call; rows of D keep the parameters' layout.
    op, idx, upd = args
    axes = node.attrs["axes"]
    nb = idx.ndim - 1
    lead = [d for d in range(max(axes) + 1) if d not in axes]
    index = [slice(None)] * op.ndim
    for k, a in enumerate(axes):
        index[a] = idx[..., k].reshape(idx.shape[:-1] + (1,) * len(lead))
    for j, d in enumerate(lead):
        shape = [1] * (nb + len(lead))
        shape[nb + j] = op.shape[d]
        index[d] = jnp.arange(op.shape[d], dtype=idx.dtype).reshape(shape)
    return [op.at[tuple(index)].set(upd, mode="drop",
                                    wrap_negative_indices=False)]


@_em("DynamicSlice")
def _(node, args, ctx):
    return [lax.dynamic_slice(args[0], args[1:], node.attrs["sizes"])]


@_em("DynamicUpdateSlice")
def _(node, args, ctx):
    return [lax.dynamic_update_slice(args[0], args[1], args[2:])]


# -- compounds (kernel-selection point) --------------------------------------
@_em("Softmax")
def _(node, args, ctx):
    return [_outcast(node, jax.nn.softmax(_f32up(args[0]), axis=node.attrs["axis"]))]


@_em("LogSoftmax")
def _(node, args, ctx):
    return [_outcast(node, jax.nn.log_softmax(_f32up(args[0]), axis=node.attrs["axis"]))]


@_em("RMSNorm")
def _(node, args, ctx):
    if ctx.use_pallas and kops.rmsnorm_supported(args[0].shape):
        return [_outcast(node, kops.rmsnorm(args[0], args[1], node.attrs["eps"],
                                            interpret=ctx.interpret_pallas))]
    x = _f32up(args[0])
    w = _f32up(args[1])
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return [_outcast(node, x * lax.rsqrt(var + node.attrs["eps"]) * w)]


@_em("LayerNorm")
def _(node, args, ctx):
    x, w, b = (_f32up(a) for a in args)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return [_outcast(node, (x - mu) * lax.rsqrt(var + node.attrs["eps"]) * w + b)]


@_em("SwiGLU")
def _(node, args, ctx):
    x, wg, wu, wd = args
    t = node.out_types[0]
    d = x.shape[-1]
    rows = x.size // d
    if ctx.use_pallas and \
            np.dtype(x.dtype) == np.dtype(wg.dtype) == np.dtype(wd.dtype) and \
            kops.swiglu_supported(rows, d, wg.shape[1], wd.shape[1],
                                  np.dtype(x.dtype).itemsize):
        out = kops.swiglu(x.reshape(rows, d), wg, wu, wd,
                          bm=ctx.mm_bm, bn=ctx.mm_bn, bk=ctx.mm_bk,
                          interpret=ctx.interpret_pallas)
        return [_outcast(node, out.reshape(t.shape))]
    g = jax.nn.silu(jnp.dot(x, wg,
                            preferred_element_type=jnp.float32).astype(x.dtype))
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32).astype(x.dtype)
    return [_outcast(node, jnp.dot(g * u, wd,
                                   preferred_element_type=jnp.float32))]


@_em("NormMatmul")
def _(node, args, ctx):
    x, g, w = args
    t = node.out_types[0]
    d = x.shape[-1]
    rows = x.size // d
    if ctx.use_pallas and np.dtype(x.dtype) == np.dtype(w.dtype) and \
            kops.norm_matmul_supported(rows, d, w.shape[1]):
        out = kops.norm_matmul(x.reshape(rows, d), g, w,
                               eps=node.attrs["eps"], bm=ctx.mm_bm,
                               bn=ctx.mm_bn, interpret=ctx.interpret_pallas)
        return [_outcast(node, out.reshape(t.shape))]
    xf = _f32up(x)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    nrm = (xf * lax.rsqrt(var + node.attrs["eps"]) * _f32up(g)).astype(x.dtype)
    return [_outcast(node, jnp.dot(nrm, w,
                                   preferred_element_type=jnp.float32))]


@_em("RotaryQKV")
def _(node, args, ctx):
    x, wq, wk, wv, cos, sin = args
    at = node.attrs
    B, S, D = x.shape

    def mm(a2, w):
        # projections route through the Pallas tiled matmul; the rope
        # epilogue is elementwise and stays in XLA
        if ctx.use_pallas and np.dtype(x.dtype) == np.dtype(w.dtype) and \
                kops.matmul_supported(B * S, D, w.shape[1]):
            return kops.matmul(a2, w, bm=ctx.mm_bm, bn=ctx.mm_bn,
                               bk=ctx.mm_bk, interpret=ctx.interpret_pallas)
        return jnp.dot(a2, w,
                       preferred_element_type=jnp.float32).astype(x.dtype)

    def split(y, h):
        return y.reshape(B, S, h, -1).transpose(0, 2, 1, 3)

    def rope(v4):
        half = v4.shape[-1] // 2
        x1, x2 = v4[..., :half], v4[..., half:]
        c = cos[None, None].astype(v4.dtype)
        s = sin[None, None].astype(v4.dtype)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    x2d = x.reshape(B * S, D)
    q = rope(split(mm(x2d, wq), at["n_heads"]))
    k = rope(split(mm(x2d, wk), at["n_kv"]))
    v = split(mm(x2d, wv), at["n_kv"])
    return [_outcast(node, q, 0), _outcast(node, k, 1), _outcast(node, v, 2)]


def reference_attention(q, k, v, *, causal, window, scale, q_offset=None):
    """jnp reference attention (BHSD, GQA by head repeat, f32 softmax)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    off = q_offset if q_offset is not None else 0
    qpos = jnp.arange(Sq)[:, None] + off
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


@_em("Attention")
def _(node, args, ctx):
    at = node.attrs
    q, k, v = args[:3]
    q_offset = args[3] if at["has_offset"] else None
    if ctx.use_pallas and kops.attention_supported(q.shape, k.shape):
        return [_outcast(node, kops.flash_attention(
            q, k, v, causal=at["causal"], window=at["window"], scale=at["scale"],
            q_offset=q_offset, interpret=ctx.interpret_pallas))]
    Sq, Skv = q.shape[2], k.shape[2]
    use_chunked = ctx.attn_impl == "chunked" or (
        ctx.attn_impl == "auto" and Sq > 1 and Skv > 2048
        and Skv % ctx.attn_chunk == 0)
    if use_chunked:
        from ..kernels.xla_attention import chunked_attention
        return [_outcast(node, chunked_attention(
            q, k, v, causal=at["causal"], window=at["window"],
            scale=at["scale"], q_offset=q_offset, bk=ctx.attn_chunk))]
    return [_outcast(node, reference_attention(
        q, k, v, causal=at["causal"], window=at["window"], scale=at["scale"],
        q_offset=q_offset))]


@_em("SoftmaxCrossEntropy")
def _(node, args, ctx):
    logits, labels = args
    lg = _f32up(logits)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return [(lse - ll).astype(jnp.float32)]


@_em("LinearRecurrence")
def _(node, args, ctx):
    a, b = args
    axis = node.attrs["axis"]

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_r * a_l, a_r * b_l + b_r

    a_s, h = lax.associative_scan(combine, (a, b), axis=axis,
                                  reverse=node.attrs["reverse"])
    del a_s
    return [_outcast(node, h)]


# -- collectives ------------------------------------------------------------
def _collective_guard(node, ctx):
    if ctx.mode != "shardmap":
        raise RuntimeError(
            f"{node.op} requires mode='shardmap' (explicit per-device program); "
            f"in pjit mode communication is realized by GSPMD from shardings"
        )


@_em("AllReduce")
def _(node, args, ctx):
    _collective_guard(node, ctx)
    ax = node.attrs["axis_name"]
    rop = node.attrs["reduce_op"]
    if rop == "sum":
        return [lax.psum(args[0], ax)]
    if rop == "max":
        return [lax.pmax(args[0], ax)]
    if rop == "min":
        return [lax.pmin(args[0], ax)]
    return [lax.pmean(args[0], ax)]


@_em("AllGather")
def _(node, args, ctx):
    _collective_guard(node, ctx)
    return [lax.all_gather(args[0], node.attrs["axis_name"],
                           axis=node.attrs["axis"], tiled=True)]


@_em("ReduceScatter")
def _(node, args, ctx):
    _collective_guard(node, ctx)
    return [lax.psum_scatter(args[0], node.attrs["axis_name"],
                             scatter_dimension=node.attrs["axis"], tiled=True)]


@_em("AllToAll")
def _(node, args, ctx):
    _collective_guard(node, ctx)
    return [lax.all_to_all(args[0], node.attrs["axis_name"],
                           node.attrs["split_axis"], node.attrs["concat_axis"],
                           tiled=True)]


@_em("CollectivePermute")
def _(node, args, ctx):
    _collective_guard(node, ctx)
    return [lax.ppermute(args[0], node.attrs["axis_name"],
                         list(node.attrs["pairs"]))]


def _resolve_spec(shape, spec, rules, mesh):
    """Map *logical* axis names in a ShardingConstraint spec to mesh axes
    via ``rules`` (logical -> tuple of mesh axes), keeping only axes that
    exist in the mesh, divide the dim, and are not already used."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    entries = []
    for dim, entry in zip(shape, spec):
        if entry is None:
            entries.append(None)
            continue
        logical = entry if isinstance(entry, tuple) else (entry,)
        axes = []
        for name in logical:
            for a in rules.get(name, (name,) if name in sizes else ()):
                if a in sizes and a not in used:
                    axes.append(a)
        keep, prod = [], 1
        for a in axes:
            if dim % (prod * sizes[a]) == 0:
                keep.append(a)
                prod *= sizes[a]
        used.update(keep)
        entries.append(None if not keep else
                       (keep[0] if len(keep) == 1 else tuple(keep)))
    return entries


@_em("ShardingConstraint")
def _(node, args, ctx):
    if ctx.mode == "pjit" and ctx.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rules = ctx.axis_rules or {}
        entries = _resolve_spec(node.out_types[0].shape, node.attrs["spec"],
                                rules, ctx.mesh)
        return [jax.lax.with_sharding_constraint(
            args[0], NamedSharding(ctx.mesh, PartitionSpec(*entries)))]
    return [args[0]]


# -- structured control -------------------------------------------------------
@_em("Scan")
def _(node, args, ctx):
    at = node.attrs
    nc, nx = at["n_carry"], at["n_xs"]
    carries = tuple(args[:nc])
    xs = tuple(args[nc:nc + nx])
    consts = tuple(args[nc + nx:])
    body_call = ctx.body_callable(at["body"])
    if ctx.remat_scan:
        body_call = jax.checkpoint(body_call)

    def f(carry, x):
        x = x if x is not None else ()
        outs = body_call(*carry, *x, *consts)
        return tuple(outs[:nc]), tuple(outs[nc:])

    final, ys = lax.scan(f, carries, xs if nx else None, length=at["length"],
                         reverse=at["reverse"], unroll=at["unroll"])
    return list(final) + list(ys)


# ---------------------------------------------------------------------------
def emit_callable(fn: Function, ctx: Optional[EmitCtx] = None) -> Callable:
    """Emit a plain python callable tracing the IR with jnp ops."""
    ctx = ctx or EmitCtx()
    nodes = fn.nodes()
    for n in nodes:
        if n.op != "Parameter" and n.op not in EMIT:
            raise NotImplementedError(f"jax backend: no emitter for {n.op}")

    def run(*args):
        if len(args) != len(fn.parameters):
            raise TypeError(f"{fn.name}: expected {len(fn.parameters)} args")
        env: Dict[int, List[Any]] = {}
        for p, a in zip(fn.parameters, args):
            env[id(p)] = [jnp.asarray(a)]
        for node in nodes:
            if node.op == "Parameter":
                continue
            ins = [env[id(v.node)][v.index] for v in node.inputs]
            env[id(node)] = list(EMIT[node.op](node, ins, ctx))
        return tuple(env[id(r.node)][r.index] for r in fn.results)

    run.__name__ = f"ngraph_{fn.name}"
    return run


class JaxTransformer(Transformer):
    """Legacy handle for the jax backend; ``compile`` (inherited) forwards
    to ``repro.backend.JaxBackend`` — codegen itself lives above in EMIT."""

    name = "jax"


register_transformer(JaxTransformer())
