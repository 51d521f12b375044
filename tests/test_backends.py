"""One IR, many backends (paper claim E2): every op evaluates identically
on the interpreter (numpy) and the JAX/XLA transformer."""
import numpy as np
import pytest

from repro.core import ops
from repro.core.function import Function
from repro.backend import Backend

RNG = np.random.default_rng(7)


def both(fn, *args, atol=1e-5):
    it = Backend.create("interpreter").compile(fn)
    jt = Backend.create("jax").compile(fn)
    a = it(*args)
    b = jt(*args)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_allclose(
            np.asarray(x, np.float64), np.asarray(y, np.float64),
            atol=atol, rtol=1e-4)
    return a


def _p(shape, dtype="f32", name=None):
    return ops.parameter(shape, dtype, name)


UNARIES = ["exp", "log1p", "tanh", "sigmoid", "relu", "abs_", "sqrt",
           "rsqrt", "erf", "sin", "cos", "floor", "gelu", "silu",
           "negative", "sign"]


@pytest.mark.parametrize("opname", UNARIES)
def test_unary(opname):
    x = _p((3, 4), name="x")
    y = getattr(ops, opname)(ops.sigmoid(x.out()) + 0.5)  # positive domain
    fn = Function([x], [y])
    both(fn, RNG.normal(size=(3, 4)).astype(np.float32))


BINARIES = ["add", "subtract", "multiply", "divide", "maximum", "minimum",
            "less", "greater_equal", "equal"]


@pytest.mark.parametrize("opname", BINARIES)
def test_binary_with_broadcast(opname):
    a = _p((3, 4), name="a")
    b = _p((4,), name="b")
    y = getattr(ops, opname)(a.out(), ops.abs_(b.out()) + 1.0)
    fn = Function([a, b], [ops.convert(y, "f32")])
    both(fn, RNG.normal(size=(3, 4)).astype(np.float32),
         RNG.normal(size=(4,)).astype(np.float32))


def test_shape_ops():
    x = _p((2, 3, 4), name="x")
    v = x.out()
    outs = [
        ops.transpose(v, (2, 0, 1)),
        ops.reshape(v, (6, 4)),
        ops.slice_(v, [0, 1, 0], [2, 3, 4], [1, 1, 2]),
        ops.pad(v, [1, 0, 0], [0, 2, 0], value=-1.0),
        ops.reverse(v, [1]),
        ops.concat([v, v], axis=2),
        ops.broadcast_to(ops.reduce_max(v, [1], keepdims=True), v.shape),
    ]
    both(Function([x], outs), RNG.normal(size=(2, 3, 4)).astype(np.float32))


def test_reductions_and_cumsum():
    x = _p((4, 5), name="x")
    v = x.out()
    outs = [ops.reduce_sum(v, [0]), ops.reduce_mean(v, [1], keepdims=True),
            ops.reduce_min(v), ops.cumsum(v, 1),
            ops.cumsum(v, 0, exclusive=True),
            ops.convert(ops.argmax(v, 1), "f32")]
    both(Function([x], outs), RNG.normal(size=(4, 5)).astype(np.float32))


def test_dot_general_und_einsum():
    a = _p((2, 3, 4), name="a")
    b = _p((2, 4, 5), name="b")
    y1 = ops.matmul(a.out(), b.out())
    y2 = ops.einsum("bij,bjk->bki", a.out(), b.out())
    both(Function([a, b], [y1, y2]),
         RNG.normal(size=(2, 3, 4)).astype(np.float32),
         RNG.normal(size=(2, 4, 5)).astype(np.float32))


@pytest.mark.parametrize("a_shape,b_shape,contracting,batch,spec", [
    ((3, 4), (4, 5), ((1,), (0,)), ((), ()), "ij,jk->ik"),
    ((4, 3), (5, 4), ((0,), (1,)), ((), ()), "ji,kj->ik"),
    ((3, 2, 4), (4, 2, 5), ((2,), (0,)), ((1,), (1,)), "ibj,jbk->bik"),
    ((2, 3, 4, 6), (4, 6, 5), ((2, 3), (0, 1)), ((), ()),
     "abjl,jlk->abk"),
    ((3,), (4,), ((), ()), ((), ()), "i,k->ik"),
    ((6,), (6,), ((0,), (0,)), ((), ()), "j,j->"),
])
def test_dot_general_layouts(a_shape, b_shape, contracting, batch, spec):
    """The interpreter's DotGeneral (one batched matmul) against einsum:
    batch dims anywhere, several or no contracting dims, scalar out."""
    a = _p(a_shape, name="a")
    b = _p(b_shape, name="b")
    fn = Function([a, b], [ops.dot_general(a.out(), b.out(), contracting,
                                           batch)])
    x = RNG.normal(size=a_shape).astype(np.float32)
    y = RNG.normal(size=b_shape).astype(np.float32)
    out = both(fn, x, y)[0]
    np.testing.assert_allclose(out, np.einsum(spec, x, y), rtol=1e-5,
                               atol=1e-5)


def test_gather_scatter_dynamic():
    x = _p((6, 3), name="x")
    idx = _p((4,), "i32", name="idx")
    g = ops.gather(x.out(), idx.out(), axis=0)
    sc = ops.scatter_add(x.out(), idx.out(), g)
    ds = ops.dynamic_slice(x.out(), [ops.constant(2), ops.constant(1)], (3, 2))
    du = ops.dynamic_update_slice(x.out(), ds * 2.0,
                                  [ops.constant(0), ops.constant(0)])
    both(Function([x, idx], [g, sc, ds, du]),
         RNG.normal(size=(6, 3)).astype(np.float32),
         np.array([0, 5, 2, 2], np.int32))


def test_compounds():
    x = _p((4, 8), name="x")
    w = _p((8,), name="w")
    b = _p((8,), name="b")
    outs = [
        ops.softmax(x.out(), -1),
        ops.log_softmax(x.out(), -1),
        ops.rms_norm(x.out(), w.out()),
        ops.layer_norm(x.out(), w.out(), b.out()),
    ]
    both(Function([x, w, b], outs),
         RNG.normal(size=(4, 8)).astype(np.float32),
         RNG.normal(size=(8,)).astype(np.float32),
         RNG.normal(size=(8,)).astype(np.float32))


@pytest.mark.parametrize("causal,window,offset", [
    (True, None, None), (False, None, None), (True, 3, None),
    (True, None, 4), (True, 2, 4)])
def test_attention_variants(causal, window, offset):
    q = _p((2, 4, 6, 8), name="q")
    k = _p((2, 2, 10, 8), name="k")
    v = _p((2, 2, 10, 8), name="v")
    off = ops.constant(offset, dtype="i32") if offset is not None else None
    y = ops.attention(q.out(), k.out(), v.out(), causal=causal,
                      window=window, q_offset=off)
    both(Function([q, k, v], [y]),
         RNG.normal(size=(2, 4, 6, 8)).astype(np.float32),
         RNG.normal(size=(2, 2, 10, 8)).astype(np.float32),
         RNG.normal(size=(2, 2, 10, 8)).astype(np.float32), atol=1e-4)


def test_xent_and_topk():
    lg = _p((3, 7), name="logits")
    lb = _p((3,), "i32", name="labels")
    y = ops.softmax_cross_entropy(lg.out(), lb.out())
    tv, ti = ops.top_k(lg.out(), 3)
    both(Function([lg, lb], [y, tv, ops.convert(ti, "f32")]),
         RNG.normal(size=(3, 7)).astype(np.float32),
         np.array([0, 6, 3], np.int32))


def test_linear_recurrence():
    a = _p((2, 5, 3), name="a")
    b = _p((2, 5, 3), name="b")
    y = ops.linear_recurrence(ops.sigmoid(a.out()), b.out(), axis=1)
    yr = ops.linear_recurrence(ops.sigmoid(a.out()), b.out(), axis=1,
                               reverse=True)
    both(Function([a, b], [y, yr]),
         RNG.normal(size=(2, 5, 3)).astype(np.float32),
         RNG.normal(size=(2, 5, 3)).astype(np.float32))


def test_scan_with_ys_and_reverse():
    c = ops.parameter((3,), "f32", "c")
    x = ops.parameter((3,), "f32", "x")
    w = ops.parameter((3,), "f32", "w")
    body = Function([c, x, w], [ops.tanh(c.out() + x.out() * w.out()),
                                c.out() * 2.0])
    init = _p((3,), name="init")
    xs = _p((6, 3), name="xs")
    wv = _p((3,), name="wv")
    outs = ops.scan(body, [init.out()], xs=[xs.out()], consts=[wv.out()])
    outs_r = ops.scan(body, [init.out()], xs=[xs.out()], consts=[wv.out()],
                      reverse=True)
    both(Function([init, xs, wv], list(outs) + list(outs_r)),
         RNG.normal(size=(3,)).astype(np.float32),
         RNG.normal(size=(6, 3)).astype(np.float32),
         RNG.normal(size=(3,)).astype(np.float32))


def test_bf16_roundtrip():
    x = _p((4, 4), "bf16", name="x")
    y = ops.rms_norm(x.out(), ops.constant(np.ones(4, np.float32)))
    fn = Function([x], [ops.convert(y, "f32")])
    import ml_dtypes
    both(fn, RNG.normal(size=(4, 4)).astype(ml_dtypes.bfloat16), atol=2e-2)
