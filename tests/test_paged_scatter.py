"""The paged programs write each step's K/V rows into the carried page
pool in place (``ops.scatter_set``) instead of blending a pool-sized
one-hot update into every page of every layer.

What is checked:

  * the op itself, on the interpreter and on the JAX backend, against a
    NumPy reference: leading and non-leading index axes, rows dropped
    for out-of-range indices, and colliding rows on a trash slot;
  * ``paged_write`` against the one-hot blend it replaced, bit for bit
    on every page but the trash page, and the paged chunk and paged
    prefill programs against the dense paths: tokens match token for
    token and every live page holds exactly the dense cache's rows,
    with random page tables, retired rows on the trash page, and a
    copy-on-write shared prefix page that comes out unchanged;
  * the lowered paged chunk holds a scatter and no pool-shaped select or
    dynamic-update-slice (the blend and the per-layer re-stack);
  * the partition pass keeps a head-sharded pool sharded through the
    scatter, and the cost model charges the rows, not the pool.
"""
import numpy as np
import pytest

from repro.backend import Backend
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import ops
from repro.core.function import Function
from repro.models import components as C
from repro.models.lm import (build_dense_chunk, build_dense_paged_prefill,
                             build_graphs)

CFG = get_config("deepseek-7b").reduced()
BACKENDS = ["interpreter", "jax"]


# -- the op ---------------------------------------------------------------------
def _np_scatter(operand, idx, upd, axes):
    """NumPy reference: rows in order, out-of-range rows dropped; returns
    the result and a mask of the elements more than one row wrote."""
    out = operand.copy()
    hits = np.zeros(operand.shape, np.int32)
    idx = idx.reshape(-1, len(axes))
    win = [d for d in range(operand.ndim) if d not in axes]
    rows = upd.reshape((idx.shape[0],) + tuple(operand.shape[d] for d in win))
    for n in range(idx.shape[0]):
        if not all(0 <= idx[n, k] < operand.shape[a]
                   for k, a in enumerate(axes)):
            continue
        index = [slice(None)] * operand.ndim
        for k, a in enumerate(axes):
            index[a] = int(idx[n, k])
        out[tuple(index)] = rows[n]
        hits[tuple(index)] += 1
    return out, hits > 1


SCATTER_CASES = {
    # the leading axis, a duplicate, one row past the end and
    # one negative row (both dropped, not wrapped)
    "leading": ((5, 3), (0,), np.array([[1], [4], [1], [5], [-1]])),
    # the pool layout: (layer, page, :, offset, :), two rows colliding
    # on the trash page 0 and one row past the last page
    "pool": ((2, 6, 3, 4, 5), (0, 1, 3),
             np.array([[0, 2, 1], [1, 5, 3], [1, 0, 2], [1, 0, 2],
                       [0, 3, 0], [0, 6, 0]])),
    # adjacent non-leading axes with two batch dims of index rows
    "inner": ((3, 4, 5, 2), (1, 2),
              np.array([[[0, 4], [3, 0]], [[2, 2], [1, 1]]])),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(SCATTER_CASES))
def test_scatter_set_matches_numpy(backend, case):
    shape, axes, idx = SCATTER_CASES[case]
    rng = np.random.default_rng(3)
    idx = idx.astype(np.int32)
    win = tuple(s for d, s in enumerate(shape) if d not in axes)
    x = rng.normal(size=shape).astype(np.float32)
    u = rng.normal(size=idx.shape[:-1] + win).astype(np.float32)
    px = ops.parameter(shape, "f32", "x")
    pi = ops.parameter(idx.shape, "i32", "i")
    pu = ops.parameter(u.shape, "f32", "u")
    out = ops.scatter_set(px.out(), pi.out(), pu.out(), axes=axes)
    got = np.asarray(Backend.create(backend).compile(
        Function([px, pi, pu], [out]))(x, idx, u)[0])
    want, collided = _np_scatter(x, idx, u, axes)
    np.testing.assert_array_equal(got[~collided], want[~collided])
    if collided.any():       # one of the colliding rows wins, whole
        flat = idx.reshape(-1, len(axes))
        rows = u.reshape((flat.shape[0],) + win)
        dup = [n for n in range(flat.shape[0])
               if (flat == flat[n]).all(axis=1).sum() > 1]
        index = [slice(None)] * len(shape)
        for k, a in enumerate(axes):
            index[a] = int(flat[dup[0], k])
        assert any(np.array_equal(got[tuple(index)], rows[n]) for n in dup)


def test_scatter_set_validates():
    x = ops.parameter((4, 3, 2), "f32", "x").out()
    i = ops.parameter((5, 2), "i32", "i").out()
    with pytest.raises(ValueError):
        ops.scatter_set(x, i, ops.parameter((5, 3), "f32", "u").out(),
                        axes=(0, 1))
    with pytest.raises(TypeError):
        ops.scatter_set(x, ops.parameter((5, 2), "f32", "i").out(),
                        ops.parameter((5, 2), "f32", "u").out(), axes=(0, 1))
    with pytest.raises(ValueError):
        ops.scatter_set(x, i, ops.parameter((5, 3), "f32", "u").out(),
                        axes=(0, 0))
    ok = ops.scatter_set(x, i, ops.parameter((5, 3), "f32", "u").out(),
                         axes=(0, 2))
    assert ok.shape == (4, 3, 2)


# -- paged_write against the one-hot blend it replaced ------------------------------
def _blend_write(pool, new, page_tbl, pos, page_size):
    """The former ``paged_write``: a pool-sized one-hot update selected
    against the whole (P, Hkv, ps, D) pool of one layer."""
    P, Hkv, ps, D = pool.shape
    B, MP = page_tbl.shape
    psc = ops.constant(page_size, dtype="i32")
    lp = pos / psc
    off = pos - lp * psc
    lp = ops.minimum(lp, ops.constant(MP - 1, dtype="i32"))
    pid = ops.reshape(ops.take_along_last(page_tbl, ops.reshape(lp, (B, 1))),
                      (B,))
    wmask = ops.einsum("bp,bs->bps", ops.one_hot(pid, P, dtype=pool.dtype),
                       ops.one_hot(off, ps, dtype=pool.dtype))
    newr = ops.reshape(ops.convert(new, pool.dtype), (B, Hkv, D))
    upd = ops.einsum("bps,bhd->phsd", wmask, newr)
    hit = ops.reshape(ops.reduce_sum(wmask, axes=[0]), (P, 1, ps, 1))
    cond = ops.greater(ops.broadcast_to(hit, pool.shape),
                       ops.constant(0.0, dtype=pool.dtype))
    return ops.select(cond, upd, pool)


@pytest.mark.parametrize("backend", BACKENDS)
def test_paged_write_matches_blend(backend):
    """Each layer of the scattered pool equals the old blend of that
    layer's slice bit for bit, every other layer is untouched, and the
    trash page differs only where retired rows wrote."""
    L, P, Hkv, ps, D, B, MP = 3, 9, 2, 4, 8, 4, 2
    rng = np.random.default_rng(5)
    pool = rng.normal(size=(L, P, Hkv, ps, D)).astype(np.float32)
    new = rng.normal(size=(B, Hkv, 1, D)).astype(np.float32)
    tbl = np.zeros((B, MP), np.int32)          # rows 2, 3 retired: trash
    tbl[:2] = rng.permutation(np.arange(1, P))[:2 * MP].reshape(2, MP)
    pos = np.array([3, 6, 5, 5], np.int32)     # retired rows collide
    pp = ops.parameter(pool.shape, "f32", "pool")
    pn = ops.parameter(new.shape, "f32", "new")
    pt = ops.parameter(tbl.shape, "i32", "tbl")
    pq = ops.parameter(pos.shape, "i32", "pos")
    pl = ops.parameter((), "i32", "layer")
    outs = [C.paged_write(pp.out(), pn.out(), pt.out(), pq.out(), ps,
                          pl.out())]
    sl = ops.parameter((P, Hkv, ps, D), "f32", "slice")
    blend = Function([sl, pn, pt, pq],
                     [_blend_write(sl.out(), pn.out(), pt.out(), pq.out(),
                                   ps)])
    be = Backend.create(backend)
    write = be.compile(Function([pp, pn, pt, pq, pl], outs))
    old = be.compile(blend)
    for layer in range(L):
        got = np.asarray(write(pool, new, tbl, pos, np.int32(layer))[0])
        want = np.asarray(old(pool[layer], new, tbl, pos)[0])
        others = [m for m in range(L) if m != layer]
        np.testing.assert_array_equal(got[others], pool[others])
        np.testing.assert_array_equal(got[layer, 1:], want[1:])
        trash = got[layer, 0]
        np.testing.assert_array_equal(np.delete(trash, 1, axis=1),
                                      np.delete(pool[layer, 0], 1, axis=1))
        assert any(np.array_equal(trash[:, 1], new[r, :, 0]) for r in (2, 3))


# -- the paged programs against the dense paths -------------------------------------
def _params_for(builder, named):
    return [named[n] for n in builder.param_names()]


def _random_pool(rng, shape, dtype):
    return rng.normal(size=shape).astype(np.float32).astype(dtype)


def test_paged_chunk_matches_dense_serve():
    """``steps`` fused paged decode steps against the dense continuous
    ``serve`` graph stepped ``steps`` times on the same K/V: live rows'
    tokens match token for token, every live page holds exactly the
    dense cache's rows afterwards, the COW-shared prefix page and every
    unreferenced page are unchanged, and the trash page changes only in
    the slots the two retired rows both wrote."""
    B, max_len, ps, steps = 4, 16, 4, 3
    mp = max_len // ps
    P = 1 + B * mp
    L, Hkv, D = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    rng = np.random.default_rng(11)
    jt = Backend.create("jax")

    srv = build_graphs(CFG, ShapeConfig("serve", "serve", max_len, B), B)
    chunk = build_dense_chunk(CFG, max_len, B, steps, page_size=ps)
    assert chunk.aux["n_pages"] == P
    named = srv.builder.init_params(0)
    dt = srv.fn.parameters[2].out_types[0].dtype

    # rows 0, 1 live and sharing their first (full, read-only) page;
    # rows 2, 3 retired onto the trash page 0, colliding at slot 1
    perm = rng.permutation(np.arange(1, P))
    tbl = np.zeros((B, mp), np.int32)
    tbl[0] = perm[:mp]
    tbl[1, 0] = tbl[0, 0]
    tbl[1, 1:] = perm[mp:2 * mp - 1]
    shared = int(tbl[0, 0])
    pos = np.array([6, 9, 5, 5], np.int32)
    tok = rng.integers(0, CFG.vocab, size=(B, 1)).astype(np.int32)
    pools = [_random_pool(rng, (L, P, Hkv, ps, D), dt) for _ in range(2)]
    dense = [np.zeros((L, B, Hkv, max_len, D), dt) for _ in range(2)]
    for pool, dc in zip(pools, dense):
        for b in range(B):
            for j in range(mp):
                dc[:, b, :, j * ps:(j + 1) * ps, :] = pool[:, tbl[b, j]]

    sex = jt.compile(srv.fn)
    d_tok, d_pos, d_caches, d_toks = tok.copy(), pos.copy(), dense, []
    for _ in range(steps):
        outs = sex(d_tok, d_pos, *d_caches, *_params_for(srv.builder, named))
        d_tok = np.asarray(outs[0])
        d_caches = [np.asarray(o) for o in outs[1:]]
        d_toks.append(d_tok)
        d_pos = d_pos + 1

    zeros = np.zeros((B,), np.int32)
    outs = jt.compile(chunk.fn)(
        tok, pos, tbl, zeros.astype(np.float32), zeros, zeros, *pools,
        *_params_for(chunk.builder, named))
    p_toks = np.asarray(outs[0])
    new_pools = [np.asarray(o) for o in outs[1:]]

    np.testing.assert_array_equal(p_toks[:, :2], np.stack(d_toks)[:, :2])
    for pool, new, dc in zip(pools, new_pools, d_caches):
        for b in (0, 1):
            for j in range(mp):
                np.testing.assert_array_equal(
                    new[:, tbl[b, j]], dc[:, b, :, j * ps:(j + 1) * ps, :])
        np.testing.assert_array_equal(new[:, shared], pool[:, shared])
        free = sorted(set(range(1, P)) - set(tbl.ravel().tolist()))
        assert free
        np.testing.assert_array_equal(new[:, free], pool[:, free])
        # retired rows write slots (5..7) % 4 of the trash page
        np.testing.assert_array_equal(new[:, 0, :, 0], pool[:, 0, :, 0])
        assert not np.array_equal(new[:, 0, :, 1:], pool[:, 0, :, 1:])


def test_paged_prefill_matches_dense_prefill():
    """Chunked paged prefill after a COW-shared first page: the final
    chunk's token matches the dense ``prefill`` graph's, every page the
    chunks wrote holds exactly the dense cache's rows, and the shared
    page, the trash page and every other page come out unchanged."""
    S, max_len, ps, Cn = 12, 16, 4, 4
    mp = max_len // ps
    P = 1 + 2 * mp
    L, Hkv, D = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    rng = np.random.default_rng(13)
    jt = Backend.create("jax")

    pre = build_graphs(CFG, ShapeConfig("prefill", "prefill", S, 1), 1)
    named = pre.builder.init_params(0)
    prompt = rng.integers(0, CFG.vocab, size=(1, S)).astype(np.int32)
    douts = jt.compile(pre.fn)(prompt, *_params_for(pre.builder, named))
    d_tok = np.argmax(np.asarray(douts[0]).reshape(-1))
    d_caches = [np.asarray(o) for o in douts[1:]]    # (L, 1, Hkv, S, D)

    g = build_dense_paged_prefill(CFG, max_len, Cn, page_size=ps,
                                  n_pages=P)
    dt = g.fn.parameters[3].out_types[0].dtype
    tbl = rng.permutation(np.arange(1, P))[:mp].astype(np.int32)[None]
    shared = int(tbl[0, 0])
    pools = [_random_pool(rng, (L, P, Hkv, ps, D), dt) for _ in range(2)]
    for pool, dc in zip(pools, d_caches):     # the attached prefix page
        pool[:, shared] = dc[:, 0, :, :ps, :]
    cf = jt.compile(g.fn)
    cur = [p.copy() for p in pools]
    for lo in range(ps, S, Cn):
        outs = cf(prompt[:, lo:lo + Cn], np.int32(lo), tbl, *cur,
                  *_params_for(g.builder, named))
        cur = [np.asarray(o) for o in outs[1:]]
    assert np.argmax(np.asarray(outs[0]).reshape(-1)) == d_tok
    for pool, new, dc in zip(pools, cur, d_caches):
        for j in range(S // ps):
            np.testing.assert_array_equal(new[:, tbl[0, j]],
                                          dc[:, 0, :, j * ps:(j + 1) * ps, :])
        np.testing.assert_array_equal(new[:, shared], pool[:, shared])
        rest = sorted(set(range(P)) - set(tbl[0, 1:S // ps].tolist()))
        np.testing.assert_array_equal(new[:, rest], pool[:, rest])


# -- structure of the lowered program ---------------------------------------------
def test_paged_chunk_lowers_to_scatter_without_pool_blend():
    """The lowered paged chunk scatters its rows, and neither selects
    over nor re-stacks a pool-shaped tensor (whole pool or one layer)."""
    import jax

    B, max_len, ps, steps, P = 2, 16, 4, 2, 11
    g = build_dense_chunk(CFG, max_len, B, steps, page_size=ps, n_pages=P)
    cf = Backend.create("jax").compile(g.fn)
    specs = [jax.ShapeDtypeStruct(t.shape, np.dtype(t.dtype))
             for t in g.fn.in_types]
    text = cf.lower(*specs).as_text()
    assert "stablehlo.scatter" in text
    L, Hkv, D = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    pool = f"{L}x{P}x{Hkv}x{ps}x{D}x"
    layer = f"tensor<{P}x{Hkv}x{ps}x{D}x"
    for line in text.splitlines():
        if "select" in line or "dynamic_update_slice" in line:
            assert pool not in line and layer not in line, line


# -- partition and cost ------------------------------------------------------------
def _pool_scatter_fn():
    """A pool sharded on heads (the tensor-parallel serving profile)
    written by rows whose heads come from a head-sharded projection."""
    L, P, H, ps, D, B = 2, 5, 4, 4, 8, 3
    pool = ops.parameter((L, P, H, ps, D), "f32", "pool")
    pool.attrs["logical_axes"] = (None, None, "kv_heads", None, None)
    x = ops.parameter((B, 6), "f32", "x")
    w = ops.parameter((6, H * D), "f32", "w")
    w.attrs["logical_axes"] = (None, "kv_heads")
    idx = ops.parameter((B, 3), "i32", "idx")
    rows = ops.reshape(ops.matmul(x.out(), w.out()), (B, H, D))
    out = ops.scatter_set(pool.out(), idx.out(), rows, axes=(0, 1, 3))
    return Function([pool, x, w, idx], [out])


def test_scatter_set_partition_keeps_pool_sharded():
    """Under the tensor-parallel profile each device scatters its own
    heads' rows into its own heads of the pool: no collective, the pool
    stays sharded on heads in and out, and the simulated device groups
    compute what the single-device interpreter computes."""
    from repro.core.passes import PartitionGraph, simulate_shards

    fn = _pool_scatter_fn()
    pg = PartitionGraph({"kv_heads": "model"}, {"model": 2},
                        last_dim_only=True, anywhere=("kv_heads",))
    new, stats = pg.run(fn)
    assert stats.get("all_gather", 0) == 0
    assert new.parameters[0].attrs["pspec"] == (None, None, "model", None,
                                                None)
    assert new.results[0].node.attrs["out_pspecs"][0] == \
        (None, None, "model", None, None)
    rng = np.random.default_rng(2)
    ins = [rng.normal(size=p.out_types[0].shape).astype(np.float32)
           for p in fn.parameters[:3]]
    ins.append(np.array([[0, 1, 2], [1, 4, 0], [1, 0, 3]], np.int32))
    ref = Backend.create("interpreter", fresh=True).compile(fn)(*ins)
    got = simulate_shards(new, ins, {"model": 2})
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))


def test_scatter_set_cost_counts_rows_not_pool():
    """The cost model charges the rows read and written in place and the
    indices, not the operand it leaves where it is."""
    from repro.core.cost import function_cost

    pool = ops.parameter((4, 64, 8, 8, 16), "f32", "pool")
    idx = ops.parameter((5, 3), "i32", "idx")
    rows = ops.parameter((5, 8, 16), "f32", "rows")
    fn = Function([pool, idx, rows], [ops.scatter_set(
        pool.out(), idx.out(), rows.out(), axes=(0, 1, 3))])
    c = function_cost(fn)
    assert c.flops == 0
    assert c.bytes == 5 * 3 * 4 + 2 * 5 * 8 * 16 * 4
