"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles in
ref.py, executed in interpret mode on CPU."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops as kops
from repro.kernels.ref import attention_ref, matmul_ref, rmsnorm_ref
from repro.kernels.xla_attention import chunked_attention

RNG = np.random.default_rng(5)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("rows,d", [(8, 128), (32, 256), (64, 512), (8, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    x = jnp.asarray(RNG.normal(size=(rows, d)), dtype)
    w = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    assert kops.rmsnorm_supported(x.shape)
    out = kops.rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rmsnorm_ref(x, w), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (384, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    a = jnp.asarray(RNG.normal(size=(m, k)), dtype)
    b = jnp.asarray(RNG.normal(size=(k, n)), dtype)
    out = kops.matmul(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(matmul_ref(a, b), np.float32),
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, Dk, Dv, causal, window, offset
    (1, 2, 2, 128, 128, 128, 128, True, None, None),
    (2, 4, 2, 256, 256, 128, 128, True, None, None),
    (1, 2, 1, 128, 512, 128, 128, True, None, 384),   # decode-with-cache
    (1, 4, 4, 256, 256, 128, 128, True, 64, None),    # sliding window
    (1, 2, 2, 128, 128, 128, 256, False, None, None),  # Dv != Dk, bidir
    (1, 8, 1, 128, 256, 128, 128, True, 100, 128),    # MQA + window + offset
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Hq, Hkv, Sq, Skv, Dk, Dv, causal, window, offset = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, Dk)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, Dk)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, Dv)), dtype)
    off = None if offset is None else jnp.int32(offset)
    assert kops.attention_supported(q.shape, k.shape)
    out = kops.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=off, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", ATTN_CASES)
def test_chunked_attention_sweep(case):
    """The XLA (dry-run) realization must match the oracle too."""
    B, Hq, Hkv, Sq, Skv, Dk, Dv, causal, window, offset = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, Dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, Dv)), jnp.float32)
    off = None if offset is None else jnp.int32(offset)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_offset=off, bk=128)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)


def test_fully_masked_rows_are_zero():
    """Window smaller than the gap: some rows see no keys at all."""
    q = jnp.asarray(RNG.normal(size=(1, 1, 128, 128)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1, 128, 128)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 1, 128, 128)), jnp.float32)
    # q_offset far beyond Skv + window=1: every row fully masked
    out = kops.flash_attention(q, k, v, causal=True, window=1,
                               q_offset=jnp.int32(4096), interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_kernel_selection_predicates():
    # Skv=1000 has no 128-aligned tiling -> falls back to generic emission
    assert not kops.attention_supported((2, 4, 512, 128), (2, 2, 1000, 128))
    assert not kops.attention_supported((2, 4, 128, 96), (2, 2, 128, 96))
    assert kops.attention_supported((1, 1, 128, 128), (1, 1, 896, 128))
    assert not kops.rmsnorm_supported((7, 100))
    assert kops.rmsnorm_supported((16, 256))


# -- fused compound kernels (PR 7) --------------------------------------------
from repro.kernels.ref import norm_matmul_ref, rotary_qkv_ref, swiglu_ref


@pytest.mark.parametrize("m,d,f,do", [(64, 128, 256, 128),
                                      (128, 256, 512, 256),
                                      (8, 128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_sweep(m, d, f, do, dtype):
    x = jnp.asarray(RNG.normal(size=(m, d)) * 0.1, dtype)
    wg = jnp.asarray(RNG.normal(size=(d, f)) * 0.05, dtype)
    wu = jnp.asarray(RNG.normal(size=(d, f)) * 0.05, dtype)
    wd = jnp.asarray(RNG.normal(size=(f, do)) * 0.05, dtype)
    assert kops.swiglu_supported(m, d, f, do)
    out = kops.swiglu(x, wg, wu, wd, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(swiglu_ref(x, wg, wu, wd),
                                          np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("m,d,n", [(64, 128, 128), (128, 256, 384),
                                   (8, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_norm_matmul_sweep(m, d, n, dtype):
    x = jnp.asarray(RNG.normal(size=(m, d)), dtype)
    g = jnp.asarray(RNG.normal(size=(d,)) * 0.1 + 1.0, dtype)
    w = jnp.asarray(RNG.normal(size=(d, n)) * 0.05, dtype)
    assert kops.norm_matmul_supported(m, d, n)
    out = kops.norm_matmul(x, g, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(norm_matmul_ref(x, g, w),
                                          np.float32),
                               **_tol(dtype))


def test_rotary_qkv_ref_matches_unfused_composition():
    """The compound oracle must equal project -> split-heads -> rope."""
    B, S, D, H = 2, 16, 64, 4
    Dh = D // H
    x = jnp.asarray(RNG.normal(size=(B, S, D)) * 0.3, jnp.float32)
    wq = jnp.asarray(RNG.normal(size=(D, D)) * 0.1, jnp.float32)
    wk = jnp.asarray(RNG.normal(size=(D, 2 * Dh)) * 0.1, jnp.float32)
    wv = jnp.asarray(RNG.normal(size=(D, 2 * Dh)) * 0.1, jnp.float32)
    ang = np.arange(S)[:, None] / (10_000.0 ** (np.arange(Dh // 2) / Dh))
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    q, k, v = rotary_qkv_ref(x, wq, wk, wv, cos, sin, n_heads=H, n_kv=2)

    def split(y, h):
        return y.reshape(B, S, h, Dh).transpose(0, 2, 1, 3)

    def rope(t):
        x1, x2 = t[..., :Dh // 2], t[..., Dh // 2:]
        c, s = cos[None, None], sin[None, None]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    np.testing.assert_allclose(np.asarray(q), np.asarray(
        rope(split(jnp.dot(x, wq), H))), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), np.asarray(
        rope(split(jnp.dot(x, wk), 2))), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(
        split(jnp.dot(x, wv), 2)), atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(7, 100, 33), (130, 128, 128),
                                   (128, 200, 128)])
def test_matmul_odd_shapes_fall_back_instead_of_asserting(m, k, n):
    """Non-tile-multiple shapes lower to the XLA reference — autotune
    sweeps over odd shapes must never crash a candidate."""
    from repro.kernels.matmul import matmul as raw_matmul
    a = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    out = raw_matmul(a, b, bm=8, bn=128, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_ref(a, b)),
                               atol=1e-4, rtol=1e-4)


def test_fused_kernels_odd_shapes_fall_back():
    from repro.kernels.swiglu import swiglu as raw_swiglu
    from repro.kernels.norm_matmul import norm_matmul as raw_norm_matmul
    x = jnp.asarray(RNG.normal(size=(7, 96)) * 0.1, jnp.float32)
    wg = jnp.asarray(RNG.normal(size=(96, 100)) * 0.05, jnp.float32)
    wu = jnp.asarray(RNG.normal(size=(96, 100)) * 0.05, jnp.float32)
    wd = jnp.asarray(RNG.normal(size=(100, 48)) * 0.05, jnp.float32)
    out = raw_swiglu(x, wg, wu, wd, bm=8, bn=128, bf=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(swiglu_ref(x, wg, wu, wd)),
                               atol=1e-5, rtol=1e-4)
    g = jnp.asarray(RNG.normal(size=(96,)) * 0.1 + 1.0, jnp.float32)
    w = jnp.asarray(RNG.normal(size=(96, 33)) * 0.05, jnp.float32)
    out = raw_norm_matmul(x, g, w, bm=8, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(norm_matmul_ref(x, g, w)),
                               atol=1e-5, rtol=1e-4)


def test_fused_kernel_selection_predicates():
    assert kops.swiglu_supported(128, 256, 512, 256)
    assert not kops.swiglu_supported(7, 256, 512, 256)    # rows not 8-aligned
    assert not kops.swiglu_supported(128, 100, 512, 256)  # D not lane-aligned
    # a row block this wide cannot fit scoped VMEM at any tile: refused,
    # so emission takes the XLA path by rule
    assert not kops.swiglu_supported(8, 65536, 128, 128)
    assert kops.norm_matmul_supported(8, 128, 384)
    assert not kops.norm_matmul_supported(8, 384, 100)


def test_interpret_mode_is_refused_on_a_tpu(monkeypatch):
    """On a TPU a kernel runs compiled or not at all; interpret mode is
    honoured only where no TPU is the default backend."""
    import jax
    from repro.kernels import check_interpret
    assert check_interpret(True) is True          # CPU: asked for by name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert check_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        check_interpret(True)
