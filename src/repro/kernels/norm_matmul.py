"""Pallas TPU fused RMSNorm+matmul kernel.

Computes ``matmul(rms_norm(x, g, eps), w)`` — the pre-attention / pre-MLP
projection pattern.  Grid (M/bm, N/bn): each instance keeps a full-width
(bm, D) row tile of x in VMEM, normalizes it in f32 on the VPU, and
contracts the normalized rows against the (D, bn) weight column block on
the MXU.  The normalized activation is recomputed per N block instead of
round-tripping through HBM (D reads beat D writes + D reads; whether
that wins on a given shape is the autotuner's call via the
``fuse_norm_matmul`` knob).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import check_interpret


def _norm_matmul_kernel(x_ref, g_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    nrm = (x * jax.lax.rsqrt(var + eps) * g[None, :]).astype(x_ref.dtype)
    o_ref[...] = jnp.dot(nrm, w_ref[...],
                         preferred_element_type=jnp.float32).astype(
                             o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "bm", "bn", "interpret"))
def norm_matmul(x: jax.Array, g: jax.Array, w: jax.Array,
                eps: float = 1e-6, bm: int = 128, bn: int = 256,
                interpret: bool = False) -> jax.Array:
    """x: (M, D); g: (D,); w: (D, N) -> (M, N)."""
    M, D = x.shape
    N = w.shape[1]
    bm, bn = min(bm, M), min(bn, N)
    if M % bm or N % bn:
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        nrm = (xf * jax.lax.rsqrt(var + eps)
               * g.astype(jnp.float32)).astype(x.dtype)
        return jnp.dot(nrm, w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return pl.pallas_call(
        functools.partial(_norm_matmul_kernel, eps=eps),
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D,), lambda i, j: (0,)),
            pl.BlockSpec((D, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=check_interpret(interpret),
    )(x, g, w)
