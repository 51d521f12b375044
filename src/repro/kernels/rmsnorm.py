"""Pallas TPU RMSNorm kernel.

Tiling: grid over row blocks; each kernel instance holds a
(block_rows, d) tile of x plus the full (d,) weight in VMEM, computes the
row-wise rms in f32 on the VPU, and writes the normalized tile.  d is the
minor (lane) dimension so the reduction is over the 128-wide lane axis;
block_rows is sized so the tile stays well under VMEM (~2 MiB budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import check_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w[None, :]).astype(o_ref.dtype)


def pick_block_rows(n_rows: int, d: int, budget_bytes: int = 2 << 20) -> int:
    """Largest power-of-two row block (>=8 sublanes) fitting the budget."""
    rows = max(budget_bytes // max(d * 4, 1), 8)
    rows = 1 << (rows.bit_length() - 1)
    while rows > 8 and n_rows % rows:
        rows //= 2
    return rows


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_fwd(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                block_rows: int = 0, interpret: bool = False) -> jax.Array:
    """x: (..., d) flattened to rows; w: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    br = block_rows or pick_block_rows(rows, d)
    if rows % br:
        raise ValueError(f"rows {rows} not divisible by block {br}")
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=check_interpret(interpret),
    )(x2, w)
    return out.reshape(orig_shape)
