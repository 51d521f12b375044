"""Pallas TPU tiled matmul.

Canonical MXU tiling: grid (M/bm, N/bn, K/bk) with the K dimension
innermost ("arbitrary" semantics) accumulating f32 partials straight into
the output tile, which stays resident in VMEM across the K sweep (its
index_map ignores the k grid index).  All tile dims are multiples of 128
to match the 128x128 systolic array; inputs feed the MXU in bf16 with f32
accumulation (``preferred_element_type``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_interpret


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, bm: int = 256, bn: int = 256,
           bk: int = 512, interpret: bool = False) -> jax.Array:
    """a: (M, K) @ b: (K, N) -> (M, N); tile dims must divide shapes."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        # No legal tiling for this shape: lower to the XLA dot (same
        # f32-accumulate numerics) instead of failing the compile —
        # autotune sweeps over odd shapes must never crash a candidate.
        return jnp.dot(a, b,
                       preferred_element_type=jnp.float32).astype(a.dtype)
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        interpret=check_interpret(interpret),
    )(a, b)
