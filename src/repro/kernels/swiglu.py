"""Pallas TPU fused SwiGLU MLP kernel.

Computes ``matmul(silu(x @ w_gate) * (x @ w_up), w_down)`` with the gate
activation resident in VMEM: grid (M/bm, Do/bn, F/bf) with the ffn
contraction (F) innermost ("arbitrary" semantics).  Each instance holds a
full-width (bm, D) row tile of x, produces the (bm, bf) gate/up slab on
the MXU, applies silu*mul on the VPU, and accumulates the down-projection
straight into an f32 VMEM scratch tile — the (M, F) hidden activation
never exists in HBM, which is the entire point of the fusion (the
unfused emission writes and re-reads it once per token).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_interpret


def _swiglu_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                   n_f: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # f32 MXU accumulate, cast back to the input dtype between stages.
    # silu*mul runs on f32 values (Mosaic refuses the f32 constants of a
    # bf16 sigmoid: vector.broadcast element-type mismatch), so a bf16
    # kernel rounds differently from ref.py and the XLA emitter, which
    # apply silu in the input dtype.
    g = jnp.dot(x, wg_ref[...],
                preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.dot(x, wu_ref[...],
                preferred_element_type=jnp.float32).astype(x.dtype)
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    acc_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_f - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bf", "interpret"))
def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, bm: int = 128, bn: int = 256, bf: int = 256,
           interpret: bool = False) -> jax.Array:
    """x: (M, D); w_gate/w_up: (D, F); w_down: (F, Do) -> (M, Do)."""
    M, D = x.shape
    F = w_gate.shape[1]
    Do = w_down.shape[1]
    bm, bn, bf = min(bm, M), min(bn, Do), min(bf, F)
    if M % bm or Do % bn or F % bf:
        g = jnp.dot(x, w_gate,
                    preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, w_up,
                    preferred_element_type=jnp.float32).astype(x.dtype)
        h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
        return jnp.dot(h.astype(x.dtype), w_down,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    n_f = F // bf
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, n_f=n_f),
        grid=(M // bm, Do // bn, n_f),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j, f: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j, f: (0, f)),
            pl.BlockSpec((D, bf), lambda i, j, f: (0, f)),
            pl.BlockSpec((bf, bn), lambda i, j, f: (f, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, f: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, Do), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        interpret=check_interpret(interpret),
    )(x, w_gate, w_up, w_down)
