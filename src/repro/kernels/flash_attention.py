"""Pallas TPU flash attention (the compound-kernel the Attention IR op
selects on the TPU backend — the MKL-DNN-analogue for attention).

TPU-native adaptation of the flash algorithm (paper's GPU kernels have no
warp/SM analogue here):

  * grid = (B, Hq, Sq/bq, Skv/bk) with the KV dimension innermost and
    ``dimension_semantics=("parallel","parallel","parallel","arbitrary")``
    so the output tile stays resident in VMEM across the KV sweep;
  * per-(b,h,q-block) running max / sum / accumulator live in VMEM
    scratch shaped (bq, 128) / (bq, Dv) — lane-replicated the way the
    official TPU flash kernel does it, so the VPU reductions stay on the
    128-wide lane axis;
  * GQA is free: the k/v BlockSpec index_map maps query head h to kv head
    h // (Hq // Hkv), so no head-repeat materialization;
  * causal/window masking is positional (q_offset supports decode with a
    prefilled cache);  blocks entirely outside the mask are skipped via
    ``pl.when`` (no MXU work, no accumulator update);
  * Dv may differ from Dk (MLA-style latent attention).

Block shapes default to (bq, bk) = (256, 512) with Dk/Dv up to 256:
q-tile 256x256xf32 (256 KB) + k/v tiles 512x256 (512 KB) + acc (256 KB)
stays well under the ~16 MiB VMEM budget and all MXU dims are multiples
of 128.  Validated in interpret mode against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_interpret

NEG_INF = -1e30
_LANES = 128


def _flash_kernel(off_ref,  # scalar prefetch: (1,) i32 q position offset
                  q_ref, k_ref, v_ref,  # (1,1,bq,Dk), (1,1,bk,Dk), (1,1,bk,Dv)
                  o_ref,  # (1,1,bq,Dv)
                  m_ref, l_ref, acc_ref,  # VMEM scratch
                  *, scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this block's queries / keys
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + off_ref[0]
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: the whole kv block is after every query (causal),
    # or before every query's window
    q_first = qi * bq + off_ref[0]
    q_last = q_first + bq - 1
    k_first = ki * bk
    k_last = k_first + bk - 1
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_first <= q_last)
    if window is not None:
        run = jnp.logical_and(run, k_last > q_first - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m_new == NEG_INF): exp(NEG_INF-NEG_INF)=1
        # would pollute l; rescale with 0 instead.
        row_dead = m_new <= NEG_INF / 2
        p = jnp.exp(s - jnp.where(row_dead, 0.0, m_new))
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.where(row_dead, 0.0, m_prev - m_new))
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, Dk)
    k: jax.Array,  # (B, Hkv, Skv, Dk)
    v: jax.Array,  # (B, Hkv, Skv, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: Optional[jax.Array] = None,
    bq: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, Dk = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (Dk ** 0.5)
    rep = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    if Sq % bq or Skv % bk:
        raise ValueError(f"Sq={Sq} % bq={bq} or Skv={Skv} % bk={bk} != 0")
    n_k = Skv // bk
    off = jnp.zeros((1,), jnp.int32) if q_offset is None else \
        jnp.reshape(q_offset, (1,)).astype(jnp.int32)

    grid = (B, Hq, Sq // bq, n_k)
    kernel = functools.partial(
        _flash_kernel, scale=float(scale), causal=causal, window=window,
        bq=bq, bk=bk, n_k=n_k)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index_maps receive (*grid_indices, *scalar_prefetch_refs)
                pl.BlockSpec((1, 1, bq, Dk),
                             lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, Dk),
                             lambda b, h, i, j, *_, rep=rep: (b, h // rep, j, 0)),
                pl.BlockSpec((1, 1, bk, Dv),
                             lambda b, h, i, j, *_, rep=rep: (b, h // rep, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, Dv),
                                   lambda b, h, i, j, *_: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        interpret=check_interpret(interpret),
        **kw,
    )(off, q, k, v)
