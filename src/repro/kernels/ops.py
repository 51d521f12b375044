"""jit'd wrappers + support predicates for the Pallas kernel library.

This module is the *kernel-selection surface* the JAX transformer consults
(paper sec. 4: transformers combine "tensor-element layout and shape
management with backend kernel selection").  Each ``*_supported`` predicate
encodes the shape/alignment constraints of the corresponding TPU kernel;
unsupported shapes fall back to the transformer's generic emission.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .matmul import matmul as _matmul
from .norm_matmul import norm_matmul as _norm_matmul
from .rmsnorm import rmsnorm_fwd as _rmsnorm
from .swiglu import swiglu as _swiglu
from .xla_attention import chunked_attention  # noqa: F401  (re-export)

_SUBLANE = 8
_LANE = 128


def _pick_block(size: int, target: int, align: int) -> Optional[int]:
    """Largest divisor of ``size`` that is <= target and a multiple of
    ``align`` (or == size when size < align)."""
    if size <= align:
        return size
    b = min(target, size)
    b -= b % align
    while b >= align:
        if size % b == 0:
            return b
        b -= align
    return size if size % align == 0 or size <= align else None


# -- rmsnorm -----------------------------------------------------------------
def rmsnorm_supported(shape: Tuple[int, ...]) -> bool:
    if len(shape) < 1:
        return False
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    return d % _LANE == 0 and rows % _SUBLANE == 0


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            interpret: bool = False) -> jax.Array:
    return _rmsnorm(x, w, eps=eps, interpret=interpret)


# -- matmul --------------------------------------------------------------------
def matmul_supported(m: int, k: int, n: int) -> bool:
    return m % _LANE == 0 and k % _LANE == 0 and n % _LANE == 0


def matmul(a: jax.Array, b: jax.Array, interpret: bool = False, **kw) -> jax.Array:
    M, K = a.shape
    _, N = b.shape
    bm = _pick_block(M, kw.pop("bm", 256), _LANE) or M
    bn = _pick_block(N, kw.pop("bn", 256), _LANE) or N
    bkk = _pick_block(K, kw.pop("bk", 512), _LANE) or K
    return _matmul(a, b, bm=bm, bn=bn, bk=bkk, interpret=interpret)


# -- fused swiglu ---------------------------------------------------------------
# Scoped VMEM a kernel may take by default is 16 MiB on v5e; the fused MLP
# keeps its double-buffered working set under this budget, with headroom
# for Mosaic's own scratch.
_VMEM_BUDGET = 12 << 20


def _swiglu_vmem(bm: int, d: int, bf: int, bn: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step holds: the double-buffered x row block,
    gate/up/down weight blocks and output block, plus the f32 accumulator
    and the f32 gate/up/hidden slabs."""
    pipelined = bm * d + 2 * d * bf + bf * bn + bm * bn
    return 2 * itemsize * pipelined + 4 * (bm * bn + 3 * bm * bf)


def _swiglu_blocks(m: int, d: int, f: int, do: int, itemsize: int,
                   bm: int, bn: int, bf: int) -> Optional[Tuple[int, int, int]]:
    """Tiles near the requested ones that fit the VMEM budget: the ffn
    block shrinks first, then the row block, then the output block.  None
    when even the smallest aligned tiles do not fit."""
    bm = _pick_block(m, bm, _SUBLANE) or m
    bn = _pick_block(do, bn, _LANE) or do
    bf = _pick_block(f, bf, _LANE) or f
    while _swiglu_vmem(bm, d, bf, bn, itemsize) > _VMEM_BUDGET:
        if bf > _LANE:
            bf = _pick_block(f, bf // 2, _LANE) or _LANE
        elif bm > _SUBLANE:
            bm = _pick_block(m, bm // 2, _SUBLANE) or _SUBLANE
        elif bn > _LANE:
            bn = _pick_block(do, bn // 2, _LANE) or _LANE
        else:
            return None
    return bm, bn, bf


def swiglu_supported(m: int, d: int, f: int, do: int,
                     itemsize: int = 4) -> bool:
    """Fused MLP kernel: lane-aligned widths, sublane-aligned rows, and a
    working set that fits VMEM at the smallest tiles."""
    return (d % _LANE == 0 and f % _LANE == 0 and do % _LANE == 0
            and m % _SUBLANE == 0 and m > 0
            and _swiglu_blocks(m, d, f, do, itemsize, _SUBLANE, _LANE,
                               _LANE) is not None)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, interpret: bool = False, **kw) -> jax.Array:
    M, D = x.shape
    F = w_gate.shape[1]
    Do = w_down.shape[1]
    blocks = _swiglu_blocks(M, D, F, Do, x.dtype.itemsize,
                            kw.pop("bm", 128), kw.pop("bn", 256),
                            kw.pop("bk", 256))
    if blocks is None:
        raise ValueError(f"swiglu ({M},{D})x({D},{F})x({F},{Do}) does not "
                         f"fit VMEM; check swiglu_supported first")
    bm, bn, bf = blocks
    return _swiglu(x, w_gate, w_up, w_down, bm=bm, bn=bn, bf=bf,
                   interpret=interpret)


# -- fused norm+matmul ----------------------------------------------------------
def norm_matmul_supported(m: int, d: int, n: int) -> bool:
    return d % _LANE == 0 and n % _LANE == 0 and m % _SUBLANE == 0 and m > 0


def norm_matmul(x: jax.Array, g: jax.Array, w: jax.Array,
                eps: float = 1e-6, interpret: bool = False,
                **kw) -> jax.Array:
    M, _D = x.shape
    N = w.shape[1]
    bm = _pick_block(M, kw.pop("bm", 128), _SUBLANE) or M
    bn = _pick_block(N, kw.pop("bn", 256), _LANE) or N
    return _norm_matmul(x, g, w, eps=eps, bm=bm, bn=bn, interpret=interpret)


# -- attention ------------------------------------------------------------------
def attention_supported(q_shape: Tuple[int, ...],
                        k_shape: Tuple[int, ...]) -> bool:
    """Flash kernel constraints: 4D BHSD, Sq/Skv tileable, D lane-aligned."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, Hq, Sq, Dk = q_shape
    _, Hkv, Skv, _ = k_shape
    if Hkv == 0 or Hq % Hkv:
        return False
    if Dk % _LANE:
        return False
    bq = _pick_block(Sq, 256, _LANE)
    bk = _pick_block(Skv, 512, _LANE)
    return bq is not None and bk is not None


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    q_offset=None, interpret: bool = False) -> jax.Array:
    B, Hq, Sq, Dk = q.shape
    Skv = k.shape[2]
    bq = _pick_block(Sq, 256, _LANE) or Sq
    bk = _pick_block(Skv, 512, _LANE) or Skv
    return _flash(q, k, v, causal=causal, window=window, scale=scale,
                  q_offset=q_offset, bq=bq, bk=bk, interpret=interpret)
