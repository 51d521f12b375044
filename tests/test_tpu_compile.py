"""Compile the main-path Pallas kernels at deepseek-7b widths for a
described TPU v5e, without a chip attached.

The TPU compiler refuses what interpret mode accepts: tiles the Mosaic
lowering cannot verify, and working sets beyond the scoped VMEM limit.
Each case lowers one kernel for one chip of a ``v5e:2x2`` topology and
checks that the compiled module holds the kernel (``tpu_custom_call``).
Nothing runs, so these say nothing about results or times.

The topology is described inside module-scoped fixtures, never while the
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops as kops

D_MODEL, D_FF, N_HEADS, D_HEAD = 4096, 11008, 32, 128   # deepseek-7b


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip's sharding, with the persistent compilation cache off: a
    compile for a described chip is written there but can never be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes_dtypes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel was not in the compiled module"
    return compiled


BF16 = jnp.bfloat16


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, w: kops.rmsnorm(x, w, interpret=False), one_chip,
             ((512, D_MODEL), BF16), ((D_MODEL,), BF16))


def test_matmul_compiles(one_chip):
    _compile(lambda a, b: kops.matmul(a, b, interpret=False), one_chip,
             ((512, D_MODEL), BF16), ((D_MODEL, D_MODEL), BF16))


def test_norm_matmul_compiles(one_chip):
    _compile(lambda x, g, w: kops.norm_matmul(x, g, w, interpret=False),
             one_chip, ((256, D_MODEL), BF16), ((D_MODEL,), BF16),
             ((D_MODEL, 3 * D_MODEL), BF16))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_swiglu_compiles(one_chip, dtype):
    """bf16 once failed Mosaic verification (a bf16 sigmoid); f32 once
    overflowed scoped VMEM with double-buffered full-width blocks."""
    assert kops.swiglu_supported(256, D_MODEL, D_FF, D_MODEL,
                                 jnp.dtype(dtype).itemsize)
    _compile(lambda x, g, u, d: kops.swiglu(x, g, u, d, interpret=False),
             one_chip, ((256, D_MODEL), dtype), ((D_MODEL, D_FF), dtype),
             ((D_MODEL, D_FF), dtype), ((D_FF, D_MODEL), dtype))


def test_flash_attention_compiles(one_chip):
    qkv = ((1, N_HEADS, 2048, D_HEAD), BF16)
    _compile(lambda q, k, v: kops.flash_attention(q, k, v, causal=True,
                                                  interpret=False),
             one_chip, qkv, qkv, qkv)
