"""Collectives as core graph ops (paper claim E7): the shardmap-mode
transformer lowers IR collectives to jax.lax collectives over real
device groups.  Runs in a subprocess with 8 placeholder devices so the
main test process keeps its single-device view."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.core import ops
    from repro.core.function import Function
    from repro.backend import Backend, CompileOptions

    mesh = jax.make_mesh((4, 2), ("data", "model"))

    # shardmap mode = explicit per-device program: the IR is built on
    # LOCAL shapes (the paper's transformers emit per-device code too)
    x = ops.parameter((8, 4), "f32", "x")  # local shard of a (32, 4) array
    y_ar = ops.all_reduce(x.out(), "data")
    y_ag = ops.all_gather(x.out(), "data", axis=0, axis_size=4)
    y_rs = ops.reduce_scatter(x.out(), "data", axis=0, axis_size=4)
    y_pp = ops.send_recv(x.out(), "data", shift=1, axis_size=4)
    fn = Function([x], [y_ar, y_ag, y_rs, y_pp])

    run = Backend.create("jax").compile(
        fn, CompileOptions(mode="shardmap", static_jit=False, level="O0")).raw
    sharded = shard_map(lambda a: tuple(run(a)), mesh=mesh,
                        in_specs=P("data", None),
                        out_specs=(P(None, None), P(None, None),
                                   P("data", None), P("data", None)),
                        check_vma=False)
    arr = np.arange(128, dtype=np.float32).reshape(32, 4)
    shards = arr.reshape(4, 8, 4)
    group_sum = shards.sum(axis=0)          # (8, 4)
    with mesh:
        ar, ag, rs, pp = jax.jit(sharded)(arr)

    # all-reduce(sum) over data: every device holds the group sum
    np.testing.assert_allclose(np.asarray(ar), group_sum, rtol=1e-6)
    # all-gather: the full array everywhere
    np.testing.assert_allclose(np.asarray(ag), arr, rtol=1e-6)
    # reduce-scatter: device i holds rows [2i, 2i+2) of the sum
    np.testing.assert_allclose(np.asarray(rs), group_sum, rtol=1e-6)
    # ppermute ring shift by 1: device j holds shard j-1
    np.testing.assert_allclose(np.asarray(pp),
                               np.roll(shards, 1, axis=0).reshape(32, 4),
                               rtol=1e-6)
    print("COLLECTIVES-OK")
""")


def test_collectives_shardmap_8dev():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          cwd=__file__.rsplit("/tests/", 1)[0])
    assert "COLLECTIVES-OK" in proc.stdout, proc.stderr[-3000:]


def test_collective_type_inference():
    from repro.core import ops
    x = ops.parameter((8, 4), "f32", "x").out()
    assert ops.all_gather(x, "d", 0, 4).shape == (32, 4)
    assert ops.reduce_scatter(x, "d", 0, 4).shape == (2, 4)
    assert ops.all_to_all(x, "d", 0, 1, 4).shape == (2, 16)
    assert ops.all_reduce(x, "d").shape == (8, 4)


def test_grad_of_collectives():
    from repro.core import ops
    from repro.core.autodiff import grad
    from repro.core.function import Function
    x = ops.parameter((8, 4), "f32", "x")
    y = ops.reduce_sum(ops.all_reduce(x.out(), "data"))
    gfn = grad(Function([x], [y]))
    counts = gfn.op_counts()
    assert counts["AllReduce"] == 2  # forward + transpose rule
    y2 = ops.reduce_sum(ops.all_gather(x.out(), "data", 0, 4))
    g2 = grad(Function([x], [y2]))
    assert "ReduceScatter" in g2.op_counts()
