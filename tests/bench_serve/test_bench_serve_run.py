"""The serving benchmark run end to end at a tiny size on the CPU.

A run measures on a TPU only: it must refuse the CPU unless the caller
asks for it by name (``--platform cpu``), and refuse a checkout that
holds the benchmark without the program.  With a token altered where
the engine makes it, ``correct`` must come out false.
"""
import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_serve_tiny as tiny  # noqa: E402

SEED = 3_000_000_019


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    return root, tiny.make(root)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny-closed", {"tok_s", "itl_p95_ms", "setup_s"}),
    ("tiny-open", {"tok_s", "itl_p95_ms", "ttft_p50_ms", "ttft_p95_ms",
                   "setup_s"})])
def test_cpu_rehearsal(checkout, cell, e2e):
    root, bench = checkout
    rc, out, err = tiny.run(root, bench, "--workload", cell, "--seed", SEED,
                            "--seconds", 2, "--trace", 0, "--platform", "cpu")
    res = tiny.result(out)
    assert rc == 0 and res is not None, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == e2e
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert "check served_gap_max" in err
    assert "lateness" in out


def test_cpu_traced_rehearsal(checkout):
    """A traced run off the TPU reduces no device trace and reads no
    peaks; the per-layer metrics that the engine's gauges and the
    program's host spans give are there, the device's are not."""
    root, bench = checkout
    rc, out, err = tiny.run(root, bench, "--workload", "tiny-closed", "--seed",
                            SEED + 1, "--seconds", 2, "--trace", 1,
                            "--platform", "cpu")
    res = tiny.result(out)
    assert rc == 0 and res is not None, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    got = res["metrics"]
    assert {"decode_rows_mean.tok", "step_host_ms.tok", "dispatch_ms.tok",
            "front_door_ms.tok"} <= set(got)
    assert not {"decode_chunk_ms.tok", "decode_roofline.tok", "mfu.tok",
                "idle_share.tok"} & set(got)
    assert all(got[n]["value"] > 0 and got[n]["unit"] == "x"
               for n in ("step_host_ms.tok", "dispatch_ms.tok",
                         "front_door_ms.tok"))
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_control_run_is_not_correct(checkout):
    """``control.py`` judges the program first (correct), then the float8
    control by the same checks, whose result line comes last: not
    correct, its ``served_gap_max`` over the limit."""
    root, bench = checkout
    control = os.path.join(os.path.dirname(bench), "control.py")
    rc, out, err = tiny.run(root, control, "--workload", "tiny-closed",
                            "--seed", SEED, "--seconds", 2, "--trace", 0,
                            "--platform", "cpu")
    assert rc == 0, err[-3000:]
    lines = [json.loads(x) for x in out.strip().splitlines()
             if x.startswith("{")]
    program, ctl = lines[-2], lines[-1]
    assert program["correct"] is True, err[-3000:]
    assert ctl["correct"] is False
    c = ctl["checks"]["served_gap_max"]
    assert c["value"] > c["limit"] and c["ok"] is False
    assert "control check served_gap_max" in err.strip().splitlines()[-5]


def test_refuses_the_cpu_unless_asked(checkout):
    root, bench = checkout
    rc, out, _ = tiny.run(root, bench, "--workload", "tiny-closed", "--seed",
                          1, "--seconds", 1, "--trace", 0)
    assert rc != 0
    assert tiny.result(out) is None


def test_refuses_a_checkout_without_the_program(tmp_path):
    bench = tiny.make(str(tmp_path), with_program=False)
    rc, out, _ = tiny.run(str(tmp_path), bench, "--workload", "tiny-closed",
                          "--seed", 1, "--seconds", 1, "--trace", 0,
                          "--platform", "cpu")
    assert rc != 0
    assert tiny.result(out) is None


FAULTS = {
    # the first token, sampled on the host at the end of prefill
    "first-token": """
        import repro.launch.engine as E
        orig = E._host_sample
        def bad(logits, *a):
            return (orig(logits, *a) + 1) % np.asarray(logits).size
        E._host_sample = bad
    """,
    # every token of the decode chunk, as the program returns it
    "decode-token": """
        build = harness.build_engine
        def build_bad(*a, **kw):
            eng = build(*a, **kw)
            raw, V = eng.cf.raw, eng.cfg.vocab
            def bad_raw(*x):
                outs = raw(*x)
                return [(outs[0] + 1) % V] + list(outs[1:])
            eng.cf._raw = bad_raw
            return eng
        harness.build_engine = build_bad
    """,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_token_is_not_correct(checkout, fault):
    root, bench = checkout
    script = os.path.join(root, f"fault_{fault}.py")
    with open(script, "w") as fh:
        fh.write(textwrap.dedent("""
            import os, sys
            import numpy as np
            sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks", "serve"))
            import bench
            from servebench import cells, harness
            cells.add_program_path()
        """) + textwrap.dedent(FAULTS[fault]) + textwrap.dedent("""
            sys.exit(bench.main(sys.argv[1:]))
        """))
    rc, out, err = tiny.run(root, script, "--workload", "tiny-closed",
                            "--seed", SEED, "--seconds", 2, "--trace", 0,
                            "--platform", "cpu")
    res = tiny.result(out)
    assert rc == 0 and res is not None, err[-3000:]
    assert res["correct"] is False
    c = res["checks"]["served_gap_max"]
    assert c["value"] > c["limit"] and c["ok"] is False
