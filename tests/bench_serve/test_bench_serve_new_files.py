"""A configuration, its plain reference, its count module, a per-layer
reader and a cell enter the benchmark as new files and new entries in
``BENCHMARK.json`` alone: no file the checkout held changes, and the
harness runs the new cell, traced and untraced, with ``correct`` true.
"""
import hashlib
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_serve_tiny as tiny  # noqa: E402

SEED = 3_000_000_029
CELL = "tiny-third"

# reads the count module of the cell's configuration, which only the new
# configuration's own module marks
PROBE = '''"""Test probe: operations of the tokens decoded in the traced window,
in GFLOP, as the configuration's own count module counts them."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from servebench import cells, spans  # noqa: E402


def read(run):
    counts = cells.counts(run.conf)
    if not spans.steps(run) or getattr(counts, "MARK", None) != "third":
        return None
    return sum(counts.decode_token_flops(run.conf, len(req.prompt) + i - 1)
               for req, i in run.decode_tokens(run.trace_t0, run.trace_t1)
               ) / 1e9
'''


def _digests(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", ".jax_cache")
                   and not os.path.islink(os.path.join(d, x))]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _add_files(root):
    serve = os.path.join(root, "benchmarks", "serve")
    with open(os.path.join(serve, "configs", "tiny-dense.json")) as fh:
        conf = json.load(fh)
    conf.update(name="tiny-third", reference="reference_third",
                counts="counts_third")
    with open(os.path.join(serve, "configs", "tiny-third.json"), "w") as fh:
        json.dump(conf, fh)
    shutil.copy(os.path.join(serve, "reference.py"),
                os.path.join(serve, "reference_third.py"))
    with open(os.path.join(serve, "reference_third.py"), "a") as fh:
        fh.write('\nimport sys as _sys\n'
                 '_sys.stderr.write("reference_third in use\\n")\n')
    shutil.copy(os.path.join(serve, "servebench", "flops.py"),
                os.path.join(serve, "servebench", "counts_third.py"))
    with open(os.path.join(serve, "servebench", "counts_third.py"),
              "a") as fh:
        fh.write('\nMARK = "third"\n')
    with open(os.path.join(serve, "metrics", "third_probe.tok.py"),
              "w") as fh:
        fh.write(PROBE)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "tiny-third", "source": "https://arxiv.org/abs/2401.02954",
        "file": "benchmarks/serve/configs/tiny-third.json",
        "reduced": conf["reduced"], "why": "rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-third", "traffic": "tiny-closed",
        "chips": 1, "why": "rehearsal"})
    bench["per_layer"].append({
        "name": "third_probe.tok", "unit": "GFLOP", "better": "higher",
        "source": "program_span", "layer": "test", "moves": "tok_s",
        "workloads": [CELL]})
    with open(path, "w") as fh:
        json.dump(bench, fh)


def test_new_configuration_and_reader_enter_as_files(tmp_path):
    root = str(tmp_path)
    bench = tiny.make(root)
    before = _digests(root)
    _add_files(root)
    results = {}
    for trace in (0, 1):
        rc, out, err = tiny.run(root, bench, "--workload", CELL, "--seed",
                                SEED + trace, "--seconds", 2, "--trace",
                                trace, "--platform", "cpu")
        res = tiny.result(out)
        assert rc == 0 and res is not None, err[-3000:]
        assert res["correct"] is True, err[-3000:]
        assert "reference_third in use" in err
        results[trace] = res["metrics"]
    assert results[0]["tok_s"]["value"] > 0
    assert set(results[1]) == {"third_probe.tok"}
    probe = results[1]["third_probe.tok"]
    assert probe["unit"] == "GFLOP" and probe["value"] > 0
    after = _digests(root)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"BENCHMARK.json"}
