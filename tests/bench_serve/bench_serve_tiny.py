"""A tiny checkout of the serving benchmark, for runs on the CPU.

It copies ``benchmarks/serve`` into a directory, links the program's
``src/`` beside it, and writes a ``BENCHMARK.json`` with two tiny cells:
``tiny-closed`` (a dense RMSNorm/SwiGLU/MHA model, closed loop) and
``tiny-open`` (LayerNorm/GELU/MQA with tied embeddings, open loop behind
shared tenant prefixes).  Widths are cut here, which no benchmark
configuration may do: these cells only rehearse the harness.
"""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SERVE = os.path.join(REPO, "benchmarks", "serve")
TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
        "vocab": 257}


def _tiny(conf: dict, name: str, kv: int) -> dict:
    out = dict(conf, name=name, n_kv_heads=kv, **TINY)
    out["reduced"] = sorted(set(TINY) | {"n_kv_heads"})
    out["engine"] = {"slots": 4, "max_len": 256, "pages": None}
    # tiny widths, a limit of their own: the program read at most 0.0097
    # and the float8 control at least 0.073, over three seeds in each cell
    out["check"] = {"served_gap_max": 0.04}
    return out


MIXES = {
    "tiny-closed": {
        "name": "tiny-closed", "loop": "closed", "clients_per_slot": 2,
        "prompt": {"median": 24, "sigma": 0.6, "min": 4, "max": 80},
        "output": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
        "block": 8, "temperature": 0.0,
        "max_requests_per_client_second": 60.0,
        "check_requests": 4, "check_tokens_min": 10},
    "tiny-open": {
        "name": "tiny-open", "loop": "open", "rate": 4.0,
        "prefix": {"tenants": 3, "zipf_s": 1.1, "tokens": 40},
        "prompt": {"median": 12, "sigma": 0.8, "min": 2, "max": 40},
        "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 30},
        "block": 10, "temperature": 0.0,
        "check_requests": 4, "check_tokens_min": 10},
}


def _metric(name, unit, cells):
    m = {"name": name, "unit": unit, "better": "lower", "bound": 0.25,
         "source": "host_clock"}
    if cells:
        m["workloads"] = cells
    return m


def _read(*path) -> dict:
    with open(os.path.join(*path) + ".json") as fh:
        return json.load(fh)


def make(root: str, with_program: bool = True) -> str:
    """Build the checkout under ``root``; returns its bench.py."""
    dst = os.path.join(root, "benchmarks", "serve")
    shutil.copytree(SERVE, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache"))
    if with_program:
        os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    cdir = os.path.join(dst, "configs")
    dense = _tiny(_read(cdir, "deepseek-7b-l4"), "tiny-dense", 4)
    mqa = _tiny(_read(cdir, "granite-34b-code-l4"), "tiny-mqa", 1)
    for conf in (dense, mqa):
        with open(os.path.join(cdir, conf["name"] + ".json"), "w") as fh:
            json.dump(conf, fh)
    for mix in MIXES.values():
        with open(os.path.join(dst, "traffic", mix["name"] + ".json"),
                  "w") as fh:
            json.dump(mix, fh)
    bench = _read(REPO, "BENCHMARK")
    # the real benchmark's end-to-end metrics, each on the tiny cells of
    # the loop its cells run (on every tiny cell where it lists none),
    # and the time to first token, which the harness reports and the
    # open-loop cell rehearses
    loop = {w["name"]: _read(SERVE, "traffic", w["traffic"])["loop"]
            for w in bench["workloads"]}
    bench["workloads"] = [
        {"name": "tiny-closed", "config": "tiny-dense",
         "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-open", "config": "tiny-mqa",
         "traffic": "tiny-open", "chips": 1, "why": "rehearsal"}]
    e2e = []
    for m in bench["end_to_end"]:
        m = dict(m)
        if "workloads" in m:
            loops = {loop[w] for w in m["workloads"]}
            m["workloads"] = [c for c in MIXES if MIXES[c]["loop"] in loops]
        e2e.append(m)
    e2e += [_metric(n, "ms", ["tiny-open"]) for n in ("ttft_p50_ms",
                                                       "ttft_p95_ms")
            if n not in {m["name"] for m in e2e}]
    bench["end_to_end"] = e2e
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(dst, "metrics"))
                     if f.endswith(".py"))
    bench["per_layer"] = [
        _metric(n, "x", ["tiny-open" if n.endswith((".ttft", ".itl"))
                         else "tiny-closed"]) for n in readers]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return os.path.join(dst, "bench.py")


def run(root, script, *args, timeout=300):
    """Run ``script`` from the checkout ``root`` on the CPU; returns
    (returncode, stdout, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, script, *map(str, args)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=root)
    return p.returncode, p.stdout, p.stderr


def result(stdout: str):
    """The result line: the last line of standard output, or None."""
    lines = stdout.strip().splitlines()
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        return None
