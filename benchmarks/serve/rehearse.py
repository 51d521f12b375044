"""Compile a configuration's serving programs for a described TPU v5e.

Builds the paged engine's decode-chunk program and its widest
prefill-chunk program at the sizes a configuration file fixes (model,
``slots``, ``max_len``, ``pages``; page size, chunk steps and prefill
chunk at the engine's defaults), compiles both with the TPU compiler for
one chip of a ``v5e:2x2`` topology that is described, not attached, and
prints ``memory_analysis()`` of each as JSON.  Nothing runs and no time
is measured: this says whether the programs fit one chip's 16 GB.

Run on a machine without the chip, from the repository root:

    JAX_PLATFORMS=cpu python3 benchmarks/serve/rehearse.py deepseek-7b-l4 \
        [--slots 8] [--max-len 4096] [--pages N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from servebench import cells  # noqa: E402


def _analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def rehearse(name: str, slots=None, max_len=None, pages=None) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cells.add_program_path()
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.backend import Backend
    from repro.launch.engine import EngineConfig
    from repro.models.lm import build_dense_chunk, build_dense_paged_prefill

    jax.config.update("jax_enable_compilation_cache", False)
    conf = cells.load_config(name)
    cfg = cells.model_config(conf)
    eng = dict(conf["engine"])
    for k, v in (("slots", slots), ("max_len", max_len), ("pages", pages)):
        if v is not None:
            eng[k] = v
    ec = EngineConfig(mode="paged", slots=eng["slots"],
                      max_len=eng["max_len"], pages=eng.get("pages"))
    # the engine's defaults (launch/engine.py): page 8, 4 steps, 4 pages
    page_size, steps = 8, 4
    chunk = 4 * page_size
    mp = -(-ec.max_len // page_size)
    n_pages = ec.pages if ec.pages is not None else 1 + ec.slots * mp
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    backend = Backend.create("jax")
    out = {"config": name, "slots": ec.slots, "max_len": ec.max_len,
           "pages": n_pages, "page_size": page_size, "chunk_steps": steps,
           "prefill_chunk": chunk}

    def compile_graph(g, step_inputs):
        b = g.builder
        donate = tuple(i for i, n in enumerate(b.inputs)
                       if n.name not in step_inputs)
        from repro.backend import CompileOptions
        cf = backend.compile(g.fn, CompileOptions(donate_argnums=donate))
        specs = [jax.ShapeDtypeStruct(t.shape, np.dtype(t.dtype),
                                      sharding=one)
                 for t in g.fn.in_types]
        return cf.lower(*specs).compile()

    g = build_dense_chunk(cfg, ec.max_len, ec.slots, steps,
                          page_size=page_size, n_pages=n_pages)
    out["decode_chunk_program"] = _analysis(compile_graph(
        g, ("token", "pos", "page_tbl", "temperature", "top_k", "key")))
    g = build_dense_paged_prefill(cfg, ec.max_len, chunk,
                                  page_size=page_size, n_pages=n_pages)
    out["prefill_chunk_program"] = _analysis(compile_graph(
        g, ("token", "pos", "page_tbl")))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--max-len", type=int)
    ap.add_argument("--pages", type=int)
    a = ap.parse_args(argv)
    print(json.dumps(rehearse(a.config, a.slots, a.max_len, a.pages)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
