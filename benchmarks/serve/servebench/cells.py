"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the model as it is run (the fields of the
  program's ``ModelConfig``), the published source, ``reduced``,
  ``assumed``, the departures from the published architecture, the
  deployment it stands for, the engine fields a deployment fixes
  (``engine``: ``slots``, ``max_len``, ``pages``), and the names of two
  modules of its own: ``reference``, its plain reference beside
  ``bench.py`` (``make_weights``, ``Reference``), and ``counts``, the
  module in ``servebench/`` that counts the operations and bytes its
  work needs (:data:`COUNT_FUNCTIONS`);
* ``traffic/<mix>.json``: the parameters the one generator reads
  (:mod:`servebench.traffic`);
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``,
  ``None`` where the run holds nothing for it to read.

Adding a cell, a configuration (with its reference and count module), a
mix or a metric is adding these files and their entries in
``BENCHMARK.json``; no file that is there changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

SERVE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SERVE_DIR))
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


class CellError(Exception):
    """A name in BENCHMARK.json has no file, or a file is inconsistent."""


def add_program_path() -> str:
    """Put the system under test (``<checkout>/src``) on ``sys.path``.

    The benchmark takes nothing else from the program.  A checkout that
    holds only the benchmark has no ``src/repro`` and fails here."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise CellError(f"the system under test is missing: no "
                        f"{os.path.join(src, 'repro')}")
    if src not in sys.path:
        sys.path.insert(0, src)
    return src


def _read_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError as exc:
        raise CellError(f"missing file {path}") from exc


def benchmark() -> dict:
    return _read_json(BENCHMARK_JSON)


def workload(name: str, bench: Optional[dict] = None) -> dict:
    bench = bench if bench is not None else benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(cell: str, group: str, bench: Optional[dict] = None
                ) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
    those whose ``workloads`` list names it, or that have no list."""
    bench = bench if bench is not None else benchmark()
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_config(name: str) -> dict:
    conf = _read_json(os.path.join(SERVE_DIR, "configs", f"{name}.json"))
    if conf.get("name") != name:
        raise CellError(f"configs/{name}.json names itself "
                        f"{conf.get('name')!r}")
    return conf


def load_traffic(name: str) -> dict:
    mix = _read_json(os.path.join(SERVE_DIR, "traffic", f"{name}.json"))
    if mix.get("name") != name:
        raise CellError(f"traffic/{name}.json names itself "
                        f"{mix.get('name')!r}")
    return mix


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    published architecture ``get_config(conf["arch"])`` with the file's
    values for every field it sets.  A field that differs from the
    published one and is not in ``reduced`` is an error."""
    add_program_path()
    from repro.configs import get_config

    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {k: v for k, v in conf.items() if k in fields and k != "name"}
    for k, v in over.items():
        if getattr(base, k) != v and k not in conf["reduced"]:
            raise CellError(f"{conf['name']}: {k}={v!r} differs from the "
                            f"published {getattr(base, k)!r} and is not in "
                            f"'reduced'")
    for k in conf["reduced"]:
        if k not in over:
            raise CellError(f"{conf['name']}: reduced key {k!r} has no value")
    return dataclasses.replace(base, name=conf["name"], **over)


def load_metric_readers(names: List[str]) -> Dict[str, object]:
    """``metrics/<name>.py`` for each name, loaded by path (names hold
    dots).  A missing reader is an error, not a silent gap."""
    out = {}
    for name in names:
        path = os.path.join(SERVE_DIR, "metrics", f"{name}.py")
        if not os.path.exists(path):
            raise CellError(f"per-layer metric {name!r} has no reader {path}")
        spec = importlib.util.spec_from_file_location(
            "servebench_metric_" + name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out


# what a count module provides; each takes the configuration first
COUNT_FUNCTIONS = (
    "prefill_flops",        # (conf, start, end): prompt positions start..end-1
    "decode_token_flops",   # (conf, pos): one decoded row, input at pos
    "decode_token_bytes",   # (conf, pos): its bytes beyond the weights
    "weight_bytes",         # (conf): the weights one decode step reads
)


def counts(conf: dict):
    """The configuration's count module, ``servebench/<conf["counts"]>.py``.
    A configuration that names none, or names a module that is missing or
    lacks one of :data:`COUNT_FUNCTIONS`, is an error: no configuration
    is counted as dense by default."""
    name = conf.get("counts")
    if not isinstance(name, str) or not name.isidentifier():
        raise CellError(f"{conf.get('name')}: names no count module "
                        f"('counts': {name!r})")
    path = os.path.join(SERVE_DIR, "servebench", f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"{conf.get('name')}: count module {name!r} has no "
                        f"file {path}")
    mod = importlib.import_module(f"{__package__}.{name}")
    missing = [f for f in COUNT_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise CellError(f"{conf.get('name')}: count module {name!r} lacks "
                        f"{missing}")
    return mod


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = _read_json(os.path.join(SERVE_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
