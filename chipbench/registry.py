"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A configuration is the JSON file its entry names; a traffic mix is
``traffic/<name>.json``; a per-layer metric's reader is
``metrics/<name>.py``; the limits of a cell's correctness check are
``limits/<workload>.json``.  Adding a cell means adding files and entries,
never editing this module.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(RuntimeError):
    """A cell cannot be run as named (a missing file or entry)."""


def _read_json(path: pathlib.Path) -> Dict:
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    with path.open() as f:
        return json.load(f)


def benchmark() -> Dict:
    return _read_json(ROOT / "BENCHMARK.json")


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            conf = _read_json(ROOT / c["file"])
            conf["_name"] = name
            return conf
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    mix = _read_json(HERE / "traffic" / f"{name}.json")
    mix["_name"] = name
    return mix


def limits(workload_name: str) -> Dict:
    return _read_json(HERE / "limits" / f"{workload_name}.json")


def metrics_for(bench: Dict, workload_name: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those that list it under ``workloads``, or list no cells at all."""
    return [m for m in bench[kind]
            if workload_name in m.get("workloads", [workload_name])]


def metric_reader(name: str):
    """The module ``metrics/<name>.py``; its ``read(ctx)`` returns the
    metric's value or None where the run holds nothing to read."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing reader {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: Dict):
    """The plain reference module the configuration names."""
    name: Optional[str] = conf.get("reference")
    if not name or not (HERE / "reference" / f"{name}.py").is_file():
        raise BenchError(f"configuration {conf.get('_name')!r} names no "
                         f"reference module (got {name!r})")
    return importlib.import_module(f"chipbench.reference.{name}")
