"""Finds what a cell needs by the names in ``BENCHMARK.json``.

A configuration is ``configs/<name>.json``, a traffic mix or training job
``traffic/<name>.json``, the limits of a cell's correctness check
``limits/<cell>.json``, a per-layer metric's reader ``metrics/<name>.py``
and the runner of a traffic kind ``runners/<kind>.py``. Adding a cell,
configuration, mix or metric is adding such files and entries; no file
here changes."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


def _json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_manifest(path: pathlib.Path = MANIFEST) -> Dict[str, Any]:
    return _json(path)


def cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _json(ROOT / config_entry(manifest, name)["file"])


def load_traffic(name: str) -> Dict[str, Any]:
    return _json(BENCH / "traffic" / f"{name}.json")


def load_limits(cell_name: str) -> Dict[str, Any]:
    return _json(BENCH / "limits" / f"{cell_name}.json")


def _load_module(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind: str):
    """The module that runs every traffic mix of ``kind``."""
    return _load_module(BENCH / "runners" / f"{kind}.py",
                        f"bench_runner_{kind}")


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``metrics/<name>.py``'s ``read(record)``: the metric's value, or
    None where the run holds nothing for it to read."""
    mod = _load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read


def metrics_of(manifest: Dict[str, Any], cell_name: str,
               kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it under ``workloads``, or that list no cells at all."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
