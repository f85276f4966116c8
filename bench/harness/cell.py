"""Resolve one cell of ``BENCHMARK.json`` into the files that define it.

Everything that belongs to one configuration, traffic mix, correctness
check or per-layer metric lives in a file of its own, found by the name
``BENCHMARK.json`` gives it:

- configuration: the ``file`` of its ``configs`` entry (JSON of sizes);
- architecture adapter: ``bench/adapters/<model_type>.py``, by the
  configuration's published ``model_type`` (``bench/adapters``);
- plain reference: the file the configuration's ``reference`` key names
  (``bench/reference``);
- traffic mix: ``bench/traffic/<traffic>.json``;
- correctness limit of a cell: ``bench/checks/<workload>.json``;
- per-layer metric: ``bench/metrics/<name>.py`` exposing ``read(run)``.

A new cell is therefore new files plus new entries, never an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    check: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    adapter: ModuleType
    reference: ModuleType
    readers: Dict[str, Callable[[Any], Any]] = field(default_factory=dict)

    @property
    def dims(self) -> Dict[str, Any]:
        """The adapter's ``dims(config)``, carrying the adapter itself
        under ``"adapter"``: the per-layer metrics count through it."""
        return dict(self.adapter.dims(self.config), adapter=self.adapter)


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _metric_applies(metric: Dict[str, Any], workload: str,
                    reported: List[str]) -> bool:
    """A metric with ``workloads`` lists its cells; one without it is in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


@functools.cache
def load_module(path: Path) -> ModuleType:
    """The Python file ``path`` as a module, loaded once a process, so
    that the jitted functions of a reference compile once however many
    runs share the process."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + re.sub(r"\W", "_", str(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: Path, name: str) -> Callable[[Any], Any]:
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def load_adapter(root: Path, model_type: str) -> ModuleType:
    return load_module(root / "bench" / "adapters" / f"{model_type}.py")


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``; raises KeyError
    for a name the file does not list, and FileNotFoundError, naming the
    path, where the configuration's adapter or reference is missing."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or "workloads" not in m
           or workload in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _metric_applies(m, workload, reported)]
    check_path = root / "bench" / "checks" / f"{workload}.json"
    config = _load_json(root / cfg_entry["file"])
    for key in ("model_type", "reference"):
        if key not in config:
            raise KeyError(f"{cfg_entry['file']} names no {key!r}")
    cell = Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=config,
        traffic=_load_json(root / "bench" / "traffic"
                           / f"{w['traffic']}.json"),
        check=_load_json(check_path) if check_path.exists() else {},
        end_to_end=e2e, per_layer=per_layer,
        adapter=load_adapter(root, config["model_type"]),
        reference=load_module(root / config["reference"]))
    cell.readers = {m["name"]: load_reader(root, m["name"])
                    for m in per_layer}
    return cell
