"""Find a cell's pieces by the names in `BENCHMARK.json` and its files.

- a configuration: `bench/configs/<config>.json`; its `generator` names
  `bench/generators/<generator>.py`, which draws its pools from the seed,
  and its `objective` names its plain reference,
  `bench/references/<objective>.py`;
- a traffic mix: `bench/traffic/<traffic>.json`, whose `path` names the
  path kind `bench/paths/<path>.py` (the program and the reference in its
  place, and the window that drives them);
- a metric, end to end or per layer: its reader `bench/metrics/<name>.py`.

Adding any of them takes new files and new entries in `BENCHMARK.json`,
and no edit.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import types
from typing import Dict, List


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration file, as run
    traffic: dict               # the traffic file
    end_to_end: List[dict]      # the metrics this cell reports, in order
    per_layer: List[dict]
    root: str                   # directory that holds BENCHMARK.json

    @property
    def objective(self) -> str:
        return self.config["objective"]

    @functools.cached_property
    def generator(self) -> types.ModuleType:
        name = self.config["generator"]
        return _module(os.path.join(self.root, "bench", "generators",
                                    name + ".py"), "bench_generator_" + name)

    @functools.cached_property
    def path(self) -> types.ModuleType:
        name = self.traffic["path"]
        return _module(os.path.join(self.root, "bench", "paths",
                                    name + ".py"), "bench_path_" + name)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _module(path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name, int(w["chips"]), cfg, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


def reference(root: str, objective: str) -> types.ModuleType:
    return _module(os.path.join(root, "bench", "references",
                                objective + ".py"),
                   f"bench_reference_{objective}")


def readers(cell: Cell, metrics: List[dict]) -> Dict[str, types.ModuleType]:
    """The reader of each of `metrics`, by name."""
    return {m["name"]: _module(os.path.join(cell.root, "bench", "metrics",
                                            m["name"] + ".py"),
                               "bench_metric_" + m["name"].replace(".", "_"))
            for m in metrics}
