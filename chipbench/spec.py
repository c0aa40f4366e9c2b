"""`BENCHMARK.json` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own:

  * a configuration: the file its `configs` entry names;
  * a traffic mix: ``chipbench/traffic/<traffic>.json``;
  * a per-layer metric: ``chipbench/metrics/<name>.py``, a reader with
    ``read(run) -> float | None``.

A later cell therefore adds files and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

from .traffic import Mix

TRAFFIC_DIR = Path("chipbench") / "traffic"
METRIC_DIR = Path("chipbench") / "metrics"


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    mix: Mix
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


class Benchmark:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.doc[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry named {name!r}")

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def mix(self, traffic: str) -> Mix:
        path = self.root / TRAFFIC_DIR / f"{traffic}.json"
        return Mix.from_dict(traffic, json.loads(path.read_text()))

    def _reports(self, metric: dict, cell: str, e2e: set[str]) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        moves = metric.get("moves")
        return moves is None or moves in e2e

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        e2e = [m for m in self.doc["end_to_end"]
               if self._reports(m, name, set())]
        names = {m["name"] for m in e2e}
        per_layer = [m for m in self.doc["per_layer"]
                     if self._reports(m, name, names)]
        return Cell(name=name, config=self.config(w["config"]),
                    mix=self.mix(w["traffic"]), chips=int(w["chips"]),
                    end_to_end=e2e, per_layer=per_layer)

    def reader(self, metric: str):
        """The `read` function of ``chipbench/metrics/<metric>.py``."""
        path = self.root / METRIC_DIR / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{re.sub(r'[^A-Za-z0-9_]', '_', metric)}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
