"""Find a cell's parts by name: everything is data under the benchmark's root.

``BENCHMARK.json`` (beside the root) names each cell's configuration and
traffic mix; the files are found from those names alone:

* ``configs/<config>.json`` — the configuration (the ``file`` of its
  ``configs`` entry), with the plain reference it names under
  ``bench/models/``;
* ``traffic/<mix>.json`` — the mix's parameters, read by
  :mod:`bench.harness.traffic`;
* ``workloads/<cell>.json`` — what belongs to the pair: the offered rate
  of an open-loop mix, and the limit of the comparison that decides
  ``correct``;
* ``metrics/<metric>.py`` — one reader per per-layer metric.

A later cell, mix, configuration or metric is a new file and a new entry;
no file that exists needs an edit.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        self.benchmark = _read(self.root.parent / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config = _read(self.root.parent / configs[self.entry["config"]]["file"])
        self.mix = _read(self.root / "traffic" / f"{self.entry['traffic']}.json")
        own = self.root / "workloads" / f"{name}.json"
        self.params = _read(own) if own.exists() else {}
        self.family = importlib.import_module(f"bench.models.{self.config['reference']}")

    def _applies(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"] if self._applies(m)]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.benchmark["per_layer"] if self._applies(m)]

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = self.root / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
