"""What a run measures, read from ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's file is ``configs[].file``; the traffic mix is
``bench/traffic/<traffic>.json``; each metric is read by
``bench/metrics/<metric name>.py``.  Nothing here knows a cell by name, so
a later change adds a cell, a mix or a metric by adding files only.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: tuple  # Metric entries this cell reports with --trace 0
    per_layer: tuple  # Metric entries this cell reports with --trace 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries: List[dict]) -> List[Metric]:
    return [Metric(e["name"], e["unit"], e["better"], e["source"],
                   tuple(e["workloads"]) if "workloads" in e else None)
            for e in entries]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in _metrics(spec["end_to_end"]) if m.applies_to(name)]
    layer = [m for m in _metrics(spec["per_layer"]) if m.applies_to(name)]
    return Cell(name, int(w["chips"]), config, traffic, tuple(e2e),
                tuple(layer))
