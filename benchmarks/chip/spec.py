"""Everything a run looks up by name, from ``BENCHMARK.json`` and the
benchmark's data files: a cell's configuration, traffic mix, settings
and metric readers.  A new cell, configuration, mix or metric is a new
file and a new entry; nothing here changes."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

from benchmarks.chip import traffic

PKG = "benchmarks/chip"


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict          # the configuration as run
    mix: dict             # the traffic mix
    settings: dict        # cells/<name>.json: engine sizes, rate, limits
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        path = self.root / PKG / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"_bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = traffic.load_mix(root / PKG / "traffic" / f"{w['traffic']}.json")
    settings = json.loads(
        (root / PKG / "cells" / f"{workload}.json").read_text())
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, mix=mix, settings=settings,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])
