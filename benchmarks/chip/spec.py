"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout's root
names the cells, their configuration files and traffic mixes, and the
metrics; a traffic mix is ``traffic/<name>.json`` and a metric's reader is
``metrics/<name>.py``, both in this directory of the checkout.  Adding a
configuration, a traffic mix or a metric adds files and entries and edits
no code.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path("benchmarks") / "chip"


class Cell:
    """One workload of ``BENCHMARK.json``, with everything it needs."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = self.workload["chips"]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = json.loads(
            (self.root / configs[self.workload["config"]]["file"]).read_text())
        self.traffic = json.loads(
            (self.root / BENCH_DIR / "traffic"
             / f"{self.workload['traffic']}.json").read_text())
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if self._has(m) and m["moves"] in reported]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def metrics(self, trace: bool) -> list[dict]:
        """The metrics a run prints: per-layer with a trace, else
        end-to-end."""
        return self.per_layer if trace else self.end_to_end

    def reader(self, metric: str) -> ModuleType:
        """The module ``metrics/<metric>.py``; it defines ``read(ctx)``."""
        path = self.root / BENCH_DIR / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"_bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def read_metrics(self, trace: bool, ctx: dict) -> dict:
        """``{name: {"value", "unit"}}`` for every metric whose reader found
        something to read."""
        out = {}
        for m in self.metrics(trace):
            value = self.reader(m["name"]).read(ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
