"""Everything the harness finds by name: the cell, its configuration, its
traffic mix and its metrics, all from ``BENCHMARK.json`` and the files beside
it.  Adding a cell or a metric adds files and entries; nothing here changes."""

from __future__ import annotations

import importlib.util
import json
from functools import lru_cache
from pathlib import Path

__all__ = ["ROOT", "HERE", "Bench"]

HERE = Path(__file__).resolve().parents[1]      # perfbench/
ROOT = HERE.parent                              # root of the checkout


class Bench:
    """``BENCHMARK.json`` and the lookups by name that it drives."""

    def __init__(self, root: Path = ROOT, spec: dict | None = None,
                 traffic_dir: Path | None = None):
        self.root = Path(root)
        self.spec = spec if spec is not None else json.loads(
            (self.root / "BENCHMARK.json").read_text())
        self.traffic_dir = Path(traffic_dir or self.root / "perfbench" / "traffic")

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.traffic_dir / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``perfbench/metrics/<metric>.py``."""
        return _load_reader(str(self.root / "perfbench" / "metrics" / f"{metric}.py"))


@lru_cache(maxsize=None)
def _load_reader(path: str):
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + Path(path).stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
