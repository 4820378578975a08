"""Find a cell and everything it names, by name alone.

``BENCHMARK.json`` at the checkout's root lists the cells. A cell names a
configuration (its file is given in ``configs``) and a traffic mix, read
from ``chipbench/traffic/<traffic>.json``. The mix names its generator, the
module ``chipbench.generators.<generator>`` that generates and runs it; each
per-layer metric is read by ``chipbench/metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path


@dataclass
class Cell:
    name: str
    root: Path
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]

    def generator(self):
        return importlib.import_module(
            f"chipbench.generators.{self.traffic['generator']}")

    def reader(self, metric: str):
        """``read(ctx)`` of the metric's own file."""
        path = self.root / "chipbench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench.metrics.{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def _for(cell: str, metrics: list[dict]) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load(root, name: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    (entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, root, config, traffic, int(w["chips"]),
                _for(name, bench["end_to_end"]), _for(name, bench["per_layer"]))
