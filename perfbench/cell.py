"""A cell as ``BENCHMARK.json`` names it, with its configuration file, its
traffic file, its family and the readers of its metrics.

Everything is found by name: the configuration's file is the one
``BENCHMARK.json`` gives, the traffic is ``perfbench/traffic/<traffic>.json``,
the family ``perfbench/families/<family>.py`` and each metric
``perfbench/metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str):
    """The ``read(run)`` function of ``perfbench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench.metrics.{name}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        bench = load_benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                           f"(cells: {sorted(cells)})")
        entry = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads((root / configs[entry["config"]]["file"])
                            .read_text())
        traffic = json.loads((BENCH / "traffic" / f"{entry['traffic']}.json")
                             .read_text())
        return cls(name=name, chips=entry["chips"], config=config,
                   traffic=traffic,
                   end_to_end=[m for m in bench["end_to_end"]
                               if applies(m, name)],
                   per_layer=[m for m in bench["per_layer"]
                              if applies(m, name)])

    def family(self):
        module = importlib.import_module(
            f"perfbench.families.{self.traffic['family']}")
        return module.Family(self.config, self.traffic)
