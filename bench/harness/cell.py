"""A cell as ``BENCHMARK.json`` names it, with its files found by name.

* configuration: ``configs[].file``, a JSON file of the deployment;
* traffic: ``bench/traffic/<traffic>.json``, parameters for the generator;
* metric: ``bench/metrics/<name>.py``, a reader with ``read(run)``.

A cell reports a metric (a per-layer one in traced runs) when the metric
lists it under ``workloads`` or lists no cells.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: object          # read(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def _reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())

    def metrics(kind: str) -> list[Metric]:
        return [Metric(m["name"], m["unit"], _reader(m["name"]))
                for m in bench[kind]
                if workload in m.get("workloads", [workload])]

    return Cell(workload, int(w["chips"]), config, traffic,
                metrics("end_to_end"), metrics("per_layer"))
