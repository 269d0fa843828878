"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

  configuration  the ``file`` its entry gives (under ``bench/configs/``),
                 which may name its fleet builder and its edge weights
                 (``harness/fleet.py``)
  traffic mix    ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<metric name>.py``, whose ``read(ctx)``
                 returns the number, or None where it finds nothing to read

so a cell, a traffic mix or a metric is added by adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

from harness import fleet as fleet_mod
from harness import traffic as tr

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str, fallback: bool) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else fallback


def find_cell(bench: Dict, name: str, root: str = ROOT,
              traffic_dir: str = tr.TRAFFIC_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic and the metrics it
    reports: an end-to-end metric applies where its ``workloads`` list it
    (every cell without the key); a per-layer metric where its
    ``workloads`` list it, else in every cell that reports what it moves."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, True)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]),
                config=fleet_mod.load(os.path.join(root, entry["file"])),
                traffic=tr.load(w["traffic"], traffic_dir),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, directory: str = METRICS_DIR
           ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(directory, metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
