"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

Nothing here knows any cell, configuration, traffic mix or metric: a cell
is the ``workloads`` entry of that name; its configuration is the file the
matching ``configs`` entry names; its traffic mix is
``bench/traffic/<traffic>.json``; each metric is read by
``bench/metrics/<metric name>.py``. A later change adds a cell, a mix, a
configuration or a metric by adding files and entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

#: the benchmark's directory (``bench/``) and the repository root above it
BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    #: metric entries of ``BENCHMARK.json`` this cell reports, per mode
    end_to_end: List[Dict]
    per_layer: List[Dict]
    run_seconds: int


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reported_in(metric: Dict, cell: str, e2e_in_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_in_cell


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reported_in(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


def reader(metric_name: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric_name>.py``."""
    path = root / "bench" / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
