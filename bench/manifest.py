"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one ``workloads`` entry) names a configuration and a traffic mix.
Its files are:

* ``configs/<config>.json``: the model sizes as run (the ``file`` of the
  ``configs`` entry);
* ``deployments/<cell>.json``: how the cell is served (engine kind,
  replicas, ``max_batch``, ``max_seq``, ``min_bucket``, ``handoff_block``,
  transfer mode);
* ``traffic/<traffic>.json``: the mix the load generator reads;
* ``metrics/<metric>.py`` for every per-layer metric the cell reports.

A later cell, mix or metric is new files plus new entries: nothing here
names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent  # the checkout
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    deployment: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_manifest(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    """Resolve one ``workloads`` entry into its files and metric lists."""
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(Path(root) / cfg_entry["file"]),
        deployment=load_json(Path(bench_dir) / "deployments" / f"{name}.json"),
        traffic=load_json(Path(bench_dir) / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in man["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in man["per_layer"] if _reports(m, name)],
    )


def load_reader(metric: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = Path(bench_dir) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
