"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one ``workloads`` entry) names a configuration and a traffic mix.
Its files are:

* ``configs/<config>.json``: the model sizes as run (the ``file`` of the
  ``configs`` entry);
* ``deployments/<cell>.json``: how the cell is served (engine kind,
  replicas, ``max_batch``, ``max_seq``, ``min_bucket``, ``handoff_block``,
  transfer mode);
* ``traffic/<traffic>.json``: the mix the load generator reads;
* ``metrics/<metric>.py`` for every per-layer metric the cell reports;
* ``blocks/<family>/``, the package of the configuration file's
  ``family``: what the benchmark knows of that model block.

A block package holds ``weights`` (``make_params(cfg, seed)``, the served
tree made from the seed on the device in one jitted call, and
``abstract_tree(cfg)``, its shapes), ``reference`` (``logits(cfg, seed,
seqs, positions, precisions)``, the plain float32 forward and its
lower-precision control), ``work`` (``prefill_flops``, ``decode_flops``,
``decode_work``: useful operations and bytes from sizes and true
lengths), and two functions: ``configure(base, cfg)`` gives the program's
config at the file's sizes, and ``accepts(mc)`` says whether the
reference covers it. The harness, the check and the readers reach the
block only through :func:`load_block`.

A later cell, mix or metric is new files plus new entries: nothing here
names a cell. A configuration of another block is a configuration file
whose ``family`` names a new ``blocks/<family>/`` package, plus that
package.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import sys
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


def families(bench_dir: Path = BENCH) -> list:
    """The block families ``blocks/`` holds."""
    return sorted(p.parent.name
                  for p in (Path(bench_dir) / "blocks").glob("*/__init__.py"))


def load_block(cfg: dict, bench_dir: Path = BENCH):
    """The package ``blocks/<family>/`` for configuration ``cfg``, by its
    ``family`` key; an unknown family raises and names those there are."""
    family = cfg["family"]
    have = families(bench_dir)
    if family not in have:
        raise KeyError(f"{cfg.get('name', '?')}: no block for family "
                       f"{family!r} in {Path(bench_dir) / 'blocks'} "
                       f"(have {have})")
    path = (Path(bench_dir) / "blocks" / family).resolve()
    # one module per directory, so a block's jitted functions are built once
    tag = hashlib.sha1(str(path).encode()).hexdigest()[:10]
    name = f"bench_block_{family.replace('.', '_').replace('-', '_')}_{tag}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, path / "__init__.py", submodule_search_locations=[str(path)])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]
