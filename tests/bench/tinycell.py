"""A tiny benchmark tree for running the harness on the CPU.

Kept out of ``conftest.py``: the repository's tests import their own
``conftest`` by name, and a second one here would shadow it.

``tiny_root`` writes, under a temporary directory, a ``BENCHMARK.json``
with two small cells (one fused engine, one two-replica disaggregated
cluster) and their configuration, deployment and traffic files, in the
layout the real benchmark uses. The metric readers, the block packages
and the layer table are copied from the real ``bench/``.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny", "source": "test", "repro_config": "starcoder2-3b",
    "family": "dense", "dtype": "bfloat16", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab_size": 512, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "reduced": [],
}
TINY_MIX = {
    "arrivals": {"process": "poisson", "rate_rps": 6.0}, "shape_seed": 3,
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 16, "max": 64},
    "output_len": {"dist": "uniform", "min": 4, "max": 12},
}
CHECK = {"served_gap": 0.05, "tokens_checked": 16}
DEPLOYMENTS = {
    "tiny-fused": {"engine": "fused",
                   "engine_options": {"max_batch": 4, "max_seq": 80,
                                      "min_bucket": 16, "inflight": 4},
                   "check": CHECK},
    "tiny-disagg": {"engine": "disagg",
                    "cluster": {"n_replicas": 2, "pods_per_replica": 2,
                                "policy": "least_loaded"},
                    "engine_options": {"max_batch": 4, "max_seq": 80,
                                       "min_bucket": 16, "inflight": 4,
                                       "transfer_mode": "direct_hbm",
                                       "handoff_block": 16},
                    "check": CHECK},
}


def write_tiny_root(root: Path) -> Path:
    bench = root / "bench"
    for sub in ("configs", "deployments", "traffic"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    shutil.copytree(ROOT / "bench" / "blocks", bench / "blocks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "bench" / "layers.json", bench / "layers.json")
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(TINY_MIX))
    for name, dep in DEPLOYMENTS.items():
        (bench / "deployments" / f"{name}.json").write_text(json.dumps(dep))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    man = dict(real)
    man["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "CPU test size"}]
    man["workloads"] = [
        {"name": n, "config": "tiny", "traffic": "tiny-mix", "chips": 1,
         "why": "CPU test"} for n in DEPLOYMENTS]
    man["per_layer"] = [dict(m, workloads=list(DEPLOYMENTS))
                        for m in real["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny_root(tmp_path)
