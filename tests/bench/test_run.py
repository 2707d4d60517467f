"""The benchmark's command refuses to run without its chips."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from bench import manifest

ARGS = ["--workload", "granite-completion", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, *cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *cmd, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("cmd", [["bench/run.py"], ["-m", "bench.run"]])
def test_no_tpu_means_no_result(cmd):
    p = _run(manifest.ROOT, *cmd)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    (no program) fails and prints no result."""
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest.load_manifest()["paths"]:
        shutil.copytree(manifest.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "bench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
