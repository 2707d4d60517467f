"""BENCHMARK.json and the files it names.

No test here touches a TPU topology: the harness imports nothing of the
chip at module level, and these tests read files only.
"""

from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import harness, loadgen, manifest

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    for p in MAN["paths"]:
        assert (manifest.ROOT / p).is_dir()
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_its_files(name):
    cell = manifest.load_cell(name)
    dep, mix, cfg = cell.deployment, cell.traffic, cell.config
    opts = harness.engine_options(dep)
    assert cell.chips in (1, 4)
    assert loadgen.longest(mix) <= opts["max_seq"]
    assert opts["min_bucket"] <= mix["prompt_len"]["min"]
    assert mix["arrivals"]["rate_rps"] > 0 and cfg["dtype"] == "bfloat16"
    if dep["engine"] == "disagg":
        assert opts["handoff_block"] <= mix["prompt_len"]["min"]
        assert cell.chips == (dep["cluster"]["n_replicas"]
                              * dep["cluster"]["pods_per_replica"])
    assert {"served_gap", "tokens_checked"} <= set(dep["check"])
    for m in cell.per_layer:
        assert callable(manifest.load_reader(m["name"]))


def test_configs_name_their_cuts():
    for c in MAN["configs"]:
        cfg = json.loads((manifest.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|d_model|d_ff|heads)$", key)
        assert any(w["config"] == c["name"] for w in MAN["workloads"])


def test_names_and_units():
    names = [m["name"] for m in METRICS] + CELLS + [c["name"] for c in
                                                      MAN["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in MAN["workloads"]]:
        assert NAME_RE.match(n), n
    for m in METRICS:
        assert UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in CELLS:
        c = manifest.load_cell(cell)
        reported = {m["name"] for m in c.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert c.per_layer
        # beside every roofline moving a metric, an mfu moving it too
        for m in c.per_layer:
            if m["name"].endswith("_roofline"):
                assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                           for x in c.per_layer)
            if m["name"].endswith("_roofline") or "mfu" in m["name"]:
                assert m["unit"] == "%"


def test_a_new_cell_is_new_files_and_one_entry(tmp_path):
    """A cell added as new files plus one workloads entry loads, and no
    file the benchmark already has changes."""
    root = tmp_path
    shutil.copytree(manifest.BENCH, root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    man = json.loads((manifest.ROOT / "BENCHMARK.json").read_text())
    base = man["workloads"][0]
    (root / "bench" / "traffic" / "fixture-mix.json").write_text(
        json.dumps(loadgen.with_rate(manifest.load_cell(base["name"]).traffic,
                                     0.5)))
    (root / "bench" / "deployments" / "fixture-cell.json").write_text(
        (manifest.BENCH / "deployments" / f"{base['name']}.json").read_text())
    man["workloads"].append(dict(base, name="fixture-cell",
                                 traffic="fixture-mix"))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.load_cell("fixture-cell", root, root / "bench")
    assert cell.traffic["arrivals"]["rate_rps"] == 0.5
    assert cell.config == manifest.load_cell(base["name"]).config
    assert [m["name"] for m in cell.end_to_end] == [
        m["name"] for m in man["end_to_end"] if "workloads" not in m]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_size_limits():
    text = (manifest.ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    for w in MAN["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in MAN["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    for c in MAN["configs"]:
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(MAN["workloads"]) // 2)


def test_unknown_deployment_key_is_refused():
    dep = json.loads((manifest.BENCH / "deployments" /
                      f"{CELLS[0]}.json").read_text())
    with pytest.raises(ValueError, match="unknown deployment keys"):
        harness.engine_options(dict(dep, packed=True))


STUB_BLOCK = '''"""A block of another family for the test: the dense block's
parts, under a gate of its own that refuses more than two layers."""

import dataclasses

from . import reference, weights, work  # noqa: F401

KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "rope_theta", "norm_eps")


def configure(base, cfg):
    return dataclasses.replace(base, name=cfg["name"],
                               **{k: cfg[k] for k in KEYS})


def accepts(mc):
    return mc.n_layers <= 2
'''


def _stub_tree(root):
    """The tiny benchmark tree, plus a ``stub`` block (new files only) and
    configurations of that family; returns the bytes of every file the
    tree held before."""
    from tinycell import TINY_CONFIG, write_tiny_root

    write_tiny_root(root)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    stub = bench / "blocks" / "stub"
    stub.mkdir()
    (stub / "__init__.py").write_text(STUB_BLOCK)
    for part in ("weights", "reference", "work"):
        shutil.copy(bench / "blocks" / "dense" / f"{part}.py", stub)
    for name, layers in (("tiny-stub", 2), ("deep-stub", 3)):
        (bench / "configs" / f"{name}.json").write_text(json.dumps(
            dict(TINY_CONFIG, name=name, family="stub", n_layers=layers)))
    return before


def test_a_block_is_found_by_its_family(tmp_path):
    """A configuration whose ``family`` names a new block package loads
    that package, and the package's own ``accepts`` is what gates it."""
    before = _stub_tree(tmp_path)
    bench = tmp_path / "bench"
    assert manifest.families(bench) == ["dense", "stub"]
    cfgs = {n: json.loads((bench / "configs" / f"{n}.json").read_text())
            for n in ("tiny-stub", "deep-stub")}
    block = manifest.load_block(cfgs["tiny-stub"], bench)
    assert block.accepts.__module__ == block.__name__
    assert manifest.load_block(cfgs["deep-stub"], bench) is block
    assert harness.model_config(cfgs["tiny-stub"], block).n_layers == 2
    # the dense block would take three layers; the stub's gate refuses them
    dense = manifest.load_block(dict(cfgs["deep-stub"], family="dense"),
                                bench)
    assert harness.model_config(cfgs["deep-stub"], dense).n_layers == 3
    with pytest.raises(ValueError, match="'stub' block's reference"):
        harness.model_config(cfgs["deep-stub"], block)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_family_raises_and_names_the_families():
    with pytest.raises(KeyError, match=r"'moe'.*\['dense'\]"):
        manifest.load_block({"name": "x", "family": "moe"})


def test_dense_block_refuses_what_its_reference_does_not_cover():
    """The dense block's gate: a config with experts, latent attention,
    state-space layers, QK norm or a window is refused."""
    import dataclasses

    cfg = json.loads((manifest.BENCH / "configs" /
                      "starcoder2-3b.json").read_text())
    block = manifest.load_block(cfg)
    mc = harness.model_config(cfg, block)
    assert block.accepts(mc)
    for change in ({"qk_norm": True}, {"sliding_window": 4096},
                   {"family": "moe"}):
        assert not block.accepts(dataclasses.replace(mc, **change))


def test_another_family_runs_with_new_files_only(tmp_path):
    """A cell on a configuration of the stub family runs a whole window on
    the CPU, correct, with nothing but the new block, its configuration
    file and the cell's entries added to the tree."""
    import time

    before = _stub_tree(tmp_path)
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-stub", "source": "test",
                           "file": "bench/configs/tiny-stub.json",
                           "reduced": [], "why": "CPU test"})
    man["workloads"].append({"name": "stub-fused", "config": "tiny-stub",
                             "traffic": "tiny-mix", "chips": 1,
                             "why": "CPU test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    (tmp_path / "bench" / "deployments" / "stub-fused.json").write_text(
        (tmp_path / "bench" / "deployments" / "tiny-fused.json").read_text())
    out = harness.run("stub-fused", 2**31 + 31, 2.0, False,
                      t_start=time.perf_counter(), accelerator=False,
                      root=tmp_path, bench_dir=tmp_path / "bench")
    assert out["correct"], out["checks"]
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")
