"""Useful work counts and the table of peaks."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from bench import manifest, peaks

CONFIGS = sorted((manifest.BENCH / "configs").glob("*.json"))


def _cfg(path: Path) -> dict:
    return json.loads(path.read_text())


def _work(cfg: dict):
    return manifest.load_block(cfg).work


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_param_count_matches_the_program(path):
    """The program's own leaves hold exactly what ``work`` counts; its
    ``ModelConfig.param_count`` adds an untied head the program's model
    does not hold (it ties the head to the embedding table)."""
    import jax

    from bench import harness
    from repro.models import Model

    cfg = _cfg(path)
    block = manifest.load_block(cfg)
    mc = harness.model_config(cfg, block)
    leaves = sum(math.prod(s.shape)
                 for s in jax.tree.leaves(Model(mc).param_specs()))
    assert block.work.param_count(cfg) == leaves
    assert not mc.tie_embeddings
    assert mc.param_count() == leaves + cfg["vocab_size"] * cfg["d_model"]


def test_granite_stage_sizes():
    cfg = _cfg(manifest.BENCH / "configs" / "granite-34b-10l.json")
    work = _work(cfg)
    assert work.layer_params(cfg) - 2 * cfg["d_model"] == 530_055_168
    assert work.embed_params(cfg) == 301_989_888
    assert work.weight_bytes(cfg) == 2 * work.param_count(cfg)
    assert 11.2e9 < work.weight_bytes(cfg) < 11.21e9


def test_prefill_and_decode_flops_from_true_lengths():
    cfg = _cfg(manifest.BENCH / "configs" / "starcoder2-3b.json")
    work = _work(cfg)
    dense = 2 * cfg["n_layers"] * (work.layer_params(cfg) - 2 * cfg["d_model"])
    head = 2 * work.embed_params(cfg)
    per_key = 4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"]
    # n tokens: n dense passes, n(n+1)/2 causal keys, one head
    assert work.prefill_flops(cfg, 100) == pytest.approx(
        100 * dense + per_key * 100 * 101 / 2 + head)
    assert work.decode_flops(cfg, 300) == pytest.approx(
        dense + head + per_key * 300)
    flops, nbytes = work.decode_work(cfg, 2, [300, 301])
    assert flops == pytest.approx(work.decode_flops(cfg, 300)
                                  + work.decode_flops(cfg, 301))
    assert nbytes == 2 * work.weight_bytes(cfg) + work.kv_bytes(cfg, 601)
    assert work.kv_bytes(cfg, 1) == 2 * 30 * 2 * 128 * 2


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_shares_read_from_the_chip_trace_stay_under_100(chip_trace_ctx):
    """Every share the readers take from the recorded chip trace, with the
    work of the requests that window served, is at most 100%."""
    values = {}
    for m in manifest.load_manifest()["per_layer"]:
        v = manifest.load_reader(m["name"])(chip_trace_ctx)
        if v is not None:
            values[m["name"]] = v
            if m["unit"] == "%":
                assert 0.0 <= v <= 100.0, (m["name"], v)
    assert values["prefill_pad_share"] == pytest.approx(
        100 * (1 - 942 / 4608))
    assert values["prefill_ms"] == pytest.approx(100.228123)
    assert values["decode_step_ms"] == pytest.approx(291.331129 / 27)
    assert 60 < values["decode_roofline"] < 90
    assert 10 < values["prefill_mfu"] < 20
    assert values["device_idle_share"] == pytest.approx(1.7382028)


@pytest.fixture
def chip_trace_ctx(tmp_path):
    """The trace fixture's window as the readers see it: the run served
    prompts of 814 and 128 tokens (buckets 1024 and 128 at 4 rows), then
    24 and 2 decode tokens in 26 steps that advanced a request."""
    import gzip
    from types import SimpleNamespace

    from bench import trace_reduce

    path = tmp_path / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(
        (manifest.ROOT / "tests" / "bench" / "fixtures" /
         "granite-completion-0.5s.xplane.pb.gz").read_bytes()))
    reduced = trace_reduce.reduce_dir(tmp_path, manifest.BENCH / "layers.json")
    # the fixture was recorded with six of the stage's layers
    cfg = dict(_cfg(manifest.BENCH / "configs" / "granite-34b-10l.json"),
               n_layers=6)
    return SimpleNamespace(
        cfg=cfg, block=manifest.load_block(cfg),
        records={}, trace=reduced, prefilled=[814, 128],
        decode_contexts=[814 + j for j in range(1, 25)] + [129, 130],
        counters={"prefill_tokens_total": 942,
                  "prefill_padded_tokens": 4 * 1024 + 4 * 128,
                  "useful_steps": 26},
        peaks=peaks.peaks("TPU v5 lite"))
