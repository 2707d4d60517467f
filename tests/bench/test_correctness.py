"""The correctness check at a size a test run can hold, on the CPU.

Each test drives a whole run of a tiny cell through the harness (its
look for a chip skipped): sound runs come out correct, and the float8
control and each fault the serving cells can have come out not correct.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, manifest

from tinycell import CHECK, TINY_CONFIG, write_tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # one tree, so the runs of this file share one compile cache
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, cell, seed, **kw):
    return harness.run(cell, seed, 2.0, False, t_start=time.perf_counter(),
                       accelerator=False, root=root, bench_dir=root / "bench",
                       **kw)


@pytest.mark.parametrize("cell", ["tiny-fused", "tiny-disagg"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell, 2**31 + 12)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["served_gap"]["value"] <= CHECK["served_gap"] / 3


def test_float8_control_is_not_correct(root):
    """The control's tokens, compared in the served tokens' place, fail
    the run's own verdict; the program's reading on the same sample
    stays well inside the limit."""
    out = _run(root, "tiny-fused", 21, control=True)
    assert not out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    control = out["readings"]["control_gap"]
    assert out["checks"]["served_gap"]["value"] == control
    assert control > CHECK["served_gap"] > 3 * out["readings"]["program_gap"]


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.serving.engine import DecodePool

    def off_by_one(self, logits, key):
        return (jnp.argmax(logits, -1).astype(jnp.int32) + 1) % logits.shape[-1]

    monkeypatch.setattr(DecodePool, "_sample", off_by_one)
    out = _run(root, "tiny-fused", 22)
    assert not out["correct"]
    assert out["checks"]["served_gap"]["value"] > CHECK["served_gap"]


def test_float8_control_on_the_disagg_cell_is_not_correct(root):
    """The control fails the disaggregated cell's verdict too, on tokens
    whose cache crossed between chips."""
    out = _run(root, "tiny-disagg", 24, control=True)
    assert not out["correct"], out["checks"]
    control = out["readings"]["control_gap"]
    assert control > CHECK["served_gap"] > 3 * out["readings"]["program_gap"]


def test_altered_token_on_the_disagg_cell_is_not_correct(root, monkeypatch):
    from repro.serving.engine import DecodePool

    def off_by_one(self, logits, key):
        return (jnp.argmax(logits, -1).astype(jnp.int32) + 1) % logits.shape[-1]

    monkeypatch.setattr(DecodePool, "_sample", off_by_one)
    out = _run(root, "tiny-disagg", 25)
    assert not out["correct"]
    assert out["checks"]["served_gap"]["value"] > CHECK["served_gap"]


def test_kv_exchange_left_out_is_not_correct(root, monkeypatch):
    """The handoff lands zeros in place of the prefill's cache: the first
    token (from prefill) stays right, every decoded one reads a cache
    that never crossed."""
    from repro.serving.disagg import DisaggregatedEngine

    land = DisaggregatedEngine._land_impl

    def dropped(self, caches, meta):
        caches, meta = land(self, caches, meta)
        return jax.tree.map(jnp.zeros_like, caches), meta

    monkeypatch.setattr(DisaggregatedEngine, "_land_impl", dropped)
    out = _run(root, "tiny-disagg", 23)
    assert not out["correct"]


def test_reference_matches_the_program_forward_in_float32():
    """At float32 the reference and the program's own full forward agree:
    the reference is the same block, written apart."""
    from repro.models import Model

    cfg = dict(TINY_CONFIG)
    block = manifest.load_block(cfg)
    model = Model(harness.model_config(cfg, block), dtype=jnp.float32)
    params = block.weights.make_params(cfg, 5, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (40, 17)]
    positions = [np.arange(n) for n in (40, 17)]
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(model.forward(params, {"tokens": s[None]})[0][0])
                for s in seqs]
    got = block.reference.logits(cfg, 5, seqs, positions, ("f32",),
                           dtype=jnp.float32)["f32"]
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=0)


def test_dense_block_keeps_its_numbers():
    """The dense block's weights and reference read as they did before
    they moved under ``blocks/dense/``: the served tree of the tiny
    configuration hashes to the same bytes, and the reference's logits
    (float32 and the float8 control) keep their sums, ends and argmaxes."""
    import hashlib

    cfg = dict(TINY_CONFIG)
    block = manifest.load_block(cfg)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(block.weights.make_params(cfg, 2**31 + 12)):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == ("f0c1f2120d26a2dbee228dd519bfeb00"
                             "432e966686427ec9a385f2293cee13f1")
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (40, 17)]
    lg = block.reference.logits(cfg, 2**31 + 12, seqs,
                                [np.arange(30, 40), np.arange(17)],
                                ("f32", "fp8"))
    want = {  # sum, sum of |x|, first, last, argmax per position
        "f32": [(-5.21798038482666, 656.3192138671875, -0.06247388571500778,
                 -0.03580433130264282,
                 [62, 64, 369, 372, 82, 3, 14, 34, 172, 206]),
                (-33.506813049316406, 1120.748779296875, -0.030569622293114662,
                 -0.22277407348155975,
                 [167, 383, 341, 341, 321, 5, 54, 40, 341, 62, 54, 54, 317,
                  193, 421, 299, 228])],
        "fp8": [(-4.48076868057251, 659.09716796875, -0.058940134942531586,
                 -0.015153723768889904,
                 [62, 433, 80, 488, 82, 3, 14, 34, 172, 206]),
                (-33.20085525512695, 1119.097900390625, -0.029420198872685432,
                 -0.21260111033916473,
                 [167, 383, 341, 341, 372, 193, 54, 40, 341, 62, 54, 54, 122,
                  193, 421, 369, 46])],
    }
    for p, rows in want.items():
        for a, (total, mass, first, last, top) in zip(lg[p], rows):
            np.testing.assert_allclose(
                [a.sum(), np.abs(a).sum(), a[0, 0], a[-1, -1]],
                [total, mass, first, last], rtol=1e-5, atol=1e-6)
            assert a.argmax(-1).tolist() == top
