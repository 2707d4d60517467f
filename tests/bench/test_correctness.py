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

from bench import harness, reference
from bench import weights as W

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
    model = Model(harness.model_config(cfg), dtype=jnp.float32)
    params = W.make_params(cfg, 5, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (40, 17)]
    positions = [np.arange(n) for n in (40, 17)]
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(model.forward(params, {"tokens": s[None]})[0][0])
                for s in seqs]
    got = reference.logits(cfg, 5, seqs, positions, ("f32",),
                           dtype=jnp.float32)["f32"]
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=0)
