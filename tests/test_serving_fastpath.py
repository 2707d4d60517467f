"""Serving fast-path invariants: bucketed prefill compile count, ragged-batch
decode-attention equivalence (length-clamped KV streaming), and drain
equivalence between the async device-resident loop and the legacy
synchronous loop."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.serving import ServingEngine
from repro.serving.request import Request


def _requests(cfg, lens, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


# --------------------------------------------------------------------------- #
# Length-aware KV streaming: clamped BlockSpec index_map must be a no-op
# numerically — ragged batches match the jnp oracle bit-for-tolerance.
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("length_aware", [True, False])
def test_ragged_decode_attention_matches_oracle(length_aware):
    rng = np.random.default_rng(0)
    B, W, Hkv, G, hd = 5, 128, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, W, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, W, Hkv, hd)), jnp.float32)
    lens = jnp.asarray([1, 17, 64, 128, 33], jnp.int32)
    out = ops.decode_attention(q, k, v, lens, block_k=32,
                               length_aware=length_aware)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-3
    )


def test_ragged_decode_attention_zero_length_rows():
    """Empty slots (length 0) must not poison the batch with NaNs."""
    rng = np.random.default_rng(1)
    B, W, Hkv, G, hd = 3, 64, 2, 1, 8
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, W, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, W, Hkv, hd)), jnp.float32)
    lens = jnp.asarray([0, 5, 64], jnp.int32)
    out = np.asarray(ops.decode_attention(q, k, v, lens, block_k=16))
    assert np.isfinite(out[1:]).all()
    want = np.asarray(ref.decode_attention_ref(q[1:], k[1:], v[1:], lens[1:]))
    np.testing.assert_allclose(out[1:], want, atol=3e-5, rtol=1e-3)


# --------------------------------------------------------------------------- #
# Bucketed prefill: compile count is O(log max_seq), not O(distinct lengths).
# --------------------------------------------------------------------------- #
def test_bucketed_prefill_compile_count(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    max_seq = 256
    eng = ServingEngine(model, params, max_batch=1, max_seq=max_seq,
                        packed=False)
    lens = list(range(5, 245, 12))  # 20 distinct prompt lengths
    assert len(set(lens)) == 20
    for req in _requests(cfg, lens, max_new=2):
        eng.submit(req, time.perf_counter())
    out = eng.run_until_drained()
    assert len(out) == 20
    # pow2 buckets in [min_bucket, max_seq]: at most log2(max_seq) shapes,
    # far below the 20 per-length compiles the seed engine paid.
    bound = int(np.log2(max_seq)) + 1
    assert eng.prefill_compile_count <= bound, (
        f"{eng.prefill_compile_count} prefill compiles > O(log max_seq) "
        f"bound {bound}"
    )


def test_legacy_engine_compiles_per_length(model_bank):
    """The baseline really does pay one compile per distinct length."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64, legacy=True)
    lens = [5, 9, 13, 21]
    for req in _requests(cfg, lens, max_new=2):
        eng.submit(req, time.perf_counter())
    eng.run_until_drained()
    assert eng.prefill_compile_count == len(set(lens))


# --------------------------------------------------------------------------- #
# Drain equivalence: async device-resident loop == legacy synchronous loop.
# --------------------------------------------------------------------------- #
def test_drain_tokens_match_legacy_sync_loop(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 8, 13, 21, 16, 30]

    def drain(**kw):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64, **kw)
        reqs = _requests(cfg, lens, max_new=6, seed=7)
        for req in reqs:
            eng.submit(req, time.perf_counter())
        out = eng.run_until_drained()
        assert len(out) == len(lens)
        return [tuple(r.generated) for r in reqs], eng

    fast, eng_fast = drain(inflight=4)
    sync, _ = drain(legacy=True)
    assert fast == sync
    # every harvested slot ended done on device too
    assert eng_fast.done_mask.all()


@pytest.mark.parametrize("arch", ["mamba2-130m", "jamba-v0.1-52b"])
def test_ssm_archs_route_to_exact_prefill_and_match_legacy(arch, model_bank):
    """Right-padded bucketing would corrupt SSM/hybrid recurrent state (pad
    tokens flow through conv/SSD), so the engine must fall back to exact
    prefill for those stacks — and still match the legacy loop's tokens."""
    from conftest import nodrop

    cfg = nodrop(get_config(arch).reduced())
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 9, 14]

    def drain(**kw):
        eng = ServingEngine(model, params, max_batch=2, max_seq=32, **kw)
        reqs = _requests(cfg, lens, max_new=4, seed=2)
        for req in reqs:
            eng.submit(req, time.perf_counter())
        out = eng.run_until_drained()
        assert len(out) == len(lens)
        return [tuple(r.generated) for r in reqs], eng

    fast, eng = drain(inflight=3)
    assert not eng.bucketed_prefill  # ssm layers force the exact path
    sync, _ = drain(legacy=True)
    assert fast == sync


def test_eos_stops_generation(model_bank):
    """Device-side EOS detection must cut sequences short, async window and
    all."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    # discover the greedy continuation, then replay with its 2nd token as EOS
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    probe = _requests(cfg, [9], max_new=6, seed=3)[0]
    eng.submit(probe, time.perf_counter())
    eng.run_until_drained()
    eos = probe.generated[1]

    eng2 = ServingEngine(model, params, max_batch=1, max_seq=64,
                         eos_token=eos, inflight=4)
    req = _requests(cfg, [9], max_new=6, seed=3)[0]
    eng2.submit(req, time.perf_counter())
    out = eng2.run_until_drained()
    assert len(out) == 1
    assert out[0].tokens == probe.generated[:2]


def test_max_new_tokens_one_finishes_at_prefill(model_bank):
    """The prefill token alone satisfies max_new_tokens=1 — no decode step,
    exactly one token (the legacy loop's off-by-one returned two)."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    req = _requests(cfg, [8], max_new=1)[0]
    eng.submit(req, time.perf_counter())
    out = eng.run_until_drained()
    assert len(out) == 1
    assert len(out[0].tokens) == 1
    assert eng.decode_steps == 0


def test_priority_admission_order(model_bank):
    """Higher-priority queued requests still win the free slot."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    lo = _requests(cfg, [8], max_new=2, seed=0)[0]
    hi = _requests(cfg, [8], max_new=2, seed=1)[0]
    hi.priority = 5
    eng.submit(lo, time.perf_counter())
    eng.submit(hi, time.perf_counter())
    out = eng.run_until_drained()
    assert [r.request_id for r in out] == [hi.request_id, lo.request_id]


def test_ttft_single_clock(model_bank):
    """ttft must be sane even when the caller passes a foreign clock value."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    req = _requests(cfg, [8], max_new=2)[0]
    eng.submit(req, now=1e12)  # e.g. time.time() epoch seconds
    out = eng.run_until_drained()
    assert len(out) == 1
    assert 0 <= out[0].ttft_s < 60
    assert out[0].total_s > 0


def test_e2e_latency_includes_modeled_ingress_and_egress(model_bank):
    """The modeled ingress stages (request wire + copy_in) charged at submit
    must reach ttft/total just like the egress stages reach total — the
    pre-fix engine folded only the response wire in, so
    ``total_s >= sum(stage_s)`` failed by the ingress (+copy_out) delta."""
    from repro.core.transport import Transport

    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64,
                        transport=Transport.RDMA)  # has copy_in AND copy_out
    req = _requests(cfg, [8], max_new=2)[0]
    eng.submit(req, time.perf_counter())
    out = eng.run_until_drained()
    rec = eng.store.records[0]
    ingress = rec.stage_s["request"] + rec.stage_s["copy_in"]
    assert ingress > 0
    raw_ttft = req.t_first_token - req.t_arrival
    assert out[0].ttft_s == pytest.approx(raw_ttft + ingress, abs=1e-9)
    # every charged stage is now inside the end-to-end stamp
    assert out[0].total_s + 1e-9 >= sum(out[0].stage_s.values())
    assert rec.t_done == req.t_done
