"""Token-packed + chunked prefill: the segment-masking equivalence suite.

Covers, bottom-up:

* the segment-masking law at the attention level — a packed sequence's
  per-segment rows are BITWISE the rows of each segment prefilled alone
  (NEG_INF masking contributes exact 0.0 terms to the softmax), as a
  hypothesis property (seeded-sweep fallback) plus a poison-token canary;
* the Pallas flash kernel's segment-id masking vs per-segment reference;
* engine-level token identity: packed vs bucketed, chunked vs unchunked,
  packed+paged, packed through the disaggregated handoff — and the
  cross-architecture matrix (attention-only archs identical; SSM/hybrid
  archs ASSERTED to auto-route to the exact prefill path);
* the prefill-FLOPs proxy win (``prefill_padded_tokens``) on a ragged
  admission, and chunked prefill's decode interleaving;
* warmup: packed/chunk jits pre-trace at construction (zero serve-time
  compiles), and the knob-validation errors;
* the derived default (``packed`` left unset packs unless a paged pool
  reuses prefixes, and the process-backend view derives it the same
  way) and the packed-width cap, with admissions past it split into
  consecutive packed calls.
"""

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import nodrop

from repro.configs import ARCHITECTURES, get_config
from repro.core import trace
from repro.core.trace import Trace
from repro.kernels import ops
from repro.models.attention import chunked_attention
from repro.serving import ServingEngine
from repro.serving.request import Request


# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #
def _requests(cfg, lens, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r, time.perf_counter())
    out = eng.run_until_drained(max_steps=100_000)
    assert len(out) == len(reqs), (len(out), len(reqs))
    return [tuple(r.generated) for r in reqs]


def _prefill_spans(eng, reqs):
    """Drain ``reqs`` traced; return (tokens, the engine.prefill spans)."""
    trace.enable_tracing(process="main")
    try:
        toks = _drain(eng, reqs)
        spans = [s for s in Trace.from_buffer().spans
                 if s.name == "engine.prefill"]
    finally:
        trace.disable_tracing()
    return toks, spans


def _pack(parts, pad_to=None):
    """Concatenate per-segment [1, s, ...] arrays along seq; return the
    packed array, its [1, T] segment ids (-1 on pad), and seg offsets."""
    T = sum(p.shape[1] for p in parts)
    Tp = max(pad_to or T, T)
    packed = np.zeros((1, Tp) + parts[0].shape[2:], parts[0].dtype)
    seg = np.full((1, Tp), -1, np.int32)
    starts, off = [], 0
    for j, p in enumerate(parts):
        s = p.shape[1]
        packed[0, off:off + s] = p[0]
        seg[0, off:off + s] = j
        starts.append(off)
        off += s
    return jnp.asarray(packed), jnp.asarray(seg), starts


# --------------------------------------------------------------------------- #
# Attention-level law: packed rows == lone-segment rows, bitwise
# --------------------------------------------------------------------------- #
def _check_packed_attention_law(seed, seg_lens, window=0):
    """Two faces of the segment-masking law, per segment j:

    * BITWISE isolation: replacing every OTHER segment's tokens with pads
      (id -1, zero qkv) moves not one bit of j's packed rows — masked
      scores hit -1e30, exp underflows to exact 0.0, and zero terms
      change no fp32 sum, so j's rows are a pure function of j's tokens.
      (Comparing at the SAME packed width pins XLA's reduction tree;
      comparing against the lone [1, s_j] run instead would measure
      shape-dependent fp summation order, not masking.)
    * reduction to the lone run: j's packed rows match segment j
      prefilled alone to fp32 accumulation-order tolerance.
    """
    H, hd = 2, 8
    rng = np.random.default_rng(seed)
    parts = [
        (rng.standard_normal((1, s, H, hd)).astype(np.float32),
         rng.standard_normal((1, s, H, hd)).astype(np.float32),
         rng.standard_normal((1, s, H, hd)).astype(np.float32))
        for s in seg_lens
    ]
    qp, seg, starts = _pack([p[0] for p in parts])
    kp, _, _ = _pack([p[1] for p in parts])
    vp, _, _ = _pack([p[2] for p in parts])
    packed = chunked_attention(qp, kp, vp, causal=True, window=window,
                               segment_ids=seg)
    T = qp.shape[1]
    for j, (q, k, v) in enumerate(parts):
        s = q.shape[1]
        got = np.asarray(packed[:, starts[j]:starts[j] + s])

        # bitwise: segment j alone IN PLACE (same width, same offset)
        def isolate(x):
            iso = np.zeros((1, T) + x.shape[2:], np.float32)
            iso[0, starts[j]:starts[j] + s] = x[0]
            return jnp.asarray(iso)

        seg_iso = np.full((1, T), -1, np.int32)
        seg_iso[0, starts[j]:starts[j] + s] = j
        alone_in_place = chunked_attention(
            isolate(q), isolate(k), isolate(v), causal=True, window=window,
            segment_ids=jnp.asarray(seg_iso),
        )
        np.testing.assert_array_equal(
            got, np.asarray(alone_in_place[:, starts[j]:starts[j] + s])
        )

        # reduction: the true lone run (different kv width reassociates
        # the fp32 softmax/output sums; masking itself is exact)
        alone = chunked_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), causal=True, window=window)
        np.testing.assert_allclose(got, np.asarray(alone), atol=3e-6, rtol=0)


try:
    from hypothesis import given, settings, strategies as st

    @given(
        seg_lens=st.lists(st.integers(1, 9), min_size=1, max_size=4),
        seed=st.integers(0, 2**31 - 1),
        window=st.sampled_from([0, 4]),
    )
    @settings(max_examples=50, deadline=None)
    def test_packed_attention_bitwise_law(seg_lens, seed, window):
        _check_packed_attention_law(seed, seg_lens, window=window)

except ImportError:

    @pytest.mark.parametrize("seed", range(50))
    def test_packed_attention_bitwise_law(seed):
        rng = np.random.default_rng(seed)
        seg_lens = [int(s) for s in rng.integers(1, 10, rng.integers(1, 5))]
        _check_packed_attention_law(seed, seg_lens,
                                    window=int(rng.choice([0, 4])))


def test_packed_poison_canary():
    """Corrupting every value of segment A must not move ONE BIT of
    segment B's packed output — the direct no-cross-attention witness."""
    rng = np.random.default_rng(7)
    H, hd, sa, sb = 2, 8, 6, 5
    mk = lambda s: rng.standard_normal((1, s, H, hd)).astype(np.float32)
    a = (mk(sa), mk(sa), mk(sa))
    b = (mk(sb), mk(sb), mk(sb))
    poison = tuple(np.full_like(x, 1e4) for x in a)  # not NaN: NaN*0 = NaN

    def run(a_parts):
        qp, seg, starts = _pack([a_parts[0], b[0]])
        kp, _, _ = _pack([a_parts[1], b[1]])
        vp, _, _ = _pack([a_parts[2], b[2]])
        out = chunked_attention(qp, kp, vp, causal=True, segment_ids=seg)
        return np.asarray(out[:, starts[1]:starts[1] + sb])

    np.testing.assert_array_equal(run(a), run(poison))


def test_segment_ids_exclusive_with_prior():
    q = jnp.zeros((1, 4, 2, 8), jnp.float32)
    seg = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="prior"):
        chunked_attention(q, q, q, segment_ids=seg, prior_k=q, prior_v=q,
                          prior_valid=jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="segment_ids"):
        chunked_attention(q, q, q, segment_ids=jnp.zeros((1, 3), jnp.int32))


def test_flash_kernel_segment_mask_matches_per_segment():
    """The Pallas kernel's segment-id refs mask exactly like running each
    segment through the kernel alone (interpret mode on CPU)."""
    rng = np.random.default_rng(11)
    H, hd = 2, 16
    seg_lens = [7, 12, 5]
    parts = [
        tuple(rng.standard_normal((1, s, H, hd)).astype(np.float32)
              for _ in range(3))
        for s in seg_lens
    ]
    qp, seg, starts = _pack([p[0] for p in parts])
    kp, _, _ = _pack([p[1] for p in parts])
    vp, _, _ = _pack([p[2] for p in parts])
    packed = ops.flash_attention(qp, kp, vp, causal=True, block_q=8,
                                 block_k=8, segment_ids=seg)
    for j, (q, k, v) in enumerate(parts):
        alone = ops.flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            block_q=8, block_k=8,
        )
        s = q.shape[1]
        got = np.asarray(packed[:, starts[j]:starts[j] + s])
        np.testing.assert_allclose(got, np.asarray(alone), atol=1e-6, rtol=0)


def test_flash_kernel_segment_ids_both_or_neither():
    from repro.kernels.flash_attention import flash_attention_bhsd

    x = jnp.zeros((1, 2, 16, 16), jnp.float32)
    with pytest.raises(ValueError, match="both or neither"):
        flash_attention_bhsd(x, x, x, q_segment_ids=jnp.zeros((1, 16),
                                                              jnp.int32))


# --------------------------------------------------------------------------- #
# Engine: packed vs bucketed token identity + the FLOPs-proxy win
# --------------------------------------------------------------------------- #
_RAGGED = [5, 17, 33, 50]


def test_packed_vs_bucketed_token_identity(engine_bank):
    cfg = get_config("llama3-8b").reduced()
    kw = dict(max_batch=4, max_seq=128, temperature=0.0)
    base = _drain(engine_bank(cfg, packed=False, **kw),
                  _requests(cfg, _RAGGED))
    eng = engine_bank(cfg, packed=True, **kw)
    assert eng.packed
    assert _drain(eng, _requests(cfg, _RAGGED)) == base


def test_packed_padded_token_win(engine_bank):
    """On a ragged admission the packed path dispatches strictly fewer
    padded token-rows (the deterministic prefill-FLOPs proxy) than the
    bucketed path — while the true-token counters agree exactly."""
    cfg = get_config("llama3-8b").reduced()
    kw = dict(max_batch=4, max_seq=128, temperature=0.0)
    bucketed = engine_bank(cfg, packed=False, **kw)
    packed = engine_bank(cfg, packed=True, **kw)
    _drain(bucketed, _requests(cfg, _RAGGED))
    _drain(packed, _requests(cfg, _RAGGED))
    assert bucketed.prefill_tokens_total == packed.prefill_tokens_total
    assert packed.prefill_padded_tokens < bucketed.prefill_padded_tokens, (
        packed.prefill_padded_tokens, bucketed.prefill_padded_tokens,
    )
    # the packed width is the pow2 roof of the admission's TRUE tokens
    assert packed.prefill_padded_tokens >= packed.prefill_tokens_total


def test_chunked_vs_bucketed_token_identity(engine_bank):
    cfg = get_config("llama3-8b").reduced()
    kw = dict(max_batch=4, max_seq=128, temperature=0.0)
    base = _drain(engine_bank(cfg, packed=False, **kw),
                  _requests(cfg, _RAGGED))
    eng = engine_bank(cfg, packed=False, prefill_chunk=16, **kw)
    assert eng._chunk_enabled
    assert _drain(eng, _requests(cfg, _RAGGED)) == base
    # every chunk dispatches exactly chunk-width token rows
    assert eng.prefill_padded_tokens % 16 == 0
    # packed + chunked compose: short prompts pack, long prompts chunk
    both = engine_bank(cfg, packed=True, prefill_chunk=16, **kw)
    assert _drain(both, _requests(cfg, _RAGGED)) == base


def test_chunked_interleaves_decode(engine_bank):
    """While a long admission is mid-chunk, an already-running request
    keeps producing tokens — the structural head-of-line property (the
    TPOT bound itself is asserted in benchmarks/serving.py --quick)."""
    cfg = get_config("llama3-8b").reduced()
    eng = engine_bank(cfg, max_batch=2, max_seq=128, temperature=0.0,
                      prefill_chunk=16)
    victim = _requests(cfg, [8], max_new=48, seed=1)[0]
    eng.submit(victim, time.perf_counter())
    while len(victim.generated) < 4:  # victim decoding before the burst
        eng.step()
    big = _requests(cfg, [100], max_new=4, seed=2)[0]
    eng.submit(big, time.perf_counter())
    progressed = []
    while eng._chunk_jobs or not big.generated:
        mid_chunk = bool(eng._chunk_jobs)
        before = len(victim.generated)
        eng.step()
        if mid_chunk:
            progressed.append(len(victim.generated) > before)
        assert len(progressed) < 10_000
    assert any(progressed), "no decode progress during the chunked admission"
    eng.run_until_drained(max_steps=100_000)
    assert len(victim.generated) == 48


def test_chunk_knob_validation(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    with pytest.raises(ValueError, match="ring"):
        ServingEngine(model, params, max_batch=2, max_seq=64, paged=True,
                      prefill_chunk=16)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        ServingEngine(model, params, max_batch=2, max_seq=64,
                      prefill_chunk=128)
    with pytest.raises(ValueError, match=">= 0"):
        ServingEngine(model, params, max_batch=2, max_seq=64,
                      prefill_chunk=-1)


def test_packed_paged_token_identity(engine_bank):
    """Packing rides the paged pool too (prefix reuse auto-off: packed
    pages interleave segments, so they never align with the index)."""
    cfg = get_config("llama3-8b").reduced()
    kw = dict(max_batch=4, max_seq=128, temperature=0.0)
    base = _drain(engine_bank(cfg, packed=False, **kw),
                  _requests(cfg, _RAGGED))
    eng = engine_bank(cfg, paged=True, packed=True, **kw)
    assert eng.packed and eng.paged and not eng.prefix_reuse
    assert _drain(eng, _requests(cfg, _RAGGED)) == base


@pytest.mark.slow
def test_packed_disagg_token_identity(model_bank):
    from repro.serving.disagg import DisaggregatedEngine, TransferMode

    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    kw = dict(max_batch=4, max_seq=128, temperature=0.0)
    base = _drain(ServingEngine(model, params, packed=False, **kw),
                  _requests(cfg, _RAGGED))
    for dkw in (dict(packed=True), dict(packed=False, prefill_chunk=16)):
        eng = DisaggregatedEngine(
            model, params, transfer_mode=TransferMode.DIRECT_HBM, **kw,
            **dkw,
        )
        assert _drain(eng, _requests(cfg, _RAGGED)) == base, dkw


# --------------------------------------------------------------------------- #
# Cross-architecture matrix: identity on attention-only, auto-route on SSM
# --------------------------------------------------------------------------- #
_PACKABLE_ARCHS = [
    "llama3-8b",
    "starcoder2-3b",
    pytest.param("qwen3-32b", marks=pytest.mark.slow),
]
_UNPACKABLE_ARCHS = ["mamba2-130m", "jamba-v0.1-52b"]


@pytest.mark.parametrize("name", _PACKABLE_ARCHS)
def test_cross_arch_packed_chunked_identity(name, engine_bank):
    cfg = nodrop(ARCHITECTURES[name].reduced())
    kw = dict(max_batch=2, max_seq=128, temperature=0.0)
    lens = [9, 40]
    base = _drain(engine_bank(cfg, packed=False, **kw), _requests(cfg, lens))
    eng = engine_bank(cfg, packed=True, prefill_chunk=32, **kw)
    assert eng.packed and eng._chunk_enabled
    assert _drain(eng, _requests(cfg, lens)) == base


@pytest.mark.parametrize("name", _UNPACKABLE_ARCHS)
def test_cross_arch_unpackable_auto_routes_exact(name, engine_bank):
    """SSM/hybrid recurrences integrate pad AND neighbor tokens into
    state, so packing is unsound there — the knobs must auto-downgrade
    to the exact prefill path (same silent gate as bucketed_prefill),
    and tokens must match the default engine exactly."""
    cfg = nodrop(ARCHITECTURES[name].reduced())
    kw = dict(max_batch=2, max_seq=128, temperature=0.0)
    lens = [9, 40]
    base = _drain(engine_bank(cfg, **kw), _requests(cfg, lens))
    eng = engine_bank(cfg, packed=True, prefill_chunk=32, **kw)
    assert not eng.bucketed_prefill  # the shared soundness gate
    assert not eng.packed and not eng._chunk_enabled
    assert _drain(eng, _requests(cfg, lens)) == base


def test_mla_auto_downgrades(model_bank):
    """MLA stacks bucket fine but can't pack (latent cache; segment
    masking rides plain attention) — packed/chunk silently downgrade."""
    cfg = nodrop(ARCHITECTURES["deepseek-v2-236b"].reduced())
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        packed=True, prefill_chunk=32)
    assert eng.bucketed_prefill
    assert not eng.packed and not eng._chunk_enabled


# --------------------------------------------------------------------------- #
# Warmup: packed/chunk grids pre-trace; zero compiles while serving
# --------------------------------------------------------------------------- #
class _LogGrab(logging.Handler):
    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _compiles_during(fn):
    grab = _LogGrab()
    logger = logging.getLogger("jax")
    old_level = logger.level
    logger.addHandler(grab)
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            fn()
    finally:
        logger.removeHandler(grab)
        logger.setLevel(old_level)
    return [m for m in grab.messages if m.startswith("Compiling ")]


def test_warmup_packed_chunk_zero_compiles(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    kw = dict(max_batch=2, max_seq=64, prefill_chunk=16, packed=True)

    # positive control: the cold engine must visibly compile
    cold = ServingEngine(model, params, **kw)
    assert _compiles_during(
        lambda: _drain(cold, _requests(cfg, [5, 40]))
    ), "log capture saw no compiles from a cold engine"

    warm = ServingEngine(model, params, warmup=True, **kw)
    assert warm.warm_s > 0
    # packed grid covers min_bucket .. pow2(max_seq)
    assert warm.packed_grid() == [16, 32, 64]
    shapes = warm.prefill_compile_count
    compiles = _compiles_during(
        lambda: _drain(warm, _requests(cfg, [5, 40]))
    )
    assert compiles == [], f"compiled inside the serving window: {compiles}"
    assert warm.prefill_compile_count == shapes


# --------------------------------------------------------------------------- #
# The derived default and the packed-width cap
# --------------------------------------------------------------------------- #
def test_default_packs_attention_only_ring_pool(model_bank):
    """Left unset, ``packed`` is derived: an attention-only ring-pool
    engine packs, and every admission's prefill span says so."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=4, max_seq=128)
    assert eng.packed
    _toks, spans = _prefill_spans(eng, _requests(cfg, _RAGGED))
    assert spans and all(s.attrs["path"] == "packed" for s in spans)


def test_default_paged_with_reuse_keeps_bucket_path(model_bank):
    """A paged pool that reuses prefixes keeps reuse and the bucket path
    by default; the same pool without reuse packs."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=4, max_seq=128, paged=True)
    assert eng.prefix_reuse and not eng.packed
    _toks, spans = _prefill_spans(eng, _requests(cfg, _RAGGED))
    assert spans and all(s.attrs["path"] == "paged" for s in spans)
    no_reuse = ServingEngine(model, params, max_batch=4, max_seq=128,
                             paged=True, prefix_reuse=False)
    assert no_reuse.packed and not no_reuse.prefix_reuse


@pytest.mark.parametrize("max_batch,max_seq,cap", [
    (4, 2112, 4096),  # the granite-completion deployment
    (2, 64, 64),
    (2, 48, 64),
    (1, 48, 64),
    (8, 64, 64),      # the slot count does not widen the row
])
def test_packed_cap_is_one_prompt_width(model_bank, max_batch, max_seq, cap):
    """The packed row is capped at pow2(max_seq): one longest prompt
    always fits, and no row is wider."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=max_batch, max_seq=max_seq,
                        min_bucket=16)
    assert eng.packed_cap() == cap
    assert eng.packed_grid() == [w for w in (16, 32, 64, 128, 256, 512,
                                             1024, 2048, 4096) if w <= cap]


def test_admission_past_cap_splits_into_packed_calls(model_bank):
    """With a non-pow2 max_seq the cap (64 here) sits below the sum of an
    admission's prompts (40 + 30): the admission goes out as two packed
    calls in pick order, token for token what the bucket path serves."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    kw = dict(max_batch=2, max_seq=48, temperature=0.0)
    base = _drain(ServingEngine(model, params, packed=False, **kw),
                  _requests(cfg, [40, 30]))
    eng = ServingEngine(model, params, **kw)
    assert eng.packed and eng.packed_cap() == 64
    toks, spans = _prefill_spans(eng, _requests(cfg, [40, 30]))
    assert toks == base
    assert [(s.attrs["path"], s.attrs["bucket"], s.attrs["rows"])
            for s in spans] == [("packed", 64, 1), ("packed", 32, 1)]
    assert eng.prefill_padded_tokens == 64 + 32


@pytest.mark.parametrize("name,kw", [
    ("llama3-8b", {}),
    ("llama3-8b", {"packed": False}),
    ("llama3-8b", {"paged": True}),
    ("llama3-8b", {"paged": True, "prefix_reuse": False}),
    ("llama3-8b", {"legacy": True, "packed": True}),
    ("mamba2-130m", {}),
])
def test_process_view_derives_packed_like_the_engine(model_bank, name, kw):
    """The process backend's engine view derives ``packed`` from the same
    options, so the router keys requests on the worker's real path."""
    from repro.serving.cluster import _RemoteEngineFacade

    cfg = nodrop(ARCHITECTURES[name].reduced())
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64, **kw)
    view = _RemoteEngineFacade(None, {"cfg": cfg, "engine_kw": kw})
    assert view.packed == eng.packed
