"""Disaggregated prefill->decode tier: the pod-boundary handoff must
preserve decode tokens (DIRECT_HBM / DIRECT_DMA bit-exact; HOST_STAGED
within the documented int8 tolerance) and charge the 'transfer' stage into
each request's TTFT.

Runs on the 1-pod degenerate mesh (one CPU device): the full tier —
tiling, collective permute, quantization, metadata round-trip, splice —
executes; CI's 8-forced-host-device smoke covers the real 2-pod
collective."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.transfer import MODE_TRANSPORT, TransferMode
from repro.serving import DisaggregatedEngine, ServingEngine
from repro.serving.request import Request


def _requests(cfg, lens, max_new=5, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


def _drain(eng, cfg, lens, max_new=5, seed=7):
    reqs = _requests(cfg, lens, max_new, seed)
    for r in reqs:
        eng.submit(r, time.perf_counter())
    out = eng.run_until_drained()
    assert len(out) == len(reqs)
    return reqs, out


@pytest.mark.parametrize(
    "mode", [TransferMode.DIRECT_HBM, TransferMode.DIRECT_DMA]
)
def test_disagg_tokens_identical_to_single_engine(mode, model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 9, 17, 26]
    kw = dict(max_batch=2, max_seq=64)
    base, _ = _drain(ServingEngine(model, params, **kw), cfg, lens)
    dis, _ = _drain(
        DisaggregatedEngine(model, params, transfer_mode=mode, **kw),
        cfg, lens,
    )
    assert [r.generated for r in dis] == [r.generated for r in base]


def test_disagg_host_staged_within_quantization_tolerance(model_bank):
    """HOST_STAGED requantizes the KV payload to int8, so later tokens may
    drift — but every request must complete with a full budget, and the
    FIRST token (computed pre-handoff, carried as int metadata) must be
    bit-exact."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 9, 17, 26]
    kw = dict(max_batch=2, max_seq=64)
    base, _ = _drain(ServingEngine(model, params, **kw), cfg, lens)
    dis, out = _drain(
        DisaggregatedEngine(
            model, params, transfer_mode=TransferMode.HOST_STAGED, **kw
        ),
        cfg, lens,
    )
    for b, d in zip(base, dis):
        assert len(d.generated) == len(b.generated)
        assert d.generated[0] == b.generated[0]  # metadata crosses exact
        assert all(0 <= t < cfg.vocab_size for t in d.generated)


def test_disagg_exact_path_feature_request(model_bank):
    """vlm (feature-frontend) requests route to exact prefill and their
    cache's true length is feature_frames + prompt_tokens: the prefix
    slice must come from the MODEL-returned length — slicing to the
    prompt length alone would cut live KV off the wire (frames 12 +
    prompt 6 = 18 > the 16-slot block a 6-token prefix would round to)
    and silently break token identity."""
    from conftest import nodrop

    from repro.models import FRONTEND_DIM

    cfg = nodrop(get_config("pixtral-12b").reduced())
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    kw = dict(max_batch=2, max_seq=32)

    def mk(seed=11):
        rng = np.random.default_rng(seed)
        return [Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, 6, dtype=np.int32),
            features=rng.normal(size=(1, 12, FRONTEND_DIM)).astype(
                np.float32),
            max_new_tokens=4,
        )]

    def drain(eng, reqs):
        for r in reqs:
            eng.submit(r, time.perf_counter())
        out = eng.run_until_drained()
        assert len(out) == len(reqs)
        return reqs

    base = drain(ServingEngine(model, params, **kw), mk())
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM, **kw
    )
    dis = drain(eng, mk())
    assert [r.generated for r in dis] == [r.generated for r in base]
    # the handoff accounted the full frames+prompt prefix, not prompt-only
    assert eng.handoff_request_bytes > eng.request_handoff_bytes(6)


def test_disagg_exact_path_ssm_arch(model_bank):
    """SSM stacks route to exact prefill; their static conv/state leaves
    must survive the handoff too (DIRECT_HBM is bit-exact)."""
    from conftest import nodrop

    cfg = nodrop(get_config("mamba2-130m").reduced())
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 9, 14]
    kw = dict(max_batch=2, max_seq=32)
    base, _ = _drain(ServingEngine(model, params, **kw), cfg, lens,
                     max_new=4)
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM, **kw
    )
    assert not eng.bucketed_prefill
    dis, _ = _drain(eng, cfg, lens, max_new=4)
    assert [r.generated for r in dis] == [r.generated for r in base]


def test_disagg_charges_transfer_stage_and_ttft(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.HOST_STAGED,
        max_batch=1, max_seq=32,
    )
    reqs, out = _drain(eng, cfg, [8], max_new=3)
    rec = eng.store.records[0]
    assert rec.stage_s["transfer"] > 0
    assert rec.cpu_s > 0  # TCP keeps the CPU on the handoff data path
    assert rec.transfer_wall_s > 0  # the collective really ran
    # on host-device runs the charge is the profile-modeled hop on this
    # request's share of the moved wire bytes — the sole rider of the one
    # handoff owns all of handoff_wire_bytes
    hop = MODE_TRANSPORT[TransferMode.HOST_STAGED]
    want = eng.profile.handoff_time(hop, eng.handoff_wire_bytes)
    assert rec.stage_s["transfer"] == pytest.approx(want, rel=1e-9)
    # ...and it is folded into the reported ttft in place of the measured
    # (non-representative) collective wall, alongside the modeled ingress
    ingress = rec.stage_s["request"] + rec.stage_s.get("copy_in", 0.0)
    raw = reqs[0].t_first_token - reqs[0].t_arrival
    assert out[0].ttft_s == pytest.approx(
        raw + ingress - rec.transfer_wall_s + want, abs=1e-9
    )
    assert eng.handoffs == 1
    assert eng.handoff_wire_bytes > 0
    assert eng.handoff_request_bytes > 0


def test_disagg_batched_admission_swaps_full_handoff_wall(model_bank):
    """Two requests co-admitted in ONE bucket both wait the FULL collective
    wall before their first token — the modeled-charge ttft swap must
    remove all of it, not a 1/N share, and fold in each request's own
    modeled hop."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.DIRECT_HBM,
        max_batch=2, max_seq=32,
    )
    reqs, out = _drain(eng, cfg, [8, 9], max_new=2)  # same pow2 bucket
    assert eng.handoffs == 1  # one collective carried both requests
    by_id = {r.request_id: r for r in out}
    tot = sum(eng.request_handoff_bytes(len(r.prompt_tokens)) for r in reqs)
    for req in reqs:
        rec = next(r for r in eng.store.records
                   if r.request_id == req.request_id)
        assert rec.transfer_wall_s == pytest.approx(eng.handoff_wall_s)
        # modeled hop on this request's prefix-proportional share of the
        # bytes the collective moved
        share = (eng.handoff_wire_bytes
                 * eng.request_handoff_bytes(len(req.prompt_tokens)) / tot)
        want = eng.profile.handoff_time(
            MODE_TRANSPORT[TransferMode.DIRECT_HBM], share,
        )
        ingress = rec.stage_s["request"] + rec.stage_s.get("copy_in", 0.0)
        raw = req.t_first_token - req.t_arrival
        assert by_id[req.request_id].ttft_s == pytest.approx(
            raw + ingress - eng.handoff_wall_s + want, abs=1e-9
        )


def test_disagg_modeled_hop_ordering(model_bank):
    """Per-request handoff charge must reproduce the paper's ordering:
    last-hop hardware acceleration is cheapest (GDR <= RDMA <= TCP)."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    charge = {}
    for mode in TransferMode:
        eng = DisaggregatedEngine(
            model, params, transfer_mode=mode, max_batch=2, max_seq=64,
        )
        _drain(eng, cfg, [9, 21, 30], max_new=2)
        recs = eng.store.records
        charge[mode] = sum(r.stage_s["transfer"] for r in recs) / len(recs)
    assert (charge[TransferMode.DIRECT_HBM]
            <= charge[TransferMode.DIRECT_DMA]
            <= charge[TransferMode.HOST_STAGED])


@pytest.mark.parametrize(
    "mode", [TransferMode.DIRECT_HBM, TransferMode.DIRECT_DMA]
)
def test_prefix_only_handoff_scales_with_occupancy(mode, model_bank):
    """The collective must move the admitted rows' KV prefix, not the
    max_batch x max_seq pool tree: one admitted short request costs exactly
    the per-row share of a full-pool admission and a small fraction of the
    padded admission tree (the pre-fix payload), with decode tokens
    unchanged."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    kw = dict(max_batch=4, max_seq=64)
    lens = [5, 5, 5, 5]  # one pow2 bucket: a single full-pool admission

    base, _ = _drain(ServingEngine(model, params, **kw), cfg, lens,
                     max_new=3)
    eng1 = DisaggregatedEngine(model, params, transfer_mode=mode, **kw)
    _drain(eng1, cfg, [5], max_new=3)
    assert eng1.handoffs == 1
    engN = DisaggregatedEngine(model, params, transfer_mode=mode, **kw)
    disN, _ = _drain(engN, cfg, lens, max_new=3)
    assert engN.handoffs == 1  # all four rode one collective
    assert [r.generated for r in disN] == [r.generated for r in base]

    # per-row scaling: 4 co-admitted rows cost exactly 4x one row (same
    # rounded prefix, per-row metadata)
    assert engN.handoff_wire_bytes == 4 * eng1.handoff_wire_bytes

    # acceptance: a single short-prompt handoff moves well under 1/4 of
    # the padded max_batch x max_seq tree the collective used to permute
    assert eng1.handoff_wire_bytes < eng1.padded_tree_wire_bytes() / 4

    # useful-prefix accounting never exceeds what the wire moved (equal up
    # to the handoff_block rounding)
    assert eng1.handoff_request_bytes <= eng1.handoff_wire_bytes
    assert engN.handoff_request_bytes <= engN.handoff_wire_bytes


def test_handoff_wire_bytes_equals_moved_payload(model_bank):
    """``handoff_wire_bytes`` must equal ``payload_wire_bytes`` of exactly
    what the collective permutes — the [rows, prefix_blocks] cache slice
    plus those rows' slot metadata — under every mechanism."""
    from repro.core.transfer import payload_wire_bytes
    from repro.models import kvcache as kvc

    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    lens = [5, 21]  # distinct pow2 buckets: one single-row handoff each
    for mode in TransferMode:
        eng = DisaggregatedEngine(
            model, params, transfer_mode=mode, max_batch=2, max_seq=64,
            packed=False,
        )
        _drain(eng, cfg, lens, max_new=2)
        assert eng.handoffs == 2
        expected = 0
        for true_len in lens:
            sliced = kvc.slice_cache(
                eng.pool.caches, 1, eng.handoff_prefix(true_len)
            )
            meta = {k: jnp.zeros((1,), jnp.int32)
                    for k in ("lengths", "next_tokens", "slot_idx",
                              "max_new")}
            expected += payload_wire_bytes(
                {"caches": sliced, "meta": meta}, mode
            )
        assert eng.handoff_wire_bytes == expected


def test_host_staged_cpu_pinned_to_wire_bytes(model_bank):
    """TCP keeps the CPU on the handoff data path: the per-request cpu_s
    shares must sum to EXACTLY the bytes the collective moved — pre-fix,
    cpu_s was charged on per-request prefix bytes while the measured wall
    (and wire counter) reflected the padded admission tree."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    eng = DisaggregatedEngine(
        model, params, transfer_mode=TransferMode.HOST_STAGED,
        max_batch=2, max_seq=64,
    )
    _drain(eng, cfg, [5, 9, 17], max_new=2)
    assert eng.handoffs >= 2  # co-admitted bucket + trailing admission
    total_cpu = sum(r.cpu_s for r in eng.store.records)
    assert total_cpu == pytest.approx(
        eng.handoff_wire_bytes * eng.profile.tcp_cpu_per_byte, rel=1e-9
    )


def test_handoff_block_granularity_knob(model_bank):
    """The moved prefix rounds up to a power of two floored at
    handoff_block: block=max_seq degenerates to a full-ring transfer,
    block=1 moves the next-pow2 prefix."""
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg, dtype=jnp.float32, seed=1)
    wire = {}
    for blk in (1, 16, 64):
        eng = DisaggregatedEngine(
            model, params, transfer_mode=TransferMode.DIRECT_HBM,
            max_batch=2, max_seq=64, handoff_block=blk,
        )
        _drain(eng, cfg, [5], max_new=2)
        wire[blk] = eng.handoff_wire_bytes
    assert wire[1] < wire[16] < wire[64]
    with pytest.raises(ValueError, match="handoff_block"):
        DisaggregatedEngine(model, params, handoff_block=0)


def test_disagg_rejects_legacy(model_bank):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    with pytest.raises(ValueError, match="legacy"):
        DisaggregatedEngine(model, params, legacy=True)
