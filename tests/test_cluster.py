"""Cluster-tier invariants: router policies, SLO telemetry math, queue
stage accounting, adaptive in-flight window, device-side sampling,
loadgen determinism, and 2-replica token identity vs independent
engines."""

import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.metrics import jain_index, percentile, slo_summary
from repro.serving import (
    Gateway,
    Router,
    ServingCluster,
    ServingEngine,
    load_trace,
    poisson_schedule,
    run_open_loop,
    save_trace,
    trace_schedule,
)
from repro.serving.cluster import replica_pod_slices
from repro.serving.request import Request, Response


def _cfg():
    return get_config("llama3-8b").reduced()


def _requests(cfg, lens, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


def _drain(engine, reqs, max_steps=50_000):
    for r in reqs:
        engine.submit(r, time.perf_counter())
    out = engine.run_until_drained(max_steps=max_steps)
    assert len(out) == len(reqs)
    return out


# --------------------------------------------------------------------------- #
# Telemetry math: golden percentiles, Jain index, warmup-aware SLO summary.
# --------------------------------------------------------------------------- #
def test_percentile_golden():
    xs = list(range(1, 101))  # 1..100
    assert percentile(xs, 0.50) == pytest.approx(50.5)
    assert percentile(xs, 0.95) == pytest.approx(95.05)
    assert percentile(xs, 0.99) == pytest.approx(99.01)
    assert percentile([7.0], 0.99) == 7.0
    assert percentile([], 0.5) == 0.0


def test_jain_index_golden():
    assert jain_index([5, 5, 5, 5]) == pytest.approx(1.0)
    assert jain_index([12, 0, 0, 0]) == pytest.approx(0.25)  # one-hot: 1/n
    assert jain_index([1, 3]) == pytest.approx(16 / 20)
    assert jain_index([]) == 1.0
    assert jain_index([0, 0]) == 1.0  # nothing routed: vacuous balance


def test_slo_summary_warmup_and_tpot():
    def rsp(ttft, total, n_tokens, queue=0.0):
        return Response(request_id=0, tokens=list(range(n_tokens)),
                        ttft_s=ttft, total_s=total,
                        stage_s={"queue": queue})

    # one cold outlier + four steady completions
    rs = [rsp(10.0, 20.0, 2, queue=9.0)] + [
        rsp(0.1 * i, 0.1 * i + 0.9, 10, queue=0.01 * i) for i in (1, 2, 3, 4)
    ]
    warm = slo_summary(rs, warmup=1)
    assert warm["n"] == 4 and warm["warmup_dropped"] == 1
    assert warm["ttft_s"]["p50"] == pytest.approx(0.25)
    # tpot = (total - ttft) / (tokens - 1) = 0.9 / 9 for every warm response
    assert warm["tpot_s"]["p99"] == pytest.approx(0.1)
    assert warm["queue_s"]["p50"] == pytest.approx(0.025)
    # without warmup the outlier dominates the tail
    cold = slo_summary(rs, warmup=0)
    assert cold["ttft_s"]["p99"] > 5.0
    with pytest.raises(ValueError):
        slo_summary(rs, warmup=-1)


# --------------------------------------------------------------------------- #
# Queue stage: the submit -> admission gap is charged on every path.
# --------------------------------------------------------------------------- #
def test_queue_stage_charged_single_engine(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    out = _drain(eng, _requests(cfg, [8, 9, 10], max_new=3))
    recs = {r.request_id: r for r in eng.store.records}
    for rsp in out:
        rec = recs[rsp.request_id]
        assert rec.stage_s["queue"] >= 0.0
        # the stage reaches the response breakdown and stays inside total
        assert rsp.stage_s["queue"] == rec.stage_s["queue"]
        assert rsp.total_s + 1e-9 >= sum(rsp.stage_s.values())
    # max_batch=1: later admissions waited on earlier requests' service,
    # so their queue charge dominates the first request's
    by_arrival = sorted(recs.values(), key=lambda r: r.t_issue)
    assert by_arrival[-1].stage_s["queue"] > by_arrival[0].stage_s["queue"]


def test_queue_stage_charged_legacy_loop(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64, legacy=True)
    _drain(eng, _requests(cfg, [8, 9], max_new=2))
    assert all("queue" in r.stage_s for r in eng.store.records)


# --------------------------------------------------------------------------- #
# Adaptive in-flight window: no overshoot past the live token budget.
# --------------------------------------------------------------------------- #
def test_adaptive_window_saves_dispatches(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)

    def run(adaptive):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                            inflight=4, adaptive_window=adaptive)
        reqs = _requests(cfg, [5, 8, 13, 21], max_new=3, seed=7)
        _drain(eng, reqs)
        return [tuple(r.generated) for r in reqs], eng

    toks_a, eng_a = run(True)
    toks_f, eng_f = run(False)
    assert toks_a == toks_f  # the cap only removes provably-dead steps
    assert eng_a.useful_steps == eng_f.useful_steps
    # fixed window: up to inflight-1 overshoot per finishing request;
    # adaptive: the window never exceeds the live outstanding budget
    assert eng_a.decode_steps < eng_f.decode_steps
    assert eng_a.decode_steps - eng_a.useful_steps < \
        eng_f.decode_steps - eng_f.useful_steps


# --------------------------------------------------------------------------- #
# Device-side sampling: greedy default, top_k=1 degeneracy, seeded streams.
# --------------------------------------------------------------------------- #
def test_sampling_top_k_one_is_greedy(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)

    def run(**kw):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64, **kw)
        reqs = _requests(cfg, [5, 9, 14], max_new=5, seed=2)
        _drain(eng, reqs)
        return [tuple(r.generated) for r in reqs]

    greedy = run()
    assert run(temperature=3.0, top_k=1, sample_seed=11) == greedy


def test_sampling_seeded_and_distinct(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)

    def run(**kw):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64, **kw)
        reqs = _requests(cfg, [5, 9, 14, 20], max_new=6, seed=2)
        _drain(eng, reqs)
        return [tuple(r.generated) for r in reqs]

    greedy = run()
    s3a = run(temperature=5.0, sample_seed=3)
    s3b = run(temperature=5.0, sample_seed=3)
    s4 = run(temperature=5.0, sample_seed=4)
    assert s3a == s3b  # the threaded PRNG key is the only entropy source
    assert s3a != greedy
    assert s3a != s4


def test_sampling_rejects_legacy_and_bad_args(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)
    with pytest.raises(ValueError, match="legacy"):
        ServingEngine(model, params, max_batch=1, max_seq=64, legacy=True,
                      temperature=1.0)
    with pytest.raises(ValueError, match="temperature"):
        ServingEngine(model, params, max_batch=1, max_seq=64,
                      temperature=-1.0)


# --------------------------------------------------------------------------- #
# Router policies.
# --------------------------------------------------------------------------- #
def test_router_validates_policy():
    with pytest.raises(ValueError, match="unknown policy"):
        Router("random")


def test_replica_pod_slices():
    # enough pods: disjoint slices
    assert replica_pod_slices(4, 2, 2) == [(0, 1), (2, 3)]
    assert replica_pod_slices(2, 2, 1) == [(0,), (1,)]
    # degenerate single-device backend: slices overlap modulo the axis
    assert replica_pod_slices(1, 2, 2) == [(0,), (0,)]


@pytest.mark.parametrize("backend,make,raises", [
    ("cpu", "cluster", False),
    ("cpu", "pod", False),
    ("tpu", "cluster", True),
    ("tpu", "pod", True),
])
def test_meshes_clamp_only_on_cpu(monkeypatch, backend, make, raises):
    """On the CPU backend a mesh asking for more devices than exist clamps
    (the single-device tests run every tier); on a chip it raises rather
    than stack stages or replicas onto one chip in silence."""
    from repro.launch.mesh import make_cluster_mesh, make_serving_pod_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n = len(jax.devices())
    build = {
        "cluster": lambda: make_cluster_mesh(n + 1, 2),
        "pod": lambda: make_serving_pod_mesh(),
    }[make]
    if n >= 2 and make == "pod":
        pytest.skip("the default 2-pod mesh fits this backend")
    if raises:
        with pytest.raises(ValueError, match="present"):
            build()
    else:
        assert build().shape["pod"] == n


def test_least_loaded_beats_round_robin_on_skewed_trace(model_bank):
    """Deterministic skew: one long-budget request, then a burst of
    1-token requests. Round-robin blindly parks half the lights behind
    the heavy decode (head-of-line blocking: each waits a full heavy
    service in 'queue'); least_loaded reads outstanding token budgets and
    routes every light around the busy replica, so the tail queue wait
    collapses from ~one heavy service to a few light services.

    Replicas are warmed so the queue waits measure steady-state service,
    not first-touch compiles (which would drown the policy effect). Note
    the shape of the skew: on a time-shared test CPU, balanced replicas
    run each other's steps slower (service stretch cancels backlog
    splitting), so head-of-line avoidance — not heavy-splitting — is the
    effect a single host can honestly measure in wall clock."""
    cfg = _cfg()
    model, params = model_bank(cfg)
    heavy, light = 24, 1
    n_light = 6

    def run(policy):
        cl = ServingCluster.build(model, params, n_replicas=2,
                                  engine="fused", policy=policy,
                                  max_batch=1, max_seq=32, warmup=True)
        reqs = _requests(cfg, [8] * (1 + n_light), max_new=light, seed=5)
        reqs[0].max_new_tokens = heavy
        for r in reqs:
            cl.submit(r)
        cl.run_until_drained(max_steps=100_000)
        routed = [rep.routed for rep in cl.replicas]
        slo = cl.telemetry()["slo"]
        return routed, slo

    rr_routed, rr_slo = run("round_robin")
    ll_routed, ll_slo = run("least_loaded")
    # deterministic routing: RR alternates blindly (3 lights land behind
    # the heavy on replica 0); least_loaded sends every light around it
    assert rr_routed == [4, 3]
    assert ll_routed == [1, n_light]
    # the latency claim: tail queue wait (and with it tail TTFT) drops by
    # ~one heavy service time, and the queue stage IS the difference —
    # prefill/decode costs are policy-independent
    assert ll_slo["queue_s"]["p99"] < rr_slo["queue_s"]["p99"]
    assert ll_slo["ttft_s"]["p99"] < rr_slo["ttft_s"]["p99"]
    ttft_gain = rr_slo["ttft_s"]["p99"] - ll_slo["ttft_s"]["p99"]
    queue_gain = rr_slo["queue_s"]["p99"] - ll_slo["queue_s"]["p99"]
    assert queue_gain == pytest.approx(ttft_gain, rel=0.35)


def test_jsq_beats_round_robin_on_skewed_trace(model_bank):
    """JSQ reads queue feedback (counts, not budgets), so it needs
    temporal spacing to act: with one long decode holding replica 0's
    slot and lights arriving slowly enough for replica 1 to drain, jsq
    routes every light around the busy replica while round-robin blindly
    parks half of them behind it. The arrival gap is calibrated to the
    measured light service time, so the load ratios (heavy spans many
    gaps; the light stream stays far below one replica's capacity) hold
    on any machine speed."""
    cfg = _cfg()
    model, params = model_bank(cfg)
    # calibrate: lights are prefill-only (max_new=1) on a warmed engine
    eng = ServingEngine(model, params, max_batch=1, max_seq=128,
                        warmup=True)
    t0 = time.perf_counter()
    _drain(eng, _requests(cfg, [8] * 4, max_new=1, seed=11))
    light_s = (time.perf_counter() - t0) / 4
    gap = max(0.02, 6.0 * light_s)
    entries = [{"t": 0.0, "prompt_len": 8, "max_new": 96}] + [
        {"t": round(i * gap, 6), "prompt_len": 8, "max_new": 1}
        for i in range(1, 9)
    ]

    def run(policy):
        cl = ServingCluster.build(model, params, n_replicas=2,
                                  engine="fused", policy=policy,
                                  max_batch=1, max_seq=128, warmup=True)
        sched = trace_schedule(entries, vocab=cfg.vocab_size, seed=13)
        assert len(run_open_loop(cl, sched)) == len(entries)
        return cl.telemetry()["slo"]

    rr, jq = run("round_robin"), run("jsq")
    assert jq["ttft_s"]["p99"] < rr["ttft_s"]["p99"]
    # the win is pre-admission queueing, nothing else
    assert jq["queue_s"]["p99"] < rr["queue_s"]["p99"]


def test_jsq_spreads_a_queue_buildup(model_bank):
    """With replica 0 pre-loaded, jsq must send new work to the empty
    replica while round-robin would alternate blindly."""
    cfg = _cfg()
    model, params = model_bank(cfg)
    cl = ServingCluster.build(model, params, n_replicas=2, engine="fused",
                              policy="jsq", max_batch=1, max_seq=64)
    pre = _requests(cfg, [8, 8, 8], max_new=4, seed=1)
    for r in pre:  # jsq walks the backlog: 0, 1, 0 (ties -> lowest index)
        cl.submit(r)
    assert [r.routed for r in cl.replicas] == [2, 1]
    late = _requests(cfg, [8], max_new=4, seed=2)[0]
    assert cl.submit(late) == 1  # shorter queue wins
    cl.run_until_drained(max_steps=100_000)


def test_affinity_reduces_prefill_compiles(model_bank):
    """Bucket-sticky routing: each replica compiles only its buckets,
    round-robin scatters every bucket onto every replica."""
    cfg = _cfg()
    model, params = model_bank(cfg)
    # 4 distinct pow2 buckets (16/32/64/128 for min_bucket=16), adjacent
    # same-bucket pairs so round-robin's parity splits every pair
    lens = [10, 12, 20, 24, 40, 48, 80, 96]

    def compiles(policy):
        cl = ServingCluster.build(model, params, n_replicas=2,
                                  engine="fused", policy=policy,
                                  max_batch=2, max_seq=128, packed=False)
        _drain(cl, _requests(cfg, lens, max_new=2, seed=3),
               max_steps=100_000)
        return sum(r.engine.prefill_compile_count for r in cl.replicas)

    assert compiles("affinity") == 4  # each bucket compiled exactly once
    assert compiles("round_robin") == 8  # every bucket on both replicas


# --------------------------------------------------------------------------- #
# Token identity: a 2-replica cluster is numerically invisible.
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode_name", ["direct_hbm", "direct_dma"])
def test_cluster_tokens_match_independent_engines(mode_name, model_bank):
    from repro.core.transfer import TransferMode
    from repro.serving import DisaggregatedEngine

    cfg = _cfg()
    model, params = model_bank(cfg)
    mode = TransferMode(mode_name)
    lens = [5, 9, 14, 20, 26, 33]
    kw = dict(max_batch=2, max_seq=64)

    cl = ServingCluster.build(model, params, n_replicas=2, engine="disagg",
                              policy="round_robin", transfer_mode=mode, **kw)
    cl_reqs = _requests(cfg, lens, max_new=4, seed=9)
    _drain(cl, cl_reqs, max_steps=100_000)

    # the same requests on two standalone engines, split the way
    # round-robin routed them (even indices -> engine 0, odd -> engine 1)
    solo_reqs = _requests(cfg, lens, max_new=4, seed=9)
    for k in range(2):
        eng = DisaggregatedEngine(model, params, transfer_mode=mode, **kw)
        _drain(eng, solo_reqs[k::2], max_steps=100_000)

    assert [tuple(r.generated) for r in cl_reqs] == \
        [tuple(r.generated) for r in solo_reqs]


# --------------------------------------------------------------------------- #
# Cluster surface: Gateway composition and merged records/store.
# --------------------------------------------------------------------------- #
def test_gateway_over_cluster(model_bank):
    from repro.core.transport import Transport

    cfg = _cfg()
    model, params = model_bank(cfg)
    cl = ServingCluster.build(model, params, n_replicas=2, engine="fused",
                              policy="round_robin", max_batch=1, max_seq=64)
    gw = Gateway(cl, first_hop=Transport.TCP)
    reqs = _requests(cfg, [8, 9], max_new=2, seed=4)
    out = _drain(gw, reqs)
    assert len(cl.store.records) == 2
    for rsp in out:
        rec = cl._records[rsp.request_id]
        # the gateway charged BOTH hops onto the stored record through the
        # cluster's merged-records view
        assert rec.stage_s["response"] == pytest.approx(
            rsp.stage_s["response"], rel=1e-12
        )
        assert rec.cpu_s > 0  # TCP keeps the CPU on the data path
    assert cl._records.get(-1) is None
    with pytest.raises(KeyError):
        cl._records[-1]


def test_cluster_build_validates():
    with pytest.raises(ValueError, match="at least one replica"):
        ServingCluster([])


# --------------------------------------------------------------------------- #
# Load generation: seeded determinism, trace round-trip, open-loop drive.
# --------------------------------------------------------------------------- #
def test_poisson_schedule_deterministic():
    a = poisson_schedule(256, rate_rps=100, n_requests=6, seed=42)
    b = poisson_schedule(256, rate_rps=100, n_requests=6, seed=42)
    c = poisson_schedule(256, rate_rps=100, n_requests=6, seed=43)
    assert [x.t for x in a] == [x.t for x in b]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.request.prompt_tokens,
                                      y.request.prompt_tokens)
    assert [x.t for x in a] != [x.t for x in c]
    assert all(a[i].t <= a[i + 1].t for i in range(len(a) - 1))
    with pytest.raises(ValueError, match="rate_rps"):
        poisson_schedule(256, rate_rps=0, n_requests=1)


def test_trace_schedule_roundtrip(tmp_path):
    entries = [
        {"t": 0.0, "prompt_len": 8, "max_new": 2},
        {"t": 0.5, "prompt_len": 16, "max_new": 4, "priority": 1},
    ]
    path = tmp_path / "trace.jsonl"
    save_trace(path, entries)
    assert load_trace(path) == entries
    sched = trace_schedule(load_trace(path), vocab=256, seed=7)
    again = trace_schedule(entries, vocab=256, seed=7)
    assert [a.t for a in sched] == [0.0, 0.5]
    np.testing.assert_array_equal(sched[1].request.prompt_tokens,
                                  again[1].request.prompt_tokens)
    assert sched[1].request.priority == 1
    with pytest.raises(ValueError, match="non-decreasing"):
        trace_schedule([{"t": 1.0, "prompt_len": 4},
                        {"t": 0.5, "prompt_len": 4}], vocab=256)


def test_open_loop_drives_engine_and_charges_queue(model_bank):
    cfg = _cfg()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=1, max_seq=64)
    sched = trace_schedule(
        [{"t": 0.0, "prompt_len": 8, "max_new": 3},
         {"t": 0.0, "prompt_len": 9, "max_new": 3},
         {"t": 0.01, "prompt_len": 10, "max_new": 3}],
        vocab=cfg.vocab_size, seed=0,
    )
    out = run_open_loop(eng, sched)
    assert len(out) == 3
    assert all("queue" in r.stage_s for r in out)
    # arrival stamps follow the schedule: every request was submitted, and
    # the max_batch=1 engine serialized them, so someone waited
    assert max(r.stage_s["queue"] for r in out) > 0.0


def test_closed_loop_baseline_on_cluster(model_bank):
    from repro.serving import run_closed_loop_baseline

    cfg = _cfg()
    model, params = model_bank(cfg)
    cl = ServingCluster.build(model, params, n_replicas=2, engine="fused",
                              policy="least_loaded", max_batch=2, max_seq=64)
    done = run_closed_loop_baseline(cl, cfg.vocab_size, n_clients=3,
                                    requests_per_client=2, prompt_len=12,
                                    max_new_tokens=3)
    assert len(done) == 6
    assert sum(r.routed for r in cl.replicas) == 6
