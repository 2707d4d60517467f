"""Threaded host pipeline (EnginePipeline): token identity with the
synchronous step() loop, record conservation, and failure surfacing."""

import numpy as np
import pytest

from benchmarks.serving import make_requests, micro_config


@pytest.fixture(scope="module")
def served():
    """One micro model + params shared across the module's engines."""
    import jax

    from repro.models.model import Model

    cfg = micro_config()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, **kw):
    from repro.serving.engine import ServingEngine

    kw.setdefault("max_batch", 3)
    kw.setdefault("max_seq", 64)
    return ServingEngine(model, params, **kw)


def test_pipeline_token_identity_and_conservation(served):
    """The three-thread pipeline must produce byte-identical tokens to the
    synchronous engine on the same requests, and emit exactly one response
    per submission (the no-reorder/no-drop invariant)."""
    from repro.serving.engine import EnginePipeline

    cfg, model, params = served
    lens = [8, 12, 20, 5, 16, 9, 30, 7]

    eng = _engine(model, params)
    reqs = make_requests(cfg, lens, 6, seed=3)
    for r in reqs:
        eng.submit(r)
    base = {r.request_id: r.tokens for r in eng.run_until_drained()}

    eng2 = _engine(model, params)
    with EnginePipeline(eng2) as pipe:
        assert pipe.async_draining
        reqs2 = make_requests(cfg, lens, 6, seed=3)
        for r in reqs2:
            pipe.submit(r)
        out = pipe.run_until_drained(max_steps=200_000)
        # conservation: one response per submission, nothing dropped or
        # duplicated by the stale-snapshot handling across thread handoffs
        assert pipe.submitted == len(reqs2)
        assert pipe.emitted == len(reqs2)
        assert len(out) == len(reqs2)
        assert sorted(r.request_id for r in out) == \
            sorted(r.request_id for r in reqs2)
        assert pipe.idle
        # identity: align by submission order (fresh ids per run)
        a = [base[i] for i in sorted(base)]
        b = {r.request_id: r.tokens for r in out}
        b = [b[i] for i in sorted(b)]
        assert a == b
        snap = pipe.load_snapshot()
        assert snap["idle"] and snap["submitted"] == snap["emitted"]
        assert snap["submitted_bytes"] == sum(r.payload_bytes for r in reqs2)


def test_pipeline_records_complete(served):
    """Every finished request's record carries a t_done and the inference
    stage — finalize ran exactly once per request despite the handoffs."""
    from repro.serving.engine import EnginePipeline

    cfg, model, params = served
    eng = _engine(model, params)
    with EnginePipeline(eng) as pipe:
        reqs = make_requests(cfg, [8, 16, 24], 5, seed=1)
        for r in reqs:
            pipe.submit(r)
        out = pipe.run_until_drained(max_steps=200_000)
        assert len(out) == len(reqs)
        assert len(pipe.store.records) == len(reqs)
        for rec in pipe.store.records:
            assert rec.t_done > rec.t_issue
            assert rec.stage_s.get("inference", 0.0) >= 0.0
            assert "preprocess" in rec.stage_s  # the prefill stage
            assert "queue" in rec.stage_s


def test_pipeline_drains_chunked_request_met_at_prefill(served):
    """A chunked prompt whose budget its prefill token meets is placed by
    the final chunk step with nothing left to decode: the pipeline must
    still emit it before it reports idle."""
    from repro.serving.engine import EnginePipeline

    cfg, model, params = served
    reqs = make_requests(cfg, [40, 8], 1, seed=3)
    base = _engine(model, params, prefill_chunk=16)
    for r in make_requests(cfg, [40, 8], 1, seed=3):
        base.submit(r)
    want = sorted(r.tokens for r in base.run_until_drained())
    eng = _engine(model, params, prefill_chunk=16)
    with EnginePipeline(eng) as pipe:
        for r in reqs:
            pipe.submit(r)
        out = pipe.run_until_drained(max_steps=200_000)
        assert pipe.idle
        assert sorted(r.tokens for r in out) == want
        assert len(out) == len(reqs) == pipe.emitted


def test_pipeline_thread_failure_surfaces(served):
    """A crash on a pipeline thread must re-raise on the caller's next
    touch (with the worker traceback), never hang the facade."""
    from repro.serving.engine import EnginePipeline

    cfg, model, params = served
    eng = _engine(model, params)

    def boom():
        raise RuntimeError("synthetic admission failure")

    pipe = EnginePipeline(eng)
    try:
        eng._admit = boom
        with pytest.raises(RuntimeError, match="synthetic admission"):
            deadline = 200
            while deadline:
                pipe.idle  # noqa: B018 — poking the facade re-raises
                deadline -= 1
                import time

                time.sleep(0.01)
            raise AssertionError("pipeline failure never surfaced")
    finally:
        pipe.close()


def test_pipeline_rejects_legacy_and_bad_backlog(served):
    from repro.serving.engine import EnginePipeline

    cfg, model, params = served
    legacy = _engine(model, params, legacy=True)
    with pytest.raises(ValueError, match="legacy"):
        EnginePipeline(legacy)
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="backlog"):
        EnginePipeline(eng, backlog=0)
    # close is idempotent
    pipe = EnginePipeline(eng)
    pipe.close()
    pipe.close()


def test_merge_record_streams_skew_tolerance():
    """Rebasing with per-stream clock offsets must put records on one
    timeline: absolute stamps shift by the offset, durations (stage_s,
    t_done - t_issue) are untouched, order is completion order."""
    from repro.core.metrics import merge_record_streams
    from repro.core.profiler import RequestRecord

    def rec(rid, t0, dur):
        r = RequestRecord(request_id=rid, client_id=0, priority=0,
                          t_issue=t0, bytes_in=4, bytes_out=4)
        r.t_done = t0 + dur
        r.add("inference", dur)
        return r

    # stream B's process booted with a perf_counter epoch 1000s ahead
    a = [rec(0, 10.0, 1.0), rec(2, 12.0, 2.0)]
    b = [rec(1, 1010.5, 1.0), rec(3, 1013.0, 0.5)]
    merged = merge_record_streams([a, b], offsets=[0.0, 1000.0])
    # rebased completions: 11.0, 11.5, 13.5, 14.0
    assert [r.request_id for r in merged] == [0, 1, 3, 2]
    by_id = {r.request_id: r for r in merged}
    assert by_id[1].t_issue == pytest.approx(10.5)
    assert by_id[3].t_done == pytest.approx(13.5)
    # durations are skew-invariant
    for src in (*a, *b):
        m = by_id[src.request_id]
        assert m.t_done - m.t_issue == pytest.approx(src.t_done - src.t_issue)
        assert m.stage_s == src.stage_s
    # sources not mutated
    assert b[0].t_issue == pytest.approx(1010.5)
    with pytest.raises(ValueError, match="offsets length"):
        merge_record_streams([a], offsets=[0.0, 1.0])


def test_cluster_telemetry_matches_single_process_golden():
    """SLO percentiles over responses merged from multiple replicas must
    equal the golden single-list math — merging adds no distortion."""
    from repro.core.metrics import percentile, slo_summary
    from repro.serving.request import Response

    def rsp(rid, ttft, total, n_tok):
        return Response(request_id=rid, tokens=list(range(n_tok)),
                        ttft_s=ttft, total_s=total, stage_s={"queue": 0.01})

    per_replica = [
        [rsp(0, 0.10, 0.50, 4), rsp(2, 0.30, 0.90, 4)],
        [rsp(1, 0.20, 0.70, 4), rsp(3, 0.40, 1.10, 4)],
    ]
    merged = [r for stream in per_replica for r in stream]
    s = slo_summary(merged)
    ttfts = sorted(r.ttft_s for r in merged)
    assert s["ttft_s"]["p50"] == pytest.approx(percentile(ttfts, 0.50))
    assert s["ttft_s"]["p99"] == pytest.approx(percentile(ttfts, 0.99))
    assert s["e2e_s"]["mean"] == pytest.approx(
        float(np.mean([r.total_s for r in merged]))
    )
    golden_tpot = [(r.total_s - r.ttft_s) / 3 for r in merged]
    assert s["tpot_s"]["mean"] == pytest.approx(float(np.mean(golden_tpot)))
