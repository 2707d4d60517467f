"""Observability: span buffer semantics, cross-process rebasing under
adversarial clock skew, Chrome export determinism, span/charge
reconciliation against a real engine drain, stamp validation, the
metrics registry/sampler, and the slo_summary stage breakdown."""

import json
import threading
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import trace
from repro.core.metrics import merge_record_streams, slo_summary
from repro.core.obs import Counter, Gauge, Histogram, Registry, Sampler
from repro.core.profiler import RequestRecord
from repro.core.trace import Span, Trace, TraceBuffer
from repro.serving import ServingEngine
from repro.serving.request import Request, Response


@pytest.fixture(autouse=True)
def _global_tracer_off():
    """Never leak an enabled global tracer into other tests."""
    yield
    trace.disable_tracing()


def _requests(cfg, lens, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, s, dtype=np.int32),
            max_new_tokens=max_new,
        )
        for s in lens
    ]


# --------------------------------------------------------------------------- #
# TraceBuffer semantics
# --------------------------------------------------------------------------- #
def test_buffer_disabled_emit_is_noop():
    buf = TraceBuffer(capacity=8)
    buf.emit("x", 0.0, 1.0)
    assert buf.snapshot() == []
    assert buf.stats() == {
        "enabled": False, "capacity": 8, "buffered": 0,
        "emitted": 0, "dropped": 0,
    }


def test_buffer_ring_counts_drops_never_raises():
    buf = TraceBuffer(capacity=4)
    buf.enable(process="p")
    for i in range(10):
        buf.emit(f"s{i}", float(i), float(i) + 0.5)
    st = buf.stats()
    assert st["emitted"] == 10 and st["buffered"] == 4 and st["dropped"] == 6
    # the ring keeps the newest spans
    assert [s.name for s in buf.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_buffer_enable_reset_and_drain():
    buf = TraceBuffer(capacity=8)
    buf.enable(process="p")
    buf.emit("a", 0.0, 1.0, request_id=7, tag="t")
    got = buf.drain()
    assert [s.name for s in got] == ["a"] and buf.snapshot() == []
    assert got[0].request_id == 7 and got[0].attrs["tag"] == "t"
    assert got[0].process == "p"
    assert got[0].thread == threading.current_thread().name
    buf.emit("b", 0.0, 1.0)
    buf.enable(process="p")  # reset=True clears the ring and counters
    assert buf.snapshot() == [] and buf.stats()["emitted"] == 0


def test_span_ring_off_writes_nothing(monkeypatch):
    buf = TraceBuffer(capacity=8)
    monkeypatch.setattr(trace, "_GLOBAL", buf)
    with trace.span("engine.step", tag="engine") as sp:
        sp.set(admitted=1)
    assert buf.snapshot() == [] and buf.stats()["emitted"] == 0


def test_span_writes_ring_and_profiler(tmp_path):
    """One span lands in the ring (perf_counter stamps, attrs incl. the
    ones set on the way) and in the profiler's host plane under the same
    name with the attrs as event stats."""
    import glob

    import jax

    trace.enable_tracing(process="main")
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    with trace.span("engine.prefill", path="bucket", bucket=128) as sp:
        sp.set(live_rows=3)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    [got] = trace.tracer().snapshot()
    assert got.name == "engine.prefill" and got.request_id is None
    assert t0 <= got.t_start <= got.t_end <= t1
    assert got.attrs == {"path": "bucket", "bucket": 128, "live_rows": 3}

    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        (e.name, dict(e.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("engine.")
    ]
    assert events == [("engine.prefill",
                       {"path": "bucket", "bucket": 128, "live_rows": 3})]


# --------------------------------------------------------------------------- #
# wire round-trip + adversarial skew rebasing (the IPC span ferry)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("offset", [-12345.678, -1e-4, 0.0, 1e-4, 98765.4321])
def test_wire_roundtrip_rebases_onto_parent_clock(offset):
    """A worker whose perf_counter epoch differs by `offset` from the
    parent ships spans over the wire; after ingest, absolute placement
    is rebased while every duration survives untouched."""
    worker = TraceBuffer(capacity=16, process="worker")
    worker.enable()
    t0 = 1000.0 + offset  # worker-clock stamp of a parent-clock t=1000
    worker.emit("prefill.bucket", t0, t0 + 0.25, request_id=1)
    worker.emit("engine.harvest", t0 + 0.25, t0 + 0.75, request_id=1)

    parent = TraceBuffer(capacity=16, process="router")
    parent.ingest_wire(worker.drain_wire(), offset=offset, process="replica0")
    got = sorted(parent.snapshot(), key=lambda s: s.t_start)
    assert [s.process for s in got] == ["replica0", "replica0"]
    assert got[0].t_start == pytest.approx(1000.0, abs=1e-9)
    assert got[0].wall == pytest.approx(0.25, abs=1e-12)
    assert got[1].wall == pytest.approx(0.50, abs=1e-12)
    # ingest bypasses the enabled gate: relaying must not require the
    # parent buffer to be actively emitting
    assert not parent.enabled and len(parent.snapshot()) == 2


def test_wire_interleaves_with_parent_spans_on_one_timeline():
    """Two workers with opposite-sign skews plus local parent spans all
    sort into true parent-clock order after ingest."""
    parent = TraceBuffer(capacity=32, process="router")
    parent.enable()
    parent.emit("router.pick", 10.0, 10.1)
    for label, off, start in (("replica0", 500.0, 10.2),
                              ("replica1", -500.0, 10.4)):
        w = TraceBuffer(capacity=8, process="w")
        w.enable()
        w.emit("request", start + off, start + off + 0.1)
        parent.ingest_wire(w.drain_wire(), offset=off, process=label)
    order = [s.process for s in
             sorted(parent.snapshot(), key=lambda s: s.t_start)]
    assert order == ["router", "replica0", "replica1"]


def test_merge_record_streams_adversarial_skew():
    def rec(rid, t_issue, t_done):
        return RequestRecord(request_id=rid, client_id=0, t_issue=t_issue,
                             t_done=t_done, stage_s={"inference": 0.5})

    # stream epochs differ by +/- hours; true completion order interleaves
    a = [rec(0, 7200.0, 7201.0), rec(2, 7204.0, 7205.0)]   # skew +7200
    b = [rec(1, -3598.0, -3597.0), rec(3, -3594.0, -3593.0)]  # skew -3600
    merged = merge_record_streams([a, b], offsets=[7200.0, -3600.0])
    assert [r.request_id for r in merged] == [0, 1, 2, 3]
    # durations are skew-invariant; sources are never mutated
    assert all(r.total == pytest.approx(1.0) for r in merged)
    assert all(r.stage_s["inference"] == 0.5 for r in merged)
    assert a[0].t_issue == 7200.0
    with pytest.raises(ValueError, match="offsets length"):
        merge_record_streams([a, b], offsets=[0.0])


# --------------------------------------------------------------------------- #
# stamp validation
# --------------------------------------------------------------------------- #
def test_validate_stamps():
    trace.validate_stamps(1.0, 2.0, 3.0)
    trace.validate_stamps(1.0, 0.0, 3.0)  # zero stamp: not yet set, skipped
    trace.validate_stamps(0.0, 0.0, 0.0)
    with pytest.raises(ValueError, match="t_first_token"):
        trace.validate_stamps(2.0, 1.0, 3.0)
    with pytest.raises(ValueError, match="replica9"):
        trace.validate_stamps(1.0, 2.5, 2.0, where="replica9 rebase")
    # tolerance absorbs clock-estimate error (the IPC rebase case)
    trace.validate_stamps(1.0, 1.0 - 0.01, 2.0, tol=0.05)


# --------------------------------------------------------------------------- #
# real engine drain: reconciliation, determinism, export, debug stamps
# --------------------------------------------------------------------------- #
def _traced_drain(model_bank, seed=0, debug_stamps=False):
    cfg = get_config("llama3-8b").reduced()
    model, params = model_bank(cfg)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        warmup=True, debug_stamps=debug_stamps)
    trace.enable_tracing(process="main")
    for req in _requests(cfg, [5, 11, 23, 37], seed=seed):
        eng.submit(req, time.perf_counter())
    out = eng.run_until_drained(max_steps=10_000)
    assert len(out) == 4
    tr = Trace.from_buffer()
    trace.disable_tracing()
    return eng, out, tr


def test_engine_drain_reconciles_and_trees_are_wellformed(model_bank):
    eng, _out, tr = _traced_drain(model_bank)
    assert len(tr) > 0
    by_req = tr.by_request()
    assert len(by_req) == 4
    # every request grew a full tree: root + queue + a prefill span
    for rid, spans in by_req.items():
        names = {s.name for s in spans}
        assert "request" in names and "queue" in names
        assert any(n.startswith("prefill.") for n in names), names
    assert tr.tree_problems() == []
    assert tr.reconcile(eng.store.records) == []
    # the text stage summary mentions every span name
    summary = tr.stage_summary()
    for name in {s.name for s in tr.spans}:
        assert name in summary


def test_engine_phase_spans_nest_in_each_step(model_bank):
    """Every engine.* phase of a drain sits inside an engine.step span,
    with the attrs the benchmark's readers use."""
    eng, _out, tr = _traced_drain(model_bank)
    eng_spans = [s for s in tr.spans if s.name.startswith("engine.")]
    by_name: dict = {}
    for s in eng_spans:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) == {"engine.step", "engine.admit", "engine.prefill",
                            "engine.dispatch", "engine.harvest"}
    steps = by_name["engine.step"]
    for s in eng_spans:
        assert s.request_id is None and s.attrs["tag"] == "engine"
        if s.name != "engine.step":
            assert any(p.t_start <= s.t_start and s.t_end <= p.t_end
                       for p in steps), s
    # 4 requests over 2 slots: every admission placed one or two of them
    admitted = [s.attrs["admitted"] for s in by_name["engine.admit"]]
    assert sum(admitted) == 4 and all(a >= 1 for a in admitted)
    for s in by_name["engine.prefill"]:
        assert s.attrs["path"] == "bucket" and s.attrs["rows"] >= 1
        assert 0 <= s.attrs["live_rows"] <= 2 - s.attrs["rows"]
        assert s.attrs["bucket"] in (16, 32, 64)
    # the first prefill finds no live decode row; a later one does
    assert by_name["engine.prefill"][0].attrs["live_rows"] == 0
    assert any(s.attrs["live_rows"] > 0 for s in by_name["engine.prefill"])
    assert sum(s.attrs["steps"] for s in by_name["engine.dispatch"]) == \
        eng.decode_steps
    # one harvest per step that found an in-flight decode step
    harvests = by_name["engine.harvest"]
    assert len(harvests) == eng.decode_steps
    assert sum(s.attrs["live"] > 0 for s in harvests) == eng.useful_steps


def test_trace_shape_deterministic_across_seeded_runs(model_bank):
    """Same seed, fresh engine -> same span tree SHAPE (request ids and
    stamps differ run to run; the structure must not)."""

    def shape(tr):
        per_req = sorted(
            tuple(sorted(s.name for s in spans))
            for spans in tr.by_request().values()
        )
        return per_req, sorted({s.name for s in tr.spans})

    _e1, _o1, tr1 = _traced_drain(model_bank, seed=3)
    _e2, _o2, tr2 = _traced_drain(model_bank, seed=3)
    assert shape(tr1) == shape(tr2)


def test_chrome_export_roundtrip(tmp_path, model_bank):
    _eng, _out, tr = _traced_drain(model_bank)
    path = tmp_path / "trace.json"
    obj = tr.export_chrome(path)
    reloaded = json.loads(path.read_text())
    assert reloaded == json.loads(json.dumps(obj))
    events = reloaded["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == len(tr)
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    # metadata events name the process/thread lanes Perfetto displays
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    assert len({e["pid"] for e in xs}) == 1  # single-process drain


def test_engine_debug_stamps_accepts_clean_drain(model_bank):
    """debug_stamps=True validates every finished record's monotonicity
    inline — a clean drain must pass, and the knob must not change
    results."""
    _eng, out, _tr = _traced_drain(model_bank, debug_stamps=True)
    assert all(r.tokens for r in out)


def test_reconcile_flags_uncovered_charge_and_malformed_trees():
    def span(name, a, b, rid=1, thread="t"):
        return Span(name=name, request_id=rid, t_start=a, t_end=b,
                    process="main", thread=thread)

    # charge exceeds total span wall -> uncovered
    rec = RequestRecord(request_id=1, client_id=0, t_issue=0.0, t_done=1.0,
                        stage_s={"inference": 5.0})
    tr = Trace([span("request", 0.0, 1.0), span("prefill.bucket", 0.0, 0.4)])
    problems = tr.reconcile([rec])
    assert problems and any("inference" in p for p in problems)

    # two roots for one request -> malformed tree
    tr2 = Trace([span("request", 0.0, 1.0), span("request", 2.0, 3.0)])
    assert tr2.tree_problems()

    # overlapping spans on one process-level lane -> malformed
    tr3 = Trace([span("transfer", 0.0, 1.0, rid=None),
                 span("transfer", 0.5, 1.5, rid=None)])
    assert tr3.tree_problems()
    # nested phases on one lane are one call stack: well formed
    nested = Trace([span("engine.step", 0.0, 1.0, rid=None),
                    span("engine.admit", 0.1, 0.6, rid=None),
                    span("engine.prefill", 0.2, 0.5, rid=None),
                    span("engine.harvest", 0.6, 0.9, rid=None),
                    span("engine.step", 1.0, 2.0, rid=None)])
    assert nested.tree_problems() == []
    # ... but a child that outlives its parent crosses it
    crossing = Trace(nested.spans + [span("engine.harvest", 0.95, 1.2,
                                          rid=None)])
    assert any("'engine.harvest' starts" in p
               for p in crossing.tree_problems())
    # a phase inside one of its own name is a mis-stamp, however it nests
    for inner in (span("engine.step", 0.2, 0.4, rid=None),
                  span("engine.prefill", 0.3, 0.4, rid=None)):
        renested = Trace(nested.spans + [inner])
        assert any(f"nests inside another '{inner.name}'" in p
                   for p in renested.tree_problems())
    # same intervals on distinct lanes (tag attr) are fine
    s1 = span("transfer", 0.0, 1.0, rid=None)
    s1.attrs["tag"] = "replica0"
    s2 = span("transfer", 0.5, 1.5, rid=None)
    s2.attrs["tag"] = "replica1"
    assert Trace([s1, s2]).tree_problems() == []

    # no records with spans to check against -> loudly inconclusive
    assert any("no record had any spans" in p
               for p in Trace([]).reconcile([rec]))


# --------------------------------------------------------------------------- #
# metrics registry + sampler
# --------------------------------------------------------------------------- #
def test_counter_monotonic_gauge_histogram():
    c = Counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = Gauge("depth")
    g.set(3.5)
    assert g.value == 3.5
    h = Histogram("lat", window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["total"] == 15.0
    assert snap["min"] == 1.0 and snap["max"] == 5.0
    assert snap["p50"] == pytest.approx(3.5)  # window kept the last 4


def test_registry_get_or_create_ingest_snapshot_delta():
    reg = Registry()
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a")
    reg.ingest_counters({"steps": 10, "tokens": 40}, prefix="engine.")
    reg.ingest_counters({"steps": 15, "tokens": 40}, prefix="engine.")
    reg.gauge("depth").set(2)
    prev = reg.snapshot()
    reg.ingest_counters({"steps": 21}, prefix="engine.")
    cur = reg.snapshot()
    assert cur["counters"]["engine.steps"] == 21
    assert cur["gauges"]["depth"] == 2
    assert Registry.delta(prev, cur)["engine.steps"] == 6
    # ingest is monotonic: a source that resets cannot rewind the counter
    reg.ingest_counters({"steps": 0}, prefix="engine.")
    assert reg.snapshot()["counters"]["engine.steps"] == 21


def test_sampler_observes_and_surfaces_source_failures():
    reg = Registry()
    with Sampler(reg, {"depth": lambda: 7.0}, interval_s=0.001):
        time.sleep(0.05)
    snap = reg.snapshot()["histograms"]["depth"]
    assert snap["count"] >= 1 and snap["p50"] == 7.0

    def boom():
        raise RuntimeError("dead source")

    s = Sampler(reg, {"bad": boom, "ok": lambda: 1.0},
                interval_s=0.001).start()
    time.sleep(0.02)
    with pytest.raises(RuntimeError, match="dead source"):
        s.stop()
    # the healthy source kept sampling despite the dead one
    assert reg.snapshot()["histograms"]["ok"]["count"] >= 1
    with pytest.raises(RuntimeError, match="already started"):
        Sampler(reg, {}).start().start()


def test_engine_counters_and_metrics_snapshot(model_bank):
    eng, _out, _tr = _traced_drain(model_bank)
    counters = eng.counters()
    assert counters["decode_steps"] > 0
    snap = eng.metrics_snapshot()
    assert snap["counters"]["engine.decode_steps"] == counters["decode_steps"]
    assert "engine.queue_depth" in snap["gauges"]


# --------------------------------------------------------------------------- #
# slo_summary stage breakdown (satellite)
# --------------------------------------------------------------------------- #
def test_slo_summary_stage_breakdown():
    def resp(rid, queue, inference):
        return Response(
            request_id=rid, tokens=[1, 2, 3], ttft_s=0.2, total_s=1.0,
            stage_s={"queue": queue, "inference": inference},
        )

    rs = [resp(0, 0.1, 0.5), resp(1, 0.3, 0.7),
          Response(request_id=2, tokens=[1, 2], ttft_s=0.1, total_s=0.5,
                   stage_s={"transfer": 0.05})]
    out = slo_summary(rs)
    assert set(out["stages"]) == {"queue", "inference", "transfer"}
    # a response missing a stage contributes 0.0, so every n matches
    for stage in out["stages"].values():
        assert stage["n"] == 3
    assert out["stages"]["queue"]["mean"] == pytest.approx((0.1 + 0.3) / 3)
    assert out["stages"]["transfer"]["mean"] == pytest.approx(0.05 / 3)
    # warmup drop applies to stages too
    assert slo_summary(rs, warmup=1)["stages"]["queue"]["n"] == 2
