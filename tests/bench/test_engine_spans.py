"""The program's engine spans: their reduction and the three readers."""

from __future__ import annotations

import gzip
import json
import shutil
import time
from types import SimpleNamespace

import pytest

from bench import engine_spans, harness, manifest, trace_reduce

from tinycell import write_tiny_root

PATTERNS = json.loads((manifest.BENCH / "layers.json").read_text())["layers"]
MS = 1_000_000  # ns
FIXTURE = manifest.ROOT / "tests" / "bench" / "fixtures" / \
    "granite-completion-0.5s.xplane.pb.gz"
READERS = ("admit_block_p95_ms", "host_stall_share",
           "prefill_stall_ms_per_token")


def _synthetic():
    """The chip and harness spans of ``test_trace_reduce._synthetic`` (a
    prefill, then three decode steps, in a 100 ms window), with the
    engine's spans: one step admits a request (its prefill held up two
    live rows), dispatches and harvests; after an idle wait, a step that
    harvests and admits nothing."""
    mods = [("jit__prefill_bucket_impl(7)", 10 * MS, 40 * MS),
            ("jit__splice_impl", 41 * MS, 42 * MS),
            ("jit__step_impl", 50 * MS, 60 * MS),
            ("jit__step_impl", 60 * MS, 70 * MS),
            ("jit__step_impl", 95 * MS, 105 * MS)]
    # the prefill program's operations leave a bubble at 25-26 ms
    ops = [(10 * MS, 25 * MS), (26 * MS, 40 * MS)] + \
        [(s, e) for _, s, e in mods[1:]]
    host = [("bench.window", 0, 100 * MS), ("bench.step", 0, 72 * MS),
            ("bench.idle", 72 * MS, 95 * MS), ("bench.step", 95 * MS, 100 * MS)]
    engine = [
        ("engine.step", 1 * MS, 71 * MS, {"tag": "engine"}),
        ("engine.admit", 2 * MS, 45 * MS, {"admitted": 1}),
        ("engine.prefill", 5 * MS, 44 * MS,
         {"path": "bucket", "bucket": 128, "rows": 1, "live_rows": 2}),
        ("engine.dispatch", 46 * MS, 48 * MS, {"steps": 2}),
        ("engine.harvest", 48 * MS, 71 * MS, {"live": 3}),
        ("engine.step", 95 * MS, 100 * MS, {}),
        ("engine.admit", 96 * MS, 97 * MS, {"admitted": 0}),
        ("engine.harvest", 97 * MS, 100 * MS, {"live": 3}),
        ("engine.step", 101 * MS, 110 * MS, {}),  # after the window
    ]
    return {"chips": {"0": {"modules": mods, "ops": ops}}, "host": host,
            "engine": engine}


def test_inner_names_the_innermost_span_covering_most():
    eng = _synthetic()["engine"]
    assert engine_spans.inner((0, 10 * MS), eng) == "engine.admit"
    assert engine_spans.inner((42 * MS, 50 * MS), eng) == "engine.step"
    assert engine_spans.inner((40 * MS, 41 * MS), eng) == "engine.prefill"
    assert engine_spans.inner((72 * MS, 94 * MS), eng) is None


def test_overlap():
    assert engine_spans.overlap([(0, 5), (8, 12)], [(3, 9), (11, 20)]) == 4
    assert engine_spans.overlap([], [(0, 1)]) == 0


def test_reduce_synthetic():
    r = engine_spans.reduce(_synthetic(), PATTERNS)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["spans"]["engine.step"] == {"count": 2,
                                         "seconds": pytest.approx(0.075)}
    assert r["spans"]["engine.admit"]["count"] == 2
    assert r["admit_block_s"] == pytest.approx([0.043])
    # between programs idle 0-10, 40-41, 42-50, 70-95; in steps 1-71 and
    # 95-100: 9+1+8+1; the bubble inside the prefill program is not a stall
    assert r["step_idle_s"] == {"0": pytest.approx(0.019)}
    # the 30 ms prefill program ran inside a span with two live rows
    assert r["prefill_stall_s"] == pytest.approx(0.060)
    assert r["idle_gaps"] == [
        ["bench.idle", pytest.approx(0.025)],
        ["bench.step/engine.admit", pytest.approx(0.010)],
        ["bench.step/engine.step", pytest.approx(0.008)],
        ["bench.step/engine.prefill", pytest.approx(0.001)],
        ["bench.step/engine.prefill", pytest.approx(0.001)]]
    # the same gaps, in the same order, as the harness's own reduction
    base = trace_reduce.reduce(_synthetic(), PATTERNS)["idle_gaps"]
    assert [g[1] for g in base] == [g[1] for g in r["idle_gaps"]]
    assert [g[0].split("/")[0] for g in r["idle_gaps"]] == [g[0] for g in base]


def test_sharded_prefill_counts_once():
    tr = _synthetic()
    tr["chips"]["1"] = {"modules": [("jit__prefill_bucket_impl(7)",
                                     10 * MS, 40 * MS)], "ops": []}
    assert engine_spans.reduce(tr, PATTERNS)["prefill_stall_s"] == \
        pytest.approx(0.060)


def test_name_gaps_in_place():
    gaps = [["bench.idle", 0.025], ["bench.step", 0.010]]
    engine_spans.name_gaps(gaps, [["bench.idle", 0.025],
                                  ["bench.step/engine.admit", 0.010]])
    assert gaps == [["bench.idle", 0.025], ["bench.step/engine.admit", 0.010]]
    # other gaps than the harness found: left as they are
    engine_spans.name_gaps(gaps, [["bench.step/engine.harvest", 0.003]])
    assert gaps[1][0] == "bench.step/engine.admit"


def _ctx(sec_trace, contexts=12):
    return SimpleNamespace(trace=sec_trace, decode_contexts=[1] * contexts)


@pytest.fixture
def traced_root(tmp_path):
    """A checkout-shaped tree whose traced run left ``trace`` behind."""
    shutil.copytree(manifest.BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copy(manifest.BENCH / "layers.json", tmp_path / "bench")

    def put(xplane: bytes):
        d = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(xplane)
        return tmp_path
    return put


def test_readers_on_the_synthetic_trace(traced_root, monkeypatch):
    root = traced_root(b"")
    monkeypatch.setattr(engine_spans, "load", lambda path: _synthetic())
    engine_spans._CACHE.clear()
    reduced = trace_reduce.reduce(_synthetic(), PATTERNS)
    ctx = _ctx(reduced)
    got = {m: manifest.load_reader(m, root / "bench")(ctx) for m in READERS}
    assert got == {"admit_block_p95_ms": pytest.approx(43.0),
                   "host_stall_share": pytest.approx(19.0),
                   "prefill_stall_ms_per_token": pytest.approx(5.0)}
    # the breakdown the harness prints now names the in-step gaps
    assert [g[0] for g in reduced["idle_gaps"]] == [
        "bench.idle", "bench.step/engine.admit", "bench.step/engine.step",
        "bench.step/engine.prefill", "bench.step/engine.prefill"]


def test_chip_trace_without_engine_spans(traced_root):
    """The committed profile predates the engine spans: its gaps are the
    harness's, the readers report nothing and leave the reduction as
    ``trace_reduce`` made it."""
    root = traced_root(gzip.decompress(FIXTURE.read_bytes()))
    path = trace_reduce.find(root / ".bench_trace")
    reduced = trace_reduce.reduce(trace_reduce.load(path), PATTERNS)
    before = json.loads(json.dumps(reduced))
    engine_spans._CACHE.clear()
    for m in READERS:
        assert manifest.load_reader(m, root / "bench")(_ctx(reduced)) is None
    assert reduced == before
    sec = engine_spans.section(_ctx(reduced),
                               root / "bench" / "metrics" / "x.py")
    assert sec["spans"] == {} and sec["admit_block_s"] == []
    assert sec["idle_gaps"] == before["idle_gaps"]
    assert sec["step_idle_s"] == {"0": 0.0}


def test_tiny_traced_run_reads_engine_spans(tmp_path):
    """A traced run of the tiny fused cell on the CPU: the profile holds
    the engine's spans with their stats, and the admission metric reads
    them (the chip-side readers find no device planes there)."""
    root = write_tiny_root(tmp_path)
    out = harness.run("tiny-fused", 2**31 + 12, 2.0, True,
                      t_start=time.perf_counter(), accelerator=False,
                      root=root, bench_dir=root / "bench")
    assert out["correct"], out["checks"]
    assert out["metrics"]["admit_block_p95_ms"]["value"] > 0
    assert "host_stall_share" not in out["metrics"]
    assert "prefill_stall_ms_per_token" not in out["metrics"]
    tr = engine_spans.load(trace_reduce.find(root / ".bench_trace"))
    stats: dict = {}
    for name, s, e, st in tr["engine"]:
        assert e >= s
        stats.setdefault(name, []).append(st)
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.dispatch", "engine.harvest"} <= set(stats)
    assert all(st["admitted"] >= 0 for st in stats["engine.admit"])
    for st in stats["engine.prefill"]:
        assert st["path"] == "bucket" and st["rows"] >= 1
        assert {"bucket", "live_rows"} <= set(st)
    assert all("steps" in st for st in stats["engine.dispatch"])
    assert all("live" in st for st in stats["engine.harvest"])
