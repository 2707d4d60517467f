"""The reduction from a profiler trace to busy time, layers and gaps."""

from __future__ import annotations

import json

import pytest

from bench import manifest, trace_reduce

PATTERNS = json.loads((manifest.BENCH / "layers.json").read_text())["layers"]
MS = 1_000_000  # ns


def _synthetic():
    """One chip: a prefill, then three decode steps, inside a 100 ms
    window; the host stepped, then slept."""
    mods = [("jit__prefill_bucket_impl(7)", 10 * MS, 40 * MS),
            ("jit__splice_impl", 41 * MS, 42 * MS),
            ("jit__step_impl", 50 * MS, 60 * MS),
            ("jit__step_impl", 60 * MS, 70 * MS),
            ("jit__step_impl", 95 * MS, 105 * MS)]  # runs past the window
    ops = [(s, e) for _, s, e in mods] + [(12 * MS, 20 * MS)]  # nested op
    host = [("bench.window", 0, 100 * MS), ("bench.step", 0, 72 * MS),
            ("bench.idle", 72 * MS, 95 * MS), ("bench.step", 95 * MS, 100 * MS)]
    return {"chips": {"0": {"modules": mods, "ops": ops}}, "host": host}


def test_merge_and_clip():
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert trace_reduce.clip([(0, 5), (6, 9), (11, 12)], 2, 10) == [(2, 5), (6, 9)]


def test_program_names_map_to_layers():
    assert trace_reduce.program("jit__step_impl(123)") == "jit__step_impl"
    assert trace_reduce.layer_of("jit__prefill_bucket_impl", PATTERNS) == "prefill"
    assert trace_reduce.layer_of("jit__step_impl", PATTERNS) == "decode"
    assert trace_reduce.layer_of("jit__splice_impl", PATTERNS) == "splice"
    assert trace_reduce.layer_of("jit_collective", PATTERNS) == "handoff"
    assert trace_reduce.layer_of("jit_convert_element_type", PATTERNS) == "other"


def test_reduce_synthetic():
    r = trace_reduce.reduce(_synthetic(), PATTERNS)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 10-40, 41-42, 50-70, 95-100 (clipped) = 56 ms
    assert r["chips"]["0"]["busy_s"] == pytest.approx(0.056)
    assert r["chips"]["0"]["idle_share"] == pytest.approx(0.44)
    assert r["layers"]["prefill"] == {"seconds": pytest.approx(0.03),
                                      "calls": 1, "chips": ["0"]}
    assert r["layers"]["decode"]["seconds"] == pytest.approx(0.025)
    assert r["layers"]["decode"]["calls"] == 3
    assert r["top_programs"][0] == ["jit__prefill_bucket_impl",
                                    pytest.approx(0.03)]
    # gaps: 70-95 (25 ms, host idle), 0-10, 42-50 (host stepping), 40-41
    assert [g[0] for g in r["idle_gaps"]] == ["bench.idle", "bench.step",
                                               "bench.step", "bench.step"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [0.025, 0.010, 0.008, 0.001])


FIXTURE = manifest.ROOT / "tests" / "bench" / "fixtures" / \
    "granite-completion-0.5s.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """A 0.5 s traced window of granite-completion on one TPU v5e (one
    prefill of 814 tokens, one of 128, 27 decode steps), gzipped."""
    import gzip

    path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return trace_reduce.load(path)


def test_load_reads_chip_trace(chip_trace):
    assert list(chip_trace["chips"]) == ["0"]
    chip = chip_trace["chips"]["0"]
    assert len(chip["modules"]) == 33 and len(chip["ops"]) == 24538
    names = {n for n, *_ in chip_trace["host"]}
    assert names == {"bench.window", "bench.submit", "bench.step"}


def test_reduce_pins_chip_trace(chip_trace):
    r = trace_reduce.reduce(chip_trace, PATTERNS)
    assert r["window_s"] == pytest.approx(0.500731844)
    assert r["chips"]["0"]["busy_s"] == pytest.approx(0.492028109)
    assert r["chips"]["0"]["idle_share"] == pytest.approx(0.017382028)
    lay = r["layers"]
    assert (lay["prefill"]["calls"], lay["decode"]["calls"],
            lay["splice"]["calls"]) == (2, 27, 2)
    assert lay["prefill"]["seconds"] == pytest.approx(0.200456246)
    assert lay["decode"]["seconds"] == pytest.approx(0.291331129)
    assert [p for p, _ in r["top_programs"]] == [
        "jit__step_impl", "jit__prefill_bucket_impl", "jit__splice_impl"]
    assert r["idle_gaps"][0] == ["bench.step", pytest.approx(0.003159897)]
    assert len(r["idle_gaps"]) == trace_reduce.TOP
