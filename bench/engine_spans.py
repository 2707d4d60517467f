"""The program's own engine spans in a traced window.

The engine opens ``engine.*`` profiler annotations around its host phases
(``repro.core.trace.span``), on the profiler's clock beside the device
planes: ``engine.step`` over each ``step()``, ``engine.admit`` (stat
``admitted``: requests it placed), ``engine.prefill`` (``path``,
``bucket``, ``rows``, ``live_rows``: decode rows live when it was
dispatched), ``engine.dispatch`` (``steps``), ``engine.harvest``
(``live``) and ``engine.handoff``.

``load`` reads them with their stats, and the device planes as
``trace_reduce.load`` does. ``reduce`` gives, for the traced window:

* ``spans``: per engine span name, its count and seconds;
* ``admit_block_s``: the wall of each admission that placed a request;
* ``step_idle_s``: per chip that decodes, the seconds inside
  ``engine.step`` spans between the chip's programs (the host had work
  and the chip waited for its next program); the bubbles between a
  program's own operations are the program's, not the host's, and are
  left out;
* ``prefill_stall_s``: over the prefill programs, device seconds times
  the ``live_rows`` of the ``engine.prefill`` span each ran in; None
  without device planes;
* ``idle_gaps``: ``trace_reduce``'s longest idle gaps, a gap inside a
  step named ``bench.step/<engine span>``: the innermost engine span
  that covers more than half of it, else the one covering most.

A program without these spans gives empty lists and the readers report
nothing. ``section`` serves the metric readers: it reduces the run's
trace once, and names the in-step gaps of the harness's reduction in
place, since the result's ``breakdown`` is built from that list after
the readers ran.
"""

from __future__ import annotations

import bisect
import json
import os
from pathlib import Path

from bench import trace_reduce


def load(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host, engine = {}, [], []
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns) for e in line.events]
            chips[m.group(1)] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.startswith("engine."):
                        engine.append((e.name, e.start_ns, e.end_ns,
                                       {k: v for k, v in e.stats}))
    return {"chips": chips, "host": host, "engine": engine}


def overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def inner(gap, engine) -> str | None:
    """The innermost engine span covering more than half of ``gap``, else
    the one covering most of it; None when none touches it."""
    half = (gap[1] - gap[0]) / 2
    best, most, innermost = 0.0, None, None
    for name, s, e, _ in engine:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        if ov > best:
            best, most = ov, name
        if ov > half and (innermost is None or e - s < innermost[0]):
            innermost = (e - s, name)
    return innermost[1] if innermost else most


def idle(busy, lo: float, hi: float) -> list:
    """The sorted gaps of ``[lo, hi]`` that no interval of ``busy``
    covers."""
    merged = trace_reduce.merge(trace_reduce.clip(busy, lo, hi))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def window(tr: dict) -> tuple:
    spans = [(s, e) for n, s, e in tr["host"] if n == "bench.window"]
    if spans:
        return spans[0]
    ends = [t for c in tr["chips"].values() for _, s, e in c["modules"]
            for t in (s, e)]
    return (min(ends), max(ends)) if ends else (0.0, 0.0)


def reduce(tr: dict, patterns: dict) -> dict:
    lo, hi = window(tr)
    window_s = (hi - lo) / 1e9
    engine = sorted((x for x in tr["engine"] if x[1] < hi and x[2] > lo),
                    key=lambda x: x[1])
    spans: dict = {}
    for name, s, e, _ in engine:
        tot = spans.setdefault(name, {"count": 0, "seconds": 0.0})
        tot["count"] += 1
        tot["seconds"] += (e - s) / 1e9
    admits = [(e - s) / 1e9 for name, s, e, st in engine
              if name == "engine.admit" and st.get("admitted", 0) >= 1]
    steps = trace_reduce.merge(trace_reduce.clip(
        [(s, e) for name, s, e, _ in engine if name == "engine.step"], lo, hi))
    prefills = [(s, e, st.get("live_rows", 0)) for name, s, e, st in engine
                if name == "engine.prefill"]
    starts = [s for s, _, _ in prefills]

    gaps, between, decoding, step_idle = {}, {}, [], {}
    per_span: dict = {}  # prefill span index -> {chip: device seconds}
    for chip, c in tr["chips"].items():
        programs = [(s, e) for _, s, e in c["modules"]]
        # the harness's gaps (between operations) for the labels; the
        # gaps between programs for the host's stalls
        gaps[chip] = idle(c["ops"] or programs, lo, hi)
        between[chip] = idle(programs, lo, hi)
        for name, s, e in c["modules"]:
            if e <= lo or s >= hi:
                continue
            layer = trace_reduce.layer_of(trace_reduce.program(name), patterns)
            if layer == "decode" and chip not in decoding:
                decoding.append(chip)
            if layer != "prefill":
                continue
            s, e = max(s, lo), min(e, hi)
            # the spans are disjoint: walk back from the last to start
            # before the program ends, to the first that ends before it
            j, best, at = bisect.bisect_left(starts, e) - 1, 0.0, None
            while j >= 0 and prefills[j][1] > s:
                ov = min(e, prefills[j][1]) - max(s, prefills[j][0])
                if ov > best:
                    best, at = ov, j
                j -= 1
            if at is not None:
                got = per_span.setdefault(at, {})
                got[chip] = got.get(chip, 0.0) + (e - s) / 1e9
    watched = decoding or list(tr["chips"])
    for chip in watched:
        step_idle[chip] = overlap(between[chip], steps) / 1e9
    # a program sharded over chips runs once per chip: count it once
    stall = (sum(max(by_chip.values()) * prefills[j][2]
                 for j, by_chip in per_span.items())
             if tr["chips"] else None)

    longest = sorted((g for c in watched for g in gaps[c]),
                     key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    labelled = []
    for g in longest:
        label = trace_reduce._label(g, tr["host"])
        name = inner(g, engine) if label == "bench.step" else None
        labelled.append([f"{label}/{name}" if name else label,
                         (g[1] - g[0]) / 1e9])
    return {"window_s": window_s, "spans": spans, "admit_block_s": admits,
            "step_idle_s": step_idle, "prefill_stall_s": stall,
            "idle_gaps": labelled}


_CACHE: dict = {}


def section(ctx, reader_file) -> dict | None:
    """The engine section of the traced run whose metric reader lives at
    ``reader_file`` (``<root>/bench/metrics/<m>.py``; the harness writes
    the trace under ``<root>/.bench_trace``); None when there is none.
    Names the in-step gaps of ``ctx.trace["idle_gaps"]`` in place."""
    if not ctx.trace:
        return None
    bench_dir = Path(reader_file).resolve().parents[1]
    try:
        path = trace_reduce.find(bench_dir.parent / ".bench_trace")
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        with open(bench_dir / "layers.json") as f:
            patterns = json.load(f)["layers"]
        _CACHE.clear()
        _CACHE[key] = reduce(load(path), patterns)
    sec = _CACHE[key]
    name_gaps(ctx.trace["idle_gaps"], sec["idle_gaps"])
    return sec


def name_gaps(gaps: list, named: list) -> None:
    """Give each ``[label, seconds]`` of ``gaps`` the name ``named`` gives
    it, where both list the same gaps and the name extends the label."""
    if [round(d, 9) for _, d in gaps] != [round(d, 9) for _, d in named]:
        return
    for g, (name, _) in zip(gaps, named):
        if name.startswith(g[0] + "/"):
            g[0] = name
