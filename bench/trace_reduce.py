"""From a profiler trace to device busy time, per-layer time and idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes, with JAX's own
``ProfileData``, into plain lists: per TPU chip the XLA programs run
(the "XLA Modules" line) and the operations run (the "XLA Ops" line),
and the harness's own host spans (``bench.*`` trace annotations).
``reduce`` turns that into numbers:

* busy seconds per chip: the union of the operation intervals inside the
  traced window, and the idle share, 1 - busy / window;
* seconds and calls per layer, each program mapped to a layer by the
  patterns of ``layers.json``;
* the programs that took most device time, over all chips;
* the longest idle gaps of the chips that decode, each named by the host
  span that covered most of it (what the harness was doing meanwhile).

The traced window is the harness's ``bench.window`` span.
"""

from __future__ import annotations

import glob
import json
import re
from pathlib import Path

TOP = 10
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def load(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
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
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith("bench.")]
    return {"chips": chips, "host": host}


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def program(name: str) -> str:
    """A program's name without the run-specific suffix XLA adds."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def layer_of(name: str, patterns: dict) -> str:
    for layer, pats in patterns.items():
        if any(re.search(p, name) for p in pats):
            return layer
    return "other"


def _label(gap, host) -> str:
    best, label = 0.0, "none"
    for name, s, e in host:
        if name == "bench.window":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, label = ov, name
    return label


def reduce(tr: dict, patterns: dict) -> dict:
    windows = [(s, e) for n, s, e in tr["host"] if n == "bench.window"]
    if windows:
        lo, hi = windows[0]
    else:
        ends = [t for c in tr["chips"].values() for _, s, e in c["modules"]
                for t in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    window_s = (hi - lo) / 1e9
    chips, layers, progs, gaps = {}, {}, {}, {}
    for chip, c in tr["chips"].items():
        busy = merge(clip(c["ops"] or [(s, e) for _, s, e in c["modules"]],
                          lo, hi))
        busy_s = sum(e - s for s, e in busy) / 1e9
        per_layer = {}
        for name, s, e in c["modules"]:
            if e <= lo or s >= hi:
                continue
            dur = (min(e, hi) - max(s, lo)) / 1e9
            p = program(name)
            progs[p] = progs.get(p, 0.0) + dur
            lay = per_layer.setdefault(layer_of(p, patterns),
                                       {"seconds": 0.0, "calls": 0})
            lay["seconds"] += dur
            lay["calls"] += 1
        for name, lay in per_layer.items():
            tot = layers.setdefault(name, {"seconds": 0.0, "calls": 0,
                                           "chips": []})
            tot["seconds"] += lay["seconds"]
            tot["calls"] += lay["calls"]
            tot["chips"].append(chip)
        chips[chip] = {"busy_s": busy_s,
                       "idle_share": 1.0 - busy_s / window_s if window_s else None}
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps[chip] = [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    # gaps of the chips that decode, where tokens wait on the device
    watched = layers.get("decode", {}).get("chips") or list(chips)
    longest = sorted((g for c in watched for g in gaps[c]),
                     key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s,
        "chips": chips,
        "layers": layers,
        "top_programs": sorted(([p, s] for p, s in progs.items()),
                               key=lambda x: -x[1])[:TOP],
        "idle_gaps": [[_label(g, tr["host"]), (g[1] - g[0]) / 1e9]
                      for g in longest[:TOP]],
    }


def find(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir, layers_file) -> dict:
    with open(layers_file) as f:
        patterns = json.load(f)["layers"]
    return reduce(load(find(trace_dir)), patterns)
