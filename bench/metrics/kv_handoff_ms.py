"""Median wall of the window's ``engine.handoff`` spans, in ms: one KV
handoff between the chip that prefills and the chip that decodes, the
collective and its landing, dispatch through the host fence. Reports
nothing where any handoff's charge is modeled: its wall then is not what
the request was charged."""

from pathlib import Path

import numpy as np

from bench import engine_spans


def read(ctx):
    sec = engine_spans.section(ctx, Path(__file__))
    if not sec or not sec["handoffs"]:
        return None
    if any(h["charge"] != "measured" for h in sec["handoffs"]):
        return None
    return float(np.median([h["wall_s"] for h in sec["handoffs"]])) * 1e3
