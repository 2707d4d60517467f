"""95th percentile of the wall of the window's ``engine.admit`` spans
that placed at least one request: how long one admission holds the host
loop (every prefill it groups, dispatch through the host fence), while
arrivals wait to be sent and live rows wait for their next token."""

from pathlib import Path

import numpy as np

from bench import engine_spans


def read(ctx):
    sec = engine_spans.section(ctx, Path(__file__))
    if not sec or not sec["admit_block_s"]:
        return None
    return float(np.percentile(sec["admit_block_s"], 95)) * 1e3
