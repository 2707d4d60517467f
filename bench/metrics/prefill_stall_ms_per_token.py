"""Decode time lost to prefill per decode token served: over the window's
prefill programs, each one's device seconds times the decode rows that
were live when its ``engine.prefill`` span dispatched it, summed, over
the decode tokens served in the window, in ms."""

from pathlib import Path

from bench import engine_spans


def read(ctx):
    sec = engine_spans.section(ctx, Path(__file__))
    if (not sec or "engine.prefill" not in sec["spans"]
            or sec["prefill_stall_s"] is None or not ctx.decode_contexts):
        return None
    return sec["prefill_stall_s"] / len(ctx.decode_contexts) * 1e3
