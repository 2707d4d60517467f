"""Useful FLOPs of the decode tokens served in the window (true cache
lengths, live rows only) over the decode programs' device time times the
chip's peak, in percent: the whole decode step's share of the peak."""


def read(ctx):
    lay = (ctx.trace or {}).get("layers", {}).get("decode")
    if not lay or not lay["seconds"] or not ctx.decode_contexts or not ctx.peaks:
        return None
    work = ctx.block.work
    flops = sum(work.decode_flops(ctx.cfg, c) for c in ctx.decode_contexts)
    return 100.0 * flops / (lay["seconds"] * ctx.peaks["flops_bf16"])
