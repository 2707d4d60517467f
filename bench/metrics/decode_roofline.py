"""The least time the decode steps of the window could take on the chip,
the larger of useful FLOPs over peak and useful bytes over HBM bandwidth,
over the decode programs' device time, in percent. Useful bytes are the
weights read once per step that advanced a request, plus each live row's
true cache."""


def read(ctx):
    lay = (ctx.trace or {}).get("layers", {}).get("decode")
    steps = ctx.counters["useful_steps"]
    if not lay or not lay["seconds"] or not steps or not ctx.peaks:
        return None
    flops, nbytes = ctx.block.work.decode_work(ctx.cfg, steps,
                                               ctx.decode_contexts)
    least = max(flops / ctx.peaks["flops_bf16"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / lay["seconds"]
