"""Useful FLOPs of the prompts prefilled in the window (true lengths,
causal attention, the head at the sampled position only) over the
prefill programs' device time times the chip's peak, in percent."""


def read(ctx):
    lay = (ctx.trace or {}).get("layers", {}).get("prefill")
    if not lay or not lay["seconds"] or not ctx.prefilled or not ctx.peaks:
        return None
    work = ctx.block.work
    flops = sum(work.prefill_flops(ctx.cfg, n) for n in ctx.prefilled)
    return 100.0 * flops / (lay["seconds"] * ctx.peaks["flops_bf16"])
