"""Device time of the prefill programs per prefill call, from the trace."""


def read(ctx):
    lay = (ctx.trace or {}).get("layers", {}).get("prefill")
    if not lay or not lay["calls"]:
        return None
    return lay["seconds"] / lay["calls"] * 1e3
