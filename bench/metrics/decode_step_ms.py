"""Device time of the decode-step programs per step, from the trace."""


def read(ctx):
    lay = (ctx.trace or {}).get("layers", {}).get("decode")
    if not lay or not lay["calls"]:
        return None
    return lay["seconds"] / lay["calls"] * 1e3
