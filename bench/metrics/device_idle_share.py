"""1 - busy / traced window on the chips that decode (their mean), where
busy is the union of the device operations' intervals, in percent."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["chips"]:
        return None
    watched = tr["layers"].get("decode", {}).get("chips") or list(tr["chips"])
    idle = [tr["chips"][c]["idle_share"] for c in watched]
    return 100.0 * sum(idle) / len(idle)
