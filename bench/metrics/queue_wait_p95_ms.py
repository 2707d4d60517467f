"""95th percentile of the program's measured queue stage (submit to the
admission that picks the request), over the window's admitted requests."""

import numpy as np


def read(ctx):
    waits = [r.stage_s["queue"] for r in ctx.records.values()
             if "queue" in r.stage_s]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
