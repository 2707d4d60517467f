"""Device idle time between programs inside the program's
``engine.step`` spans, over the traced window, on the chips that decode
(their mean), in percent: time the engine had work on the host and the
chip waited for its next program. Gaps between one program's own
operations are left out: the host does not cause them."""

from pathlib import Path

from bench import engine_spans


def read(ctx):
    sec = engine_spans.section(ctx, Path(__file__))
    if (not sec or "engine.step" not in sec["spans"]
            or not sec["step_idle_s"] or not sec["window_s"]):
        return None
    idle = list(sec["step_idle_s"].values())
    return 100.0 * sum(idle) / len(idle) / sec["window_s"]
