"""Find the highest arrival rate a cell's deployment sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 2 3 4

Sets the cell up once, then offers its traffic mix at each rate in turn,
once for each of ``--seeds``, for ``--seconds``, draining between
windows. For each rate it prints the
client-side TTFT (median, p95), the time per output token, the requests
due and those with no first token at the window's end (the backlog), and
how much later the last quarter's first tokens came than the first
quarter's. A rate is sustained when, on every seed, the backlog stays
within the batch and that ratio under 1.5. The cell's mix file then takes 0.8 x the highest such
rate as a number: the benchmark never searches for a rate itself.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def window(front, mix: dict, seed: int, seconds: float, vocab: int) -> None:
    """One window at ``mix``'s rate, its line printed, then a drain."""
    import numpy as np

    from bench import client, harness, loadgen

    sched = loadgen.schedule(mix, seed, seconds, vocab)
    cl, t0, t_end, _ = harness.serve_window(front, sched, seconds,
                                            harness._null,
                                            loadgen.clients(mix))
    ttft = client.ttft_s(cl.seen, t_end)
    q = max(len(cl.seen) // 4, 1)
    late = float(np.mean(ttft[-q:]) / max(np.mean(ttft[:q]), 1e-9))
    tpot = client.tpot_s(cl.seen)
    print(json.dumps({
        "rate_rps": mix["arrivals"]["rate_rps"], "seed": seed,
        "due": len(cl.seen),
        "backlog_at_end": sum(s.first is None for s in cl.seen),
        "finished": sum(s.req is not None and s.finished for s in cl.seen),
        "ttft_p50_ms": client.percentile(ttft, 50) * 1e3,
        "ttft_p95_ms": client.percentile(ttft, 95) * 1e3,
        "tpot_ms": tpot * 1e3 if tpot else None,
        "last_vs_first_quarter_ttft": late,
    }), flush=True)
    front.run_until_drained(max_steps=1_000_000)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    args = ap.parse_args(argv)
    import jax

    from bench import harness, loadgen, manifest
    from repro.models import Model

    cell = manifest.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.use_cache(manifest.ROOT)
    cfg = cell.config
    block = manifest.load_block(cfg)
    model = Model(harness.model_config(cfg, block))
    params = jax.block_until_ready(block.weights.make_params(cfg,
                                                             args.seeds[0]))
    front, _engines, _ = harness.build(cell.deployment, model, params)
    del params
    print(f"setup {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    for rate in args.rates:
        for seed in args.seeds:
            window(front, loadgen.with_rate(cell.traffic, rate), seed,
                   args.seconds, cfg["vocab_size"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
