"""Readings for the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell as ``run.py`` makes it (a window of
``--seconds`` at the cell's own load), after which the reference reads
two gaps on the same sample: the served tokens' (the program's reading)
and that of the tokens the float8 control ranks first (the control's
reading). The limit in the cell's deployment file lies between the
largest program reading and the smallest control reading. Prints one
JSON line per seed; the benchmark's own runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    t = T_START
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "setup_s": out["metrics"]["setup_s"]["value"],
                          "tokens_checked":
                              out["checks"]["tokens_checked"]["value"],
                          **out.get("readings", {})}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
