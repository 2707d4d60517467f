"""Chip benchmark of the serving path: data files plus one harness.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything a cell needs is found by name: its model sizes in
``configs/``, the weights, reference and work counts of the
configuration's ``family`` in ``blocks/<family>/``, its deployment in
``deployments/<cell>.json``, its traffic mix in ``traffic/``, and each
per-layer metric's reader in ``metrics/``.
"""
