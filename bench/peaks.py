"""Peak rates of one chip, by device kind, from ``peaks.json``."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name} (have {sorted(table)})")
    return table[device_kind]
