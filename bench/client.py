"""What the client sees: token stamps on the harness's clock.

The harness steps the engine and, after each step, reads how far each
open request's ``generated`` list has grown. New tokens are stamped with
the harness's ``perf_counter`` at that moment. Nothing the program
records (its response latencies or its stage accounting) enters these
numbers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Seen:
    """One request as the client saw it."""

    due: float  # when the schedule said to send it (harness clock)
    submitted: float
    req: object  # the program's request object
    max_new: int
    first: Optional[float] = None  # stamp of the first token
    last: Optional[float] = None  # stamp of the newest token
    n: int = 0  # tokens seen

    @property
    def finished(self) -> bool:
        return self.n >= self.max_new


class Client:
    def __init__(self):
        self.seen: list[Seen] = []
        self._open: list[Seen] = []

    def sent(self, req, due: float, submitted: float, max_new: int) -> Seen:
        s = Seen(due, submitted, req, max_new)
        self.seen.append(s)
        self._open.append(s)
        return s

    def observe(self, now: float) -> None:
        """Stamp every token that appeared since the last call with ``now``."""
        still = []
        for s in self._open:
            n = len(s.req.generated)
            if n > s.n:
                if s.n == 0:
                    s.first = now
                s.last = now
                s.n = n
            if not s.finished:
                still.append(s)
        self._open = still

    @property
    def open(self) -> list:
        return self._open


def ttft_s(seen: list, end: float) -> np.ndarray:
    """Time from each request's due time to its first token; a request
    with no token by ``end`` counts with the time it has waited."""
    return np.asarray([(s.first if s.first is not None else end) - s.due
                       for s in seen])


def tpot_s(seen: list) -> Optional[float]:
    """All gaps between successive tokens, summed over every request, over
    the number of those gaps: one ratio for the whole window. Tokens that
    arrive together are gaps of 0."""
    span = sum(s.last - s.first for s in seen if s.n >= 2)
    gaps = sum(s.n - 1 for s in seen if s.n >= 2)
    return span / gaps if gaps else None


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))
