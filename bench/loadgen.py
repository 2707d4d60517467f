"""Open-loop traffic from a mix file and a seed.

Adapted from the program's Poisson generator (``serving/loadgen.py``),
with clipped lognormal and uniform lengths and bursty arrivals added. A
mix file gives the arrival process and the length distributions, each
by name, and a ``shape_seed`` of its own::

    {"arrivals": {"process": "poisson", "rate_rps": 3.0}, "shape_seed": 7,
     "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                    "min": 128, "max": 2048},
     "output_len": {"dist": "uniform", "min": 16, "max": 64}}

The shape seed fixes, for a window of ``seconds``, one circular
sequence of requests: each with its prompt length, its output length and
the gap to the next. The run's seed picks where the circle is cut, which
request is due first, and draws the prompt tokens. So every seed offers
the same requests with the same neighbours, bursts and lulls, and seeds
differ only at the cut: a queue built in one burst is the same on every
seed, and the seed does not change the work. A window holds
``round(rate * seconds)`` requests, the first due at 0, with gaps drawn
by the named process and scaled to sum to the window: ``poisson`` draws them exponential,
``gamma`` from a gamma law of coefficient of variation ``cv`` (above 1,
bursts and lulls). ``closed`` is a closed loop of ``clients``: the
harness sends the next request of the list as soon as fewer than that
many are open, and the request is due when it is sent; ``rate_rps``
then only sizes the list, and should exceed what the deployment serves.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        out = rng.integers(lo, hi + 1, n)
    elif spec["dist"] == "lognormal":
        out = np.rint(spec["median"] * np.exp(spec["sigma"]
                                              * rng.standard_normal(n)))
    elif spec["dist"] == "fixed":
        out = np.full(n, lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


ARRIVALS = {
    "poisson": lambda spec, n, rng: rng.exponential(1.0, n),
    "gamma": lambda spec, n, rng: rng.gamma(1.0 / float(spec["cv"]) ** 2,
                                            1.0, n),
    "closed": lambda spec, n, rng: np.zeros(n),
}


def draw_gaps(spec: dict, n: int, rng) -> np.ndarray:
    if spec["process"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return ARRIVALS[spec["process"]](spec, n, rng)


def shapes(mix: dict, seconds: float) -> tuple:
    """(gaps, prompt lengths, output lengths) before any run's ordering."""
    n = max(1, int(round(float(mix["arrivals"]["rate_rps"]) * seconds)))
    rng = np.random.default_rng(int(mix["shape_seed"]))
    gaps = draw_gaps(mix["arrivals"], n, rng)
    if gaps.sum() > 0:
        gaps *= seconds / gaps.sum()
    return (gaps, draw_lengths(mix["prompt_len"], n, rng),
            draw_lengths(mix["output_len"], n, rng))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The window's arrivals in due order, deterministic in ``seed``."""
    gaps, plens, olens = shapes(mix, seconds)
    rng = np.random.default_rng(int(seed))
    cut = int(rng.integers(len(gaps)))
    gaps, plens, olens = (np.roll(x, -cut) for x in (gaps, plens, olens))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [
        Arrival(float(due[i]),
                rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                int(olens[i]))
        for i in range(len(due))
    ]


def clients(mix: dict):
    """The closed loop's number of clients, or None for open arrivals."""
    arr = mix["arrivals"]
    return int(arr["clients"]) if arr["process"] == "closed" else None


def with_rate(mix: dict, rate: float) -> dict:
    """``mix`` with its arrival rate replaced (for a sweep)."""
    return dict(mix, arrivals=dict(mix["arrivals"], rate_rps=rate))


def longest(mix: dict) -> int:
    """Most tokens one request can hold: longest prompt plus output."""
    return int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])

