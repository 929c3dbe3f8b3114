"""The closed-loop window and its arithmetic.

One client sends queries one after another (``FrontDoor`` lanes run
serially). Queries come in cycles: each cycle runs every template of the
traffic once, in an order shuffled from the seed. The window starts a new
cycle while fewer than ``seconds`` have passed and always finishes the
cycle it started, so every seed does the same work in another order and
every rate is all the queries over all the time, with no partial query.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Iterator, Sequence

import numpy as np


def cycle_orders(templates: Sequence[str], seed: int) -> Iterator[list]:
    """Endless cycles of ``templates``, each shuffled from ``seed``."""
    rng = np.random.default_rng((int(seed) % 2**64, 0x5EED))
    names = list(templates)
    while True:
        yield [names[i] for i in rng.permutation(len(names))]


def run_window(orders: Iterator[list], run_query: Callable[[str], dict],
               seconds: float, clock=time.perf_counter) -> tuple:
    """Run whole cycles from ``orders`` until ``seconds`` have passed;
    returns (records, t_start, t_end) on ``clock``."""
    records = []
    t0 = clock()
    while True:
        for name in next(orders):
            records.append(run_query(name))
        if clock() - t0 >= seconds:
            return records, t0, clock()


def rate(n: int, t0: float, t1: float) -> float:
    """Completed items per second over the window [t0, t1]."""
    return n / (t1 - t0)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100): the smallest value
    with at least ``q``% of the values at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]
