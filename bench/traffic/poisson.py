"""Open loop: independent requests at a fixed mean rate.

Every seed sends the same number of requests, ``round(rate_per_s *
seconds)``, with due times spread as a Poisson process given that count
(sorted uniform draws over the window, from the seed).  A request's latency
runs from its due time, so a stall that delays later sends is counted in
them; how late the generator sent each request is recorded beside it.
One still unanswered ``driver.answer_wait_s`` after the window closes
never came.
"""
from __future__ import annotations

import time

import numpy as np

#: stream of the seed that draws due times (the workload draws inputs
#: from the seed itself)
STREAM = 1


def due_offsets(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Sorted due times, in seconds after the window's start."""
    n = int(round(rate_per_s * seconds))
    rng = np.random.default_rng([seed, STREAM])
    return np.sort(rng.random(n)) * seconds


def drive(driver, traffic: dict, seconds: float, seed: int) -> float:
    """Run the loop; returns the window's start on the ``Driver``'s clock."""
    due = due_offsets(float(traffic["rate_per_s"]), seconds, seed)
    t0 = driver.clock()
    i = 0
    give_up = t0 + seconds + driver.answer_wait_s
    while i < len(due) or (driver.pending and driver.clock() < give_up):
        now = driver.clock()
        while i < len(due) and t0 + due[i] <= now:
            driver.submit(i, t0 + due[i])
            i += 1
        if driver.pending:
            driver.step()
            driver.collect()
        elif i < len(due):
            with driver.annotate("wait"):
                time.sleep(max(0.0, t0 + due[i] - driver.clock()))
    return t0
