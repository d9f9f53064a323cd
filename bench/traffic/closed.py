"""Closed loop: ``clients`` callers, each sending its next request as soon
as its previous reply returns.

All clients send at the window's start.  No request is sent after
``seconds``; the window ends when the last one sent has returned, so every
request counted is counted whole.
"""
from __future__ import annotations


def drive(driver, traffic: dict, seconds: float, seed: int) -> float:
    """Run the loop; returns the window's start on the ``Driver``'s clock.
    A request is due when its client sends it."""
    del seed                                   # the workload draws inputs
    t0 = driver.clock()
    end = t0 + seconds
    sent = 0
    for _ in range(int(traffic["clients"])):
        driver.submit(sent, t0)
        sent += 1
    while driver.pending and driver.clock() < end + driver.answer_wait_s:
        driver.step()
        finished = driver.collect()
        now = driver.clock()            # one decision for the whole step, so
        if now < end:                   # clients that step together stay
            for _ in finished:          # together (a group of the same width)
                driver.submit(sent, now)
                sent += 1
    return t0
