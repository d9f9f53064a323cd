"""Arithmetic the per-metric readers in ``bench/metrics/`` share.

Each reader returns None where its run has nothing to read (no trace, no
completed work, no device time), and the harness then leaves the metric
out of the result line.  A share of a roofline is never reported as 0.
"""
from __future__ import annotations

import statistics

from bench import trace_reduce


def busy_s(run) -> float | None:
    """Device busy seconds inside the traced window (union of program
    executions, averaged over the chips)."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = trace_reduce.busy(run.trace, lo, hi) * 1e-9
    return busy if busy > 0 else None


def roofline(run) -> float | None:
    """Percent of the HBM roofline: the least time the algorithmic bytes
    of the completed work take at the chip's peak bandwidth, over the
    device's busy time."""
    busy = busy_s(run)
    moved = run.work.get("bytes", 0)
    if busy is None or not moved or "hbm_bytes_per_s" not in run.peaks:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / busy


def idle_share(run) -> float | None:
    """Percent of the traced window in which no program ran on the
    device."""
    busy = busy_s(run)
    if busy is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))


def group_size(run) -> float | None:
    """Requests served per batched launch over the window."""
    launches = run.stats.get("launches", 0)
    return run.stats["served"] / launches if launches else None


def step_host_ms(run) -> float | None:
    """Median over the service's steps of the step's wall time minus the
    device's busy time inside it, in ms."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    host = trace_reduce.host_minus_device(run.trace, "step", lo, hi)
    return statistics.median(host) * 1e-6 if host else None


def rate(run, key: str) -> float | None:
    """``run.work[key]`` per second of the window."""
    count = run.work.get(key, 0)
    return count / run.window_s if count and run.window_s > 0 else None
