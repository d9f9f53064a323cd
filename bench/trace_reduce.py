"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, the
device programs that took most time, and the idle gaps between them.

Device time comes from the ``XLA Modules`` line of every ``/device:TPU:n``
plane: one event per program execution, named after the jitted function
(``jit_spmm_sell(<fingerprint>)``; the fingerprint is dropped).  A trace
without a TPU plane is an error, unless the caller asks for the host
stand-in: the operations that carry an ``hlo_module`` stat on the host's
threads, so that the reduction can be checked on a CPU trace.

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation`` events
(``window``, ``submit``, ``step``, ``poll``, ``wait``, ``warmup``), read from
any host thread by name.  Every time is in nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

#: the harness's own annotations, by name
SPANS = ("window", "warmup", "submit", "step", "poll", "wait")
#: label of an idle gap that no harness span covers
UNCOVERED = "other"

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    """What the reduction reads: device program executions per device and
    the harness's host spans, as (name, start_ns, end_ns) tuples."""

    device_ops: list[list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]

    @property
    def n_devices(self) -> int:
        return len(self.device_ops)

    def span(self, name: str) -> tuple[float, float]:
        """(start, end) of the one span called ``name`` (the window)."""
        found = [(s, e) for n, s, e in self.spans if n == name]
        if len(found) != 1:
            raise ValueError(f"expected one {name!r} span, found {len(found)}")
        return found[0]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path: str, host_stand_in: bool = False) -> Trace:
    """Read ``path`` with ``jax.profiler.ProfileData``.  Raises
    ``ValueError`` where the trace has no TPU plane, unless
    ``host_stand_in`` takes the host's ``hlo_module`` operations as the
    device's."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        profile = ProfileData.from_serialized_xspace(fh.read())
    device_ops, spans, cpu_ops = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(_FINGERPRINT.sub("", e.name), e.start_ns,
                    e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Modules"
                   for e in line.events]
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0:
                        module = _stats(e).get("hlo_module")
                        if module is not None:
                            cpu_ops.append((str(module), e.start_ns,
                                            e.start_ns + e.duration_ns))
    if not device_ops:
        if not host_stand_in:
            raise ValueError(f"{path}: no /device:TPU: plane in the trace")
        device_ops = [cpu_ops]
    return Trace(device_ops=device_ops, spans=spans)


class Intervals:
    """Disjoint, sorted (start, end) intervals: the union of the input."""

    def __init__(self, intervals):
        merged: list[list[float]] = []
        for s, e in sorted((s, e) for s, e in intervals if e > s):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]

    def __iter__(self):
        return iter(zip(self.starts, self.ends))

    def overlap(self, lo: float, hi: float) -> float:
        """Length of the union inside [lo, hi]."""
        total = 0.0
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        while i < len(self.starts) and self.starts[i] < hi:
            total += max(0.0, min(self.ends[i], hi) - max(self.starts[i], lo))
            i += 1
        return total


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Device busy nanoseconds inside [lo, hi], averaged over devices."""
    per = [Intervals((s, e) for _, s, e in ops).overlap(lo, hi)
           for ops in trace.device_ops]
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> list[tuple[str, float]]:
    """The ``n`` device programs with most time inside [lo, hi], as (name,
    seconds averaged over devices), longest first."""
    totals: dict[str, float] = {}
    for ops in trace.device_ops:
        for name, s, e in ops:
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    scale = 1e-9 / max(trace.n_devices, 1)
    return sorted(((k, v * scale) for k, v in totals.items()),
                  key=lambda kv: -kv[1])[:n]


def gaps(trace: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of the first device inside [lo, hi]."""
    out, t = [], lo
    for s, e in Intervals((s, e) for _, s, e in trace.device_ops[0]):
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _labelled_gaps(trace: Trace, lo: float, hi: float):
    """(label, start, end) of every idle gap inside [lo, hi]: the harness
    span (other than the window) that covers most of the gap, or
    :data:`UNCOVERED`.  The spans a harness records inside the window do
    not overlap one another."""
    spans = sorted((s, e, n) for n, s, e in trace.spans if n != "window")
    starts = [s for s, _, _ in spans]
    for gs, ge in gaps(trace, lo, hi):
        best, cover = UNCOVERED, 0.0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(spans) and spans[i][0] < ge:
            s, e, name = spans[i]
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
            i += 1
        yield best, gs, ge


def idle_by_label(trace: Trace, lo: float, hi: float, n: int = 10
                  ) -> list[tuple[str, float]]:
    """Idle time inside [lo, hi] grouped by the label of each gap, as
    ("<span> x<gaps>", seconds), largest first."""
    totals: dict[str, list] = {}
    for label, gs, ge in _labelled_gaps(trace, lo, hi):
        t = totals.setdefault(label, [0.0, 0])
        t[0] += ge - gs
        t[1] += 1
    return sorted(((f"{k} x{c}", v * 1e-9) for k, (v, c) in totals.items()),
                  key=lambda kv: -kv[1])[:n]


def longest_gaps(trace: Trace, lo: float, hi: float, n: int = 3
                 ) -> list[tuple[str, float, float]]:
    """The ``n`` longest idle gaps inside [lo, hi], as (label, seconds
    after ``lo``, seconds long), longest first."""
    found = sorted(_labelled_gaps(trace, lo, hi), key=lambda g: g[1] - g[2])
    return [(label, (gs - lo) * 1e-9, (ge - gs) * 1e-9)
            for label, gs, ge in found[:n]]


def host_minus_device(trace: Trace, name: str, lo: float, hi: float
                      ) -> list[float]:
    """For every ``name`` span inside [lo, hi]: its length minus the
    first device's busy time inside it, in nanoseconds."""
    merged = Intervals((s, e) for _, s, e in trace.device_ops[0])
    return [(e - s) - merged.overlap(s, e) for n, s, e in trace.spans
            if n == name and s >= lo and e <= hi]
