#!/usr/bin/env python3
"""The program's phase spans in a profiler trace, and the device's idle
time split among them.

The program marks where its host is with ``repro.obs.trace.phase``:
``svc.*`` in the service (``svc.schedule``, ``svc.preflight``,
``svc.prepare``, ``svc.launch``, ``svc.fetch``, ``svc.split``) and
``graph.*`` in the graph drivers' loops (``graph.level``,
``graph.converge``).  With ``repro.obs.trace.annotate(True)`` each phase
is a ``jax.profiler.TraceAnnotation`` on the host plane, on the clock of
the device's ``XLA Modules`` events.  :func:`idle_by_phase` gives every
part of every idle gap of the device to the innermost phase the host was
in there, else to the harness span it was in, else to ``other``.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of a cell through the harness, with phase annotation
on, and prints one JSON line: the run's result line with the per-layer
metrics the benchmark reads, the harness's notes (stalls, counters), the
idle time by phase, the phase counts and the per-phase metrics of
``PERF.md`` section 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace_reduce  # noqa: E402

#: name prefixes of the program's phases
PREFIXES = ("svc.", "graph.")


def load(path: str) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every program phase on the host planes
    of the ``.xplane.pb`` at ``path``, on the trace's clock."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        profile = ProfileData.from_serialized_xspace(fh.read())
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def _innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint, sorted (start, end, name) pieces of the union of
    ``spans`` ((name, start, end, rank) tuples).  Each piece is named after
    the span covering it with the highest rank, then the latest start (the
    innermost of nested spans)."""
    order = sorted((s, e, rank, name) for name, s, e, rank in spans if e > s)
    bounds = sorted({t for s, e, _, _ in order for t in (s, e)})
    pieces, active, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][0] <= a:
            s, e, rank, name = order[i]
            active.append(((rank, s, -e), e, name))
            i += 1
        active = [x for x in active if x[1] > a]
        if active:
            pieces.append((a, b, max(active)[2]))
    return pieces


def idle_by_phase(trace, phases, lo: float, hi: float
                  ) -> dict[str, list]:
    """The first device's idle time inside [lo, hi], split by where the
    host was: ``{name: [seconds, gaps]}``, largest first.  Each part of a
    gap goes to the innermost program phase covering it; where none does,
    to the harness span covering it (``trace.spans`` other than
    ``window``); where neither does, to ``trace_reduce.UNCOVERED``.
    ``gaps`` counts the gaps that gave the name any part.  The parts sum
    to the idle total."""
    ranked = [(n, s, e, 1) for n, s, e in phases]
    ranked += [(n, s, e, 0) for n, s, e in trace.spans if n != "window"]
    pieces = _innermost(ranked)
    starts = [a for a, _, _ in pieces]
    totals: dict[str, list] = {}
    for gs, ge in trace_reduce.gaps(trace, lo, hi):
        parts: dict[str, float] = {}
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(pieces) and pieces[i][0] < ge:
            a, b, name = pieces[i]
            c = min(b, ge) - max(a, gs)
            if c > 0:
                parts[name] = parts.get(name, 0.0) + c
            i += 1
        rest = (ge - gs) - sum(parts.values())
        if rest > 0:
            parts[trace_reduce.UNCOVERED] = rest
        for name, c in parts.items():
            t = totals.setdefault(name, [0.0, 0])
            t[0] += c * 1e-9
            t[1] += 1
    return dict(sorted(totals.items(), key=lambda kv: -kv[1][0]))


def count(phases, name: str, lo: float, hi: float) -> int:
    """``name`` phases that start inside [lo, hi]."""
    return sum(1 for n, s, _ in phases if n == name and lo <= s <= hi)


def programs(trace, lo: float, hi: float) -> float:
    """Device program executions that start inside [lo, hi], averaged
    over the devices."""
    per = [sum(1 for _, s, _ in ops if lo <= s <= hi)
           for ops in trace.device_ops]
    return sum(per) / len(per) if per else 0.0


def window(trace) -> tuple[float, float]:
    """The harness's traced window: the first ``submit`` to the end of the
    last ``poll`` inside the ``window`` span."""
    lo, hi = trace.span("window")
    first = min((s for n, s, _ in trace.spans
                 if n == "submit" and lo <= s <= hi), default=lo)
    last = max((e for n, _, e in trace.spans
                if n == "poll" and lo <= e <= hi), default=hi)
    return first, last


def phase_metrics(idle: dict, levels: int, launches: int) -> dict:
    """The per-phase metrics, in ms, where they have something to read:
    idle in the level loop per level; idle in ``svc.prepare``, and in
    ``svc.fetch`` with ``svc.split``, per launch."""
    def ms(names, per):
        if not per or not any(n in idle for n in names):
            return None
        return 1e3 * sum(idle[n][0] for n in names if n in idle) / per

    found = {
        "level_idle_ms": ms(("graph.level", "graph.converge"), levels),
        "prep_idle_ms": ms(("svc.prepare",), launches),
        "fetch_idle_ms": ms(("svc.fetch", "svc.split"), launches),
    }
    return {k: v for k, v in found.items() if v is not None}


@contextlib.contextmanager
def _kept(store: dict):
    """While open, the harness's load of its trace also keeps the trace
    and reads the phases from the same file (the harness removes the file
    once it has read it)."""
    harness_load = trace_reduce.load

    def load_and_keep(path, **kw):
        store["phases"] = load(path)
        store["trace"] = harness_load(path, **kw)
        return store["trace"]

    trace_reduce.load = load_and_keep
    try:
        yield store
    finally:
        trace_reduce.load = harness_load


def measure(cell: str, seed: int, seconds: float, *, t_start: float,
            require_tpu: bool = True) -> dict:
    """One traced harness run of ``cell`` with phase annotation on; returns
    its result line with the phase tables added."""
    from bench import harness
    from repro.obs import trace as obs_trace

    store: dict = {}
    was = obs_trace.annotate(True)
    try:
        with _kept(store):
            line, notes = harness.run(cell, seed, seconds, True,
                                      t_start=t_start,
                                      require_tpu=require_tpu)
    finally:
        obs_trace.annotate(was)
    tr, phases = store["trace"], store["phases"]
    lo, hi = window(tr)
    idle = idle_by_phase(tr, phases, lo, hi)
    names = sorted({n for n, _, _ in phases})
    levels = count(phases, "graph.level", lo, hi)
    line["notes"] = notes
    line["idle_by_phase"] = idle
    line["phase_counts"] = {n: count(phases, n, lo, hi) for n in names}
    line["programs"] = programs(tr, lo, hi)
    line["phase_metrics"] = phase_metrics(idle, levels,
                                          notes["stats"].get("launches", 0))
    return line


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness

    try:
        line = measure(args.workload, args.seed, args.seconds,
                       t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
