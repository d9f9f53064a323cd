"""The program's phase spans in a profiler trace (``bench/phases.py``):
idle time split by phase on hand-made intervals, a real ``KernelService``
recorded under ``jax.profiler`` on the CPU, the ``programs_per_level``
reader, and one traced harness run with phase annotation on.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, phases  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "cpu_window.xplane.pb")
SMALL = {"graph500-s14": {"scale": 8},
         "hpcg-24": {"nx": 8, "ny": 8, "nz": 8}}


@pytest.fixture
def hand():
    """Device busy 0-10, 20-30, 60-70; idle 10-20, 30-60, 70-100."""
    return T.Trace(
        device_ops=[[("a", 0, 10), ("a", 20, 30), ("b", 60, 70)]],
        spans=[("window", 0, 100), ("step", 0, 50), ("poll", 50, 55),
               ("step", 58, 80)])


PHASES = [("svc.launch", 2, 40), ("graph.level", 5, 35),
          ("graph.converge", 12, 18), ("svc.fetch", 40, 45),
          ("svc.prepare", 58, 62), ("svc.split", 75, 90)]


def test_idle_parts_sum_to_the_idle_total(hand):
    idle = phases.idle_by_phase(hand, PHASES, 0, 100)
    total = sum(e - s for s, e in T.gaps(hand, 0, 100))
    assert sum(v for v, _ in idle.values()) == pytest.approx(total * 1e-9)
    assert total == 10 + 30 + 30


def test_innermost_phase_wins_then_harness_span_then_other(hand):
    idle = {k: (pytest.approx(v * 1e9), n) for k, (v, n) in
            phases.idle_by_phase(hand, PHASES, 0, 100).items()}
    assert idle == {
        "graph.level": (4 + 5, 2),         # 10-12, 18-20; 30-35
        "graph.converge": (6, 1),          # 12-18, inside graph.level
        "svc.launch": (5, 1),              # 35-40
        "svc.fetch": (5, 1),               # 40-45
        "svc.prepare": (2, 1),             # 58-60
        "svc.split": (15, 1),              # 75-90
        "step": (5 + 5, 2),                # 45-50, 70-75: a bare step
        "poll": (5, 1),                    # 50-55
        "other": (3 + 10, 2),              # 55-58, 90-100: no span
    }


def test_without_phases_the_split_follows_the_harness_spans(hand):
    idle = phases.idle_by_phase(hand, [], 0, 100)
    assert {k: round(v * 1e9) for k, (v, _) in idle.items()} == {
        "step": 10 + (20 + 2) + 10, "poll": 5, "other": 3 + 20}
    # where every gap lies inside one span, the labels agree with
    # trace_reduce's whole-gap labels
    inside = T.Trace(device_ops=hand.device_ops,
                     spans=[("window", 0, 100), ("step", 0, 60),
                            ("poll", 65, 100)])
    by_label = {k.split(" x")[0]: v
                for k, v in T.idle_by_label(inside, 0, 100)}
    split = {k: v for k, (v, _) in
             phases.idle_by_phase(inside, [], 0, 100).items()}
    assert split == pytest.approx(by_label)


def test_recorded_trace_splits_its_idle_by_harness_span():
    tr = T.load(RECORDED, host_stand_in=True)
    lo, hi = tr.span("window")
    idle = phases.idle_by_phase(tr, [], lo, hi)
    total = sum(e - s for s, e in T.gaps(tr, lo, hi)) * 1e-9
    assert sum(v for v, _ in idle.values()) == pytest.approx(total)
    assert set(idle) <= {"submit", "step", "poll", "wait", T.UNCOVERED}
    assert max(idle, key=lambda k: idle[k][0]) == "wait"
    assert idle["wait"][1] == 3


def test_recorded_trace_reads_as_before():
    """The readers the benchmark had read the recorded trace as they did
    before the program had phases."""
    from bench import readers

    tr = T.load(RECORDED, host_stand_in=True)
    run = harness.Run(setup_s=1.0, window_s=1.0, records=[],
                      stats={"served": 6, "launches": 3}, work={},
                      peaks={}, trace=tr, trace_window=tr.span("window"))
    assert readers.idle_share(run) == pytest.approx(95.117598415, rel=1e-9)
    assert readers.step_host_ms(run) == pytest.approx(0.484775, rel=1e-9)
    assert readers.group_size(run) == 2.0
    assert readers.roofline(run) is None             # no peaks on the CPU


def test_programs_per_level_reader_on_a_hand_made_run(hand):
    reader = harness.load_module(harness.reader_path(
        "programs_per_level.graph"))

    def run(stats, trace=hand):
        return harness.Run(setup_s=1.0, window_s=1.0, records=[],
                           stats=stats, work={}, peaks={}, trace=trace,
                           trace_window=(0, 65))
    assert reader.read(run({"graph_steps": 2})) == 3 / 2
    two = T.Trace(device_ops=hand.device_ops + [[("a", 0, 5)]],
                  spans=hand.spans)
    assert reader.read(run({"graph_steps": 2}, two)) == (3 + 1) / 2 / 2
    # a service without the counter, and an untraced run, read nothing
    assert reader.read(run({"launches": 1})) is None
    assert reader.read(run({"graph_steps": 2}, None)) is None


def _record_service(tmp_path):
    """A real service on the CPU: one BFS and one SpMV request per step
    inside harness-style ``window``/``submit``/``step``/``poll`` spans,
    under ``jax.profiler`` with phase annotation on."""
    import jax

    from repro.graphs import gen as G
    from repro.kernels import bfs
    from repro.obs import trace as obs_trace
    from repro.service import KernelRegistry, KernelService
    from repro.sparse import formats as F

    reg = KernelRegistry()
    reg.register_graph("g", G.random_graph(n_nodes=64, avg_degree=3, seed=1))
    reg.register_matrix("m", F.random_csr(64, 64, 4.0, seed=5))
    svc = KernelService(reg, n_slots=4, interpret=True)
    x = np.ones(64)
    svc.submit("bfs", "g", source=0)          # compile outside the trace
    svc.submit("spmv", "m", x)
    svc.drain()
    levels = []
    was = obs_trace.annotate(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for source in (3, 17):
                with jax.profiler.TraceAnnotation("submit"):
                    rids = [svc.submit("bfs", "g", source=source),
                            svc.submit("spmv", "m", x)]
                with jax.profiler.TraceAnnotation("step"):
                    svc.step()
                with jax.profiler.TraceAnnotation("poll"):
                    levels.append(bfs.levels_run(svc.poll(rids[0])))
                    for rid in rids:
                        svc.release(rid)
    finally:
        jax.profiler.stop_trace()
        obs_trace.annotate(was)
    path = T.find_xplane(str(tmp_path))
    return T.load(path, host_stand_in=True), phases.load(path), levels, svc


def test_recorded_service_phases_nest_inside_the_harness_spans(tmp_path):
    tr, found, levels, svc = _record_service(tmp_path)
    steps = [(s, e) for n, s, e in tr.spans if n == "step"]
    submits = [(s, e) for n, s, e in tr.spans if n == "submit"]
    assert len(steps) == 2 and all(lv > 1 for lv in levels)
    names = [n for n, _, _ in found]
    for name in ("svc.schedule", "svc.prepare", "svc.launch", "svc.fetch",
                 "svc.split"):
        assert names.count(name) >= 2, name
    assert names.count("svc.preflight") == 4
    for name, s, e in found:
        home = submits if name == "svc.preflight" else steps
        assert any(a <= s and e <= b for a, b in home), name
    # one graph.level per BFS level run, each with its blocking read
    assert names.count("graph.level") == sum(levels)
    assert names.count("graph.converge") == sum(levels)
    assert svc.stats["graph_steps"] >= sum(levels)
    # the harness's own reduction sees only its own spans
    assert {n for n, _, _ in tr.spans} <= set(T.SPANS)
    lo, hi = tr.span("window")
    idle = phases.idle_by_phase(tr, found, lo, hi)
    total = sum(e - s for s, e in T.gaps(tr, lo, hi)) * 1e-9
    assert sum(v for v, _ in idle.values()) == pytest.approx(total)
    inside_steps = sum(v for k, (v, _) in idle.items()
                       if k.startswith(phases.PREFIXES))
    assert inside_steps > idle.get("step", (0.0, 0))[0]


def small_cell(name):
    c = harness.find_cell(name)
    c.config = {**c.config, **SMALL[c.config["name"]]}
    return c


@pytest.mark.parametrize("name", ["bfs.graph500-s14.seq",
                                  "spmv.hpcg-24.clients8"])
def test_measure_traces_a_cell_with_its_phases(name, monkeypatch):
    from repro.obs import trace as obs_trace

    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    c = small_cell(name)
    monkeypatch.setattr(harness, "find_cell", lambda _name: c)
    line = phases.measure(name, 2**31 + 5, 1.0, t_start=0.0,
                          require_tpu=False)
    assert not obs_trace.annotating()        # switched back off
    assert line["correct"] and line["attempted"] > 0
    idle = line["idle_by_phase"]
    busy, window = line["device"]["busy_s"], line["device"]["window_s"]
    assert sum(v for v, _ in idle.values()) == pytest.approx(window - busy)
    counts = line["phase_counts"]
    got = line["phase_metrics"]
    if c.op == "bfs":
        assert counts["graph.level"] == line["notes"]["stats"]["graph_steps"]
        assert line["metrics"]["programs_per_level.graph"]["value"] == \
            pytest.approx(line["programs"] / counts["graph.level"])
        assert "level_idle_ms" in got
    else:
        assert counts["svc.launch"] == line["notes"]["stats"]["launches"]
        assert {"prep_idle_ms", "fetch_idle_ms"} <= set(got)
        assert "programs_per_level.graph" not in line["metrics"]
