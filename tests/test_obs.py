"""Observability-subsystem tests (repro.obs + service integration).

The load-bearing guarantee is TRACE COMPLETENESS: every submit attempt —
served, queue-rejected, preflight-rejected, or failed inside a coalesced
group — retires exactly one closed root span, and mixed load leaves zero
orphans.  Around that: metric primitives (counter monotonicity, histogram
quantile error, frozen CounterDict contract), tracer mechanics (ring
eviction, idempotent end, fan-in links, both exporters), the launch
profiler (planned-vs-measured pairing through the service AND through the
module-level ops hook), registry timing summaries, and the obs_report
dashboard's --strict orphan gate.
"""
import dataclasses
import importlib.util
import io
import json
import os

import numpy as np
import pytest

import repro
from repro.analysis import LaunchPlanError
from repro.graphs import gen as G
from repro.kernels import ops
from repro.kernels.execspec import ExecSpec
from repro.obs import (
    Counter,
    CounterDict,
    Gauge,
    Histogram,
    LaunchProfiler,
    MetricsRegistry,
    Stopwatch,
    Tracer,
    now_s,
    now_us,
    profiled,
)
from repro.obs import trace as obs_trace
from repro.service import KernelRegistry, KernelService, QueueFull
from repro.service.service import STATS_KEYS
from repro.sparse import formats as F

RNG = np.random.default_rng(11)


MAT = F.random_csr(64, 64, 4.0, seed=5)
GRAPH = G.random_graph(n_nodes=32, avg_degree=3, seed=0)


def _bfs_levels(graph, source=0):
    """Level steps of a level-synchronous BFS from ``source``: one per
    level that reaches a vertex, plus the last, which reaches none."""
    adj = [{int(v) for v in row if v != G.PAD} for row in graph.adj]
    seen, frontier, levels = {source}, {source}, 1
    while True:
        frontier = {v for u in frontier for v in adj[u]} - seen
        if not frontier:
            return levels
        seen |= frontier
        levels += 1


GRAPH_LEVELS = _bfs_levels(GRAPH)


def make_service(**kw):
    reg = KernelRegistry()
    reg.register_matrix("m", MAT)
    kw.setdefault("tracer", Tracer())
    return KernelService(reg, n_slots=kw.pop("n_slots", 4),
                         interpret=True, **kw)


# ---------------------------------------------------------------------------
# Timer + Stopwatch
# ---------------------------------------------------------------------------


def test_stopwatch_measures_and_reads_live():
    assert now_us() > 0 and now_s() > 0
    sw = Stopwatch().start()
    live = sw.elapsed_us                       # readable while running
    assert live >= 0
    assert sw.stop() is sw                     # chains
    assert sw.elapsed_us >= live
    assert sw.elapsed_s * 1e6 == pytest.approx(sw.elapsed_us)
    with Stopwatch() as cm:
        pass
    assert cm.elapsed_us >= 0.0


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------


def test_counter_is_monotonic():
    c = Counter("served")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    c.set(10)
    with pytest.raises(ValueError, match="cannot decrease"):
        c.set(9)


def test_gauge_moves_both_ways():
    g = Gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4 and g.snapshot() == 4


def test_histogram_quantiles_within_bucket_error():
    h = Histogram("lat")
    for v in range(1, 1001):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    assert snap["mean"] == pytest.approx(500.5)
    # log-bucketed at base 2**0.25 -> quantiles good to ~±9%
    assert snap["p50"] == pytest.approx(500, rel=0.1)
    assert snap["p95"] == pytest.approx(950, rel=0.1)
    assert snap["p99"] == pytest.approx(990, rel=0.1)
    # quantiles clamp to the observed range, zero has its own bucket
    h2 = Histogram("z")
    h2.observe(0.0)
    assert h2.percentile(99) == 0.0
    assert Histogram("empty").snapshot()["count"] == 0


def test_registry_kinds_do_not_collide(tmp_path):
    m = MetricsRegistry()
    m.counter("served").inc()
    m.gauge("depth").set(3)
    m.histogram("lat").observe(7.0)
    with pytest.raises(TypeError, match="registered as"):
        m.gauge("served")
    assert "served" in m and "absent" not in m
    assert set(m.names()) == {"served", "depth", "lat"}
    path = tmp_path / "metrics.json"
    m.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert doc["served"] == 1 and doc["depth"] == 3
    assert doc["lat"]["count"] == 1


def test_counterdict_is_a_frozen_view_over_the_registry():
    m = MetricsRegistry()
    stats = CounterDict(m, ("served", "rejected"))
    stats["served"] += 2                       # get-then-set through Counter
    assert stats["served"] == 2
    assert m.counter("served").value == 2      # same underlying counter
    assert dict(stats) == {"served": 2, "rejected": 0}
    assert list(stats) == ["served", "rejected"] and len(stats) == 2
    with pytest.raises(KeyError):
        stats["typo"] = 1                      # key set is frozen
    with pytest.raises(TypeError, match="frozen"):
        del stats["served"]


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------


def test_span_tree_ids_and_idempotent_end():
    t = Tracer()
    root = t.start("request")
    assert root.trace_id == root.span_id       # roots name their own tree
    child = t.start("queued", parent=root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    t.end(child)
    t.end(child, status="error")               # second end keeps the verdict
    assert child.status == "ok"
    t.end(None)                                # defensive no-op
    assert t.open_count == 1
    t.end(root, status="error", error="boom")
    assert root.attrs["error"] == "boom"
    assert t.open_count == 0
    assert [s.name for s in t.closed_roots()] == ["request"]
    assert t.children(root) == [child]
    assert root.duration_us >= child.duration_us >= 0


def test_tracer_ring_bound_counts_evictions():
    t = Tracer(capacity=4)
    for i in range(6):
        t.end(t.start(f"s{i}"))
    assert len(t.spans()) == 4 and t.dropped == 2
    assert [s.name for s in t.spans()] == ["s2", "s3", "s4", "s5"]
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


def test_closed_roots_name_filter_excludes_launch_roots():
    t = Tracer()
    req = t.start("request")
    t.end(req)
    t.end(t.start("launch", links=[req]))      # parentless fan-in root
    assert len(t.closed_roots()) == 2
    assert len(t.closed_roots("request")) == 1


def test_span_contextmanager_records_errors():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("execute", slot=3):
            raise RuntimeError("kernel died")
    (s,) = t.spans()
    assert s.status == "error" and s.attrs["slot"] == 3


def test_exporters_roundtrip_and_flag_open_spans(tmp_path):
    t = Tracer()
    root = t.start("request")
    t.end(t.start("queued", parent=root))
    t.end(root)
    t.end(t.start("launch", links=[root], group_size=1))
    orphan = t.start("execute")                # left open on purpose
    path = tmp_path / "trace.jsonl"
    assert t.export_jsonl(str(path)) == 4
    docs = [json.loads(l) for l in path.read_text().splitlines()]
    assert sum(1 for d in docs if d.get("open")) == 1
    assert {d["name"] for d in docs} == {"request", "queued", "launch",
                                         "execute"}
    buf = io.StringIO()
    assert t.export_jsonl(buf, include_open=False) == 3
    # the fan-in link survives the round trip
    (launch,) = [d for d in docs if d["name"] == "launch"]
    assert launch["links"] == [root.span_id]

    t.end(orphan)
    t.reset()
    assert t.open_count == 0 and not t.spans() and t.dropped == 0


# ---------------------------------------------------------------------------
# Launch profiler (planned vs measured)
# ---------------------------------------------------------------------------


def test_profiler_pairs_plan_statics_with_wall(small=None):
    prof = LaunchProfiler()
    plan = dataclasses.make_dataclass(
        "P", ["kernel", "n_launches", "grid_cells", "peak_vmem_bytes", "ok"]
    )("spmm_sell", 2, 64, 1 << 16, True)
    for wall in (10.0, 30.0):
        prof.record(op="spmv", operand="m", wall_us=wall, plan=plan)
    (res,) = prof.residuals().values()
    assert res["op"] == "spmv" and res["count"] == 2
    assert res["wall_us_mean"] == pytest.approx(20.0)
    assert res["grid_cells"] == 64
    assert res["us_per_grid_cell"] == pytest.approx(20.0 / 64)
    assert prof.records(operand="m")[0].planned_ok is True


def test_ops_hook_profiles_kernel_launch_without_service():
    """The module-level hook reaches the kernel layer directly: a bare
    ops.spmv call under ``profiled()`` records a measured launch paired
    with the static plan, no service object anywhere."""
    csr = F.random_csr(64, 64, 4.0, seed=3)
    x = RNG.standard_normal(64)
    prof = LaunchProfiler()
    with profiled(prof):
        y = ops.spmv(csr, x, spec=ExecSpec(interpret=True))
    np.testing.assert_allclose(np.asarray(y), csr.matvec(x),
                               rtol=1e-10, atol=1e-10)
    recs = prof.records()
    assert recs and recs[0].op == "spmm" and recs[0].wall_us > 0
    assert recs[0].kernel and recs[0].grid_cells > 0
    # hook uninstalled on exit: further launches record nothing
    ops.spmv(csr, x, spec=ExecSpec(interpret=True))
    assert len(prof.records()) == len(recs)


# ---------------------------------------------------------------------------
# Service integration: completeness under every exit path
# ---------------------------------------------------------------------------


def test_served_request_closes_full_span_tree():
    svc = make_service()
    x = RNG.standard_normal(64)
    rid = svc.submit("spmv", "m", x)
    svc.drain()
    np.testing.assert_allclose(svc.poll(rid), MAT.matvec(x),
                               rtol=1e-10, atol=1e-10)
    t = svc.tracer
    assert t.open_count == 0
    (root,) = t.closed_roots("request")
    assert root.status == "ok" and root.attrs["rid"] == rid
    stages = {s.name for s in t.children(root)}
    assert stages == {"svc.preflight", "queued", "execute"}
    (launch,) = [s for s in t.spans() if s.name == "launch"]
    assert launch.parent_id is None            # fan-in root, not a child
    assert launch.links == (root.span_id,)
    assert launch.attrs["group_size"] == 1
    # gauges settle back to idle after the drain
    assert svc.metrics.get("queue_depth").value == 0
    assert svc.metrics.get("in_flight").value == 0
    assert svc.metrics.get("planned_vmem_bytes").value > 0
    assert svc.metrics.get("latency_us_class_kernel").snapshot()["count"] == 1
    assert "latency_us_spmv" not in svc.metrics    # per class, not per op


def test_queue_full_rejection_closes_root_as_rejected():
    svc = make_service(n_slots=2, max_queue=2)
    xs = [RNG.standard_normal(64) for _ in range(2)]
    for x in xs:
        svc.submit("spmv", "m", x)
    with pytest.raises(QueueFull):
        svc.submit("spmv", "m", xs[0])
    rejected = [s for s in svc.tracer.closed_roots("request")
                if s.status == "rejected"]
    assert len(rejected) == 1
    assert rejected[0].attrs["reason"] == "queue_full"
    svc.drain()
    assert svc.tracer.open_count == 0
    assert len(svc.tracer.closed_roots("request")) == 3


def test_preflight_rejection_closes_root_and_child():
    svc = make_service()
    record = svc.registry.get("m")
    good = record.tuned
    record.tuned = dataclasses.replace(good, k_block=1 << 24)
    with pytest.raises(LaunchPlanError):
        svc.submit("spmv", "m", np.ones(64))
    record.tuned = good
    t = svc.tracer
    assert t.open_count == 0
    (root,) = t.closed_roots("request")
    assert root.status == "rejected" and root.attrs["reason"] == "preflight"
    (pre,) = t.children(root)
    assert pre.name == "svc.preflight" and pre.status == "rejected"


def test_failed_groupmate_closes_as_error_others_ok():
    svc = make_service()
    x = RNG.standard_normal(64)
    bad = svc.submit("spmv", "m", RNG.standard_normal(63))
    good = svc.submit("spmv", "m", x)
    svc.drain()
    with pytest.raises(RuntimeError):
        svc.poll(bad)
    t = svc.tracer
    assert t.open_count == 0
    by_rid = {s.attrs["rid"]: s for s in t.closed_roots("request")}
    assert by_rid[bad].status == "error"
    assert "must have shape" in by_rid[bad].attrs["error"]
    assert by_rid[good].status == "ok"
    # both rode the same coalesced launch: one span, two fan-in links
    (launch,) = [s for s in t.spans() if s.name == "launch"]
    assert set(launch.links) == {by_rid[bad].span_id, by_rid[good].span_id}
    assert launch.attrs["group_size"] == 2
    assert svc.metrics.get("group_size").snapshot()["max"] == 2


def test_mixed_load_leaves_zero_orphans():
    """The acceptance invariant at test scale: served + queue-rejected +
    preflight-rejected + failed submits each retire exactly one closed
    request root, nothing stays open."""
    svc = make_service(n_slots=2, max_queue=4)
    attempts = 0
    record = svc.registry.get("m")
    good_tuned = record.tuned
    for wave in range(3):
        for i in range(6):
            attempts += 1
            n = 63 if (wave, i) == (1, 2) else 64  # one bad payload
            if (wave, i) == (2, 3):                # one poisoned preflight
                record.tuned = dataclasses.replace(good_tuned,
                                                   k_block=1 << 24)
            try:
                svc.submit("spmv", "m", RNG.standard_normal(n))
            except (QueueFull, LaunchPlanError):
                pass
            finally:
                record.tuned = good_tuned
        svc.step()
    svc.drain()
    t = svc.tracer
    assert t.open_count == 0, [s.name for s in t.open_spans()]
    assert len(t.closed_roots("request")) == attempts
    assert svc.stats["submitted"] + svc.stats["rejected"] + \
        svc.stats["preflight_rejected"] == attempts
    assert svc.stats["rejected"] > 0           # the mix really mixed
    assert svc.profiler.records()              # service-path profiling on
    assert svc.metrics.get("launch_wall_us_spmv").snapshot()["count"] > 0


def test_service_without_tracer_pays_nothing_and_still_counts(monkeypatch):
    def opened(*_a, **_k):
        raise AssertionError("a phase was opened with tracing off")

    monkeypatch.setattr(obs_trace, "_Phase", opened)
    svc = make_service(tracer=None)
    svc.registry.register_graph("g", GRAPH)
    svc.submit("spmv", "m", RNG.standard_normal(64))
    svc.submit("bfs", "g", source=0)
    svc.submit("pagerank", "g", damping=0.85, iters=3)
    svc.drain()
    assert svc.tracer is None
    assert not obs_trace.annotating() and obs_trace._PARENT is None
    assert svc.stats["served"] == 3            # CounterDict path unaffected
    assert svc.stats["launches"] == 3 and svc.stats["steps"] == 1
    assert dict(svc.stats) == {k: svc.stats[k] for k in STATS_KEYS}
    assert svc.stats["graph_steps"] == GRAPH_LEVELS + 3


# ---------------------------------------------------------------------------
# Phase spans
# ---------------------------------------------------------------------------


def test_phase_off_is_one_shared_null_context(monkeypatch):
    import tracemalloc

    def clock():
        raise AssertionError("a phase read the clock with tracing off")

    monkeypatch.setattr(obs_trace.timer, "now_us", clock)
    assert not obs_trace.annotating()
    a, b = obs_trace.phase("svc.prepare"), obs_trace.phase("graph.level")
    assert a is b
    assert obs_trace.children_of(None, None) is a
    assert obs_trace.children_of(Tracer(), None) is a
    with a as span:
        assert span is None
    for _ in range(10):                        # warm the call path
        with obs_trace.phase("graph.level"):
            pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(1000):
        with obs_trace.phase("graph.level"):
            pass
    grown = tracemalloc.take_snapshot().compare_to(before, "lineno")
    tracemalloc.stop()
    assert sum(d.count_diff for d in grown
               if "obs/trace.py" in str(d.traceback)) <= 0


def test_phases_nest_under_their_parent_and_restore_it():
    t = Tracer()
    root = t.start("request")
    with obs_trace.children_of(t, root):
        with obs_trace.phase("svc.launch", op="bfs") as outer:
            with obs_trace.phase("graph.level") as inner:
                assert inner.parent_id == outer.span_id
        with pytest.raises(RuntimeError):
            with obs_trace.phase("svc.fetch"):
                raise RuntimeError("device lost")
    t.end(root)
    assert obs_trace.phase("x") is obs_trace.phase("y")    # switched off
    by_name = {s.name: s for s in t.spans()}
    assert by_name["svc.launch"].parent_id == root.span_id
    assert by_name["svc.launch"].attrs == {"op": "bfs"}
    assert by_name["svc.fetch"].parent_id == root.span_id
    assert by_name["svc.fetch"].status == "error"
    assert t.open_count == 0 and t.closed_roots() == [root]


def test_annotate_opens_a_profiler_annotation_per_phase(monkeypatch):
    import jax.profiler

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("open", self.name))

        def __exit__(self, *exc):
            seen.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert obs_trace.annotate(True) is False
    try:
        assert obs_trace.annotating()
        with obs_trace.phase("graph.level") as span:
            with obs_trace.phase("graph.converge"):
                pass
        assert span is None                    # no tracer: no ring span
    finally:
        assert obs_trace.annotate(False) is True
    assert seen == [("open", "graph.level"), ("open", "graph.converge"),
                    ("close", "graph.converge"), ("close", "graph.level")]
    assert not obs_trace.annotating()


def test_launch_phases_close_into_the_ring_under_the_launch():
    svc = make_service()
    svc.registry.register_graph("g", GRAPH)
    svc.submit("spmv", "m", RNG.standard_normal(64))
    svc.submit("bfs", "g", source=0)
    svc.drain()
    t = svc.tracer
    assert t.open_count == 0
    assert len(t.closed_roots("request")) == 2
    for launch in (s for s in t.spans() if s.name == "launch"):
        kids = [s.name for s in t.children(launch)]
        assert kids == ["svc.prepare", "svc.launch", "svc.fetch",
                        "svc.split"], kids
    (bfs_launch,) = [s for s in t.spans() if s.name == "svc.launch"
                     and t.children(s)]
    levels = [s for s in t.children(bfs_launch) if s.name == "graph.level"]
    assert len(levels) == GRAPH_LEVELS == svc.stats["graph_steps"]
    for level in levels:
        assert [c.name for c in t.children(level)] == ["graph.converge"]
    # the scheduling phase has no parent span: profiler annotation only
    assert not [s for s in t.spans() if s.name == "svc.schedule"]


# ---------------------------------------------------------------------------
# Registry timing summary
# ---------------------------------------------------------------------------


def test_registry_summary_surfaces_register_timings():
    reg = KernelRegistry()
    reg.register_matrix("m", F.random_csr(64, 64, 4.0, seed=5))
    reg.register_graph("g", G.random_graph(n_nodes=32, avg_degree=3, seed=0))
    s = reg.summary()
    assert set(s["operands"]) == {"m", "g"}
    assert s["operands"]["m"]["register_us"] > 0
    assert s["operands"]["m"]["kind"] == "matrix"
    assert s["operands"]["g"]["kind"] == "graph"
    assert reg.metrics.get("register_us").snapshot()["count"] == 2
    assert reg.metrics.get("registered_matrix").value == 1


# ---------------------------------------------------------------------------
# obs_report dashboard
# ---------------------------------------------------------------------------


def _obs_report():
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    path = os.path.join(os.path.dirname(src_dir), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_renders_and_strict_gates_orphans(tmp_path, capsys):
    rep = _obs_report()
    svc = make_service(n_slots=2, max_queue=2)
    for _ in range(4):
        try:
            svc.submit("spmv", "m", RNG.standard_normal(64))
        except QueueFull:
            pass
        svc.step()
    svc.drain()
    trace = tmp_path / "trace.jsonl"
    metrics = tmp_path / "metrics.json"
    svc.tracer.export_jsonl(str(trace))
    svc.metrics.dump_json(str(metrics))

    assert rep.main([str(trace), "--metrics", str(metrics),
                     "--strict"]) == 0
    out = capsys.readouterr().out
    assert "closed request roots: 4" in out
    assert "open (orphan) spans:  0" in out
    assert "== launch fan-in ==" in out and "== metrics ==" in out

    # an open span trips the strict gate
    svc.tracer.start("execute")
    svc.tracer.export_jsonl(str(trace))
    assert rep.main([str(trace), "--strict"]) == 1
    assert rep.main([str(trace)]) == 0         # non-strict only reports
