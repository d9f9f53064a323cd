"""``slots_per_level.graph``: padded slots a graph node step sweeps per
state column, from the service's own counters."""
import numpy as np
import pytest

from bench import harness


def _run(stats):
    return harness.Run(setup_s=1.0, window_s=1.0, records=[], stats=stats,
                       work={}, peaks={})


def test_slots_per_level_reader_reads_the_counters():
    reader = harness.load_module(harness.reader_path("slots_per_level.graph"))
    assert reader.read(_run({"graph_steps": 4, "graph_slots": 10})) == 2.5
    # a program without the counter (as the parent has it), and a window
    # with no node step, read nothing
    assert reader.read(_run({"graph_steps": 4})) is None
    assert reader.read(_run({"graph_steps": 0, "graph_slots": 0})) is None


@pytest.mark.parametrize("cell", ["bfs.graph500-s14.seq",
                                  "pagerank.graph500-s14.seq"])
def test_graph_cells_report_slots_per_level(cell):
    assert "slots_per_level.graph" in [m["name"] for m in
                                       harness.find_cell(cell).per_layer]


def test_service_counts_slots_per_node_step():
    """One BFS search and one two-config PageRank through a real service:
    each node step adds the operand's padded slots once per column."""
    from repro.graphs import gen as G
    from repro.service import KernelRegistry, KernelService

    g = G.rmat_graph(n_nodes=256, avg_degree=6, seed=1)
    reg = KernelRegistry()
    op = reg.register_graph("g", g)
    svc = KernelService(reg, n_slots=4)
    svc.submit("bfs", "g", source=3)
    svc.drain()
    levels = svc.stats["graph_steps"]
    assert svc.stats["graph_slots"] == levels * op.split["padded_slots"]
    svc.submit("pagerank", "g", iters=3)
    svc.submit("pagerank", "g", iters=5, damping=0.5)
    svc.drain()
    reader = harness.load_module(harness.reader_path("slots_per_level.graph"))
    got = reader.read(_run(dict(svc.stats)))
    want = (levels + 2 * 5) / (levels + 5) * op.split["padded_slots"]
    assert got == pytest.approx(want) and np.isfinite(got)
