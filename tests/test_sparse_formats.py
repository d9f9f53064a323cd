"""Property tests for the sparse/graph substrates."""
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.graphs import gen as G
from repro.sparse import formats as F


@given(
    n=st.integers(min_value=1, max_value=60),
    m=st.integers(min_value=1, max_value=60),
    density=st.floats(min_value=0.02, max_value=0.5),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_csr_dense_roundtrip(n, m, density, seed):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n, m)) * (rng.random((n, m)) < density)
    csr = F.csr_from_dense(dense)
    np.testing.assert_array_equal(F.csr_to_dense(csr), dense)


@given(
    n=st.integers(min_value=1, max_value=80),
    avg=st.floats(min_value=1.0, max_value=6.0),
    c=st.sampled_from([4, 16, 32]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_ellpack_matvec_matches_csr(n, avg, c, seed):
    csr = F.random_csr(n, n, avg, seed=seed)
    ell = F.csr_to_ellpack(csr, c=c)
    x = np.random.default_rng(seed).standard_normal(n)
    np.testing.assert_allclose(ell.matvec(x), csr.matvec(x), rtol=1e-12, atol=1e-12)


@given(
    n=st.integers(min_value=1, max_value=80),
    avg=st.floats(min_value=1.0, max_value=6.0),
    c=st.sampled_from([4, 16]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_sell_matvec_matches_csr(n, avg, c, seed):
    csr = F.random_csr(n, n, avg, seed=seed)
    sell = F.csr_to_sell(csr, c=c, sigma=4 * c)
    x = np.random.default_rng(seed).standard_normal(n)
    np.testing.assert_allclose(sell.matvec(x), csr.matvec(x), rtol=1e-12, atol=1e-12)


def test_sell_pads_less_than_ellpack():
    """Sigma-sorting exists to cut padding: must never pad MORE."""
    csr = F.random_csr(2000, 2000, 8.0, seed=0)
    ell = F.csr_to_ellpack(csr, c=64)
    sell = F.csr_to_sell(csr, c=64, sigma=512)
    assert sell.pad_factor <= ell.pad_factor
    assert sell.pad_factor < 2.5


def test_cage10_like_statistics():
    m = F.cage10_like(seed=1)
    assert m.n_rows == m.n_cols == 11_397
    assert abs(m.nnz / m.n_rows - 13.2) < 1.0
    assert int(m.row_lengths.max()) <= 40


def test_graph_transpose_involution_edges():
    g = G.random_graph(n_nodes=64, avg_degree=4, seed=0)
    gt = g.transpose()
    # edge sets must match: (u,v) in g iff (v,u) in gt
    def edges(graph):
        src, k = np.nonzero(graph.adj != G.PAD)
        return set(zip(src.tolist(), graph.adj[src, k].tolist()))
    assert {(v, u) for (u, v) in edges(g)} == edges(gt)
    assert g.n_edges == gt.n_edges


def test_rmat_graph_is_skewed():
    g = G.rmat_graph(n_nodes=1 << 10, avg_degree=8, seed=0)
    deg = g.out_degree
    assert deg.max() >= 4 * max(deg.mean(), 1)  # heavy tail


@pytest.mark.parametrize("gen", [G.random_graph, G.rmat_graph])
def test_generators_produce_valid_ellpack(gen):
    g = gen(n_nodes=128, avg_degree=4, seed=3)
    valid = g.adj[g.adj != G.PAD]
    assert ((valid >= 0) & (valid < g.n_nodes)).all()


# ---------------------------------------------------------------------------
# Split hub rows of the reverse-graph SELL layout
# ---------------------------------------------------------------------------


def hub_graph(n: int = 300, hub: int = 5, seed: int = 0) -> G.EllpackGraph:
    """A random digraph plus an edge from every other node to ``hub``: one
    in-degree of about n against a mean of about 5."""
    g = G.random_graph(n, avg_degree=4, seed=seed, connected_ring=False)
    adj = np.concatenate([g.adj, np.full((n, 1), G.PAD, np.int32)], axis=1)
    others = np.arange(n) != hub
    adj[others, -1] = hub
    return G.EllpackGraph(adj=adj, n_nodes=n)


def _lanes(slabs):
    """(owner, row length, stored neighbours) of every lane of the slabs."""
    owners = np.concatenate([m.reshape(-1) for m in slabs.bucket_nodes])
    rows = [a.reshape(-1, a.shape[-1]) for a in slabs.bucket_adj]
    lengths = np.concatenate([(r != G.PAD).sum(axis=1) for r in rows])
    return owners, lengths, rows


@pytest.mark.parametrize("n", [70, 129, 300])
def test_split_layout_holds_every_edge_once_in_short_rows(n):
    width = F.SPLIT_WIDTH
    g = hub_graph(n)
    deg = G.in_degree(g)
    assert deg.max() > width
    slabs = G.graph_to_sell_slabs(g, c=16, sigma=64, reverse=True)
    owners, lengths, rows = _lanes(slabs)
    # every stored in-edge appears exactly once, under its owning node
    got = sorted((int(o), int(v)) for o, r in zip(
        owners, (row for rs in rows for row in rs)) for v in r[r != G.PAD])
    src, k = np.nonzero(g.adj != G.PAD)
    assert got == sorted(zip(g.adj[src, k].tolist(), src.tolist()))
    assert lengths.max() <= width
    # owners repeat for split nodes only, ceil(d / W) times; padding lanes
    # map to the dump slot and hold nothing
    real = owners < g.n_nodes
    assert (lengths[~real] == 0).all()
    counts = np.bincount(owners[real], minlength=g.n_nodes)
    np.testing.assert_array_equal(
        counts, np.maximum(-(-deg // width), 1))
    stats = G.split_stats(slabs)
    assert stats["split_width"] == width
    assert stats["split_nodes"] == int((deg > width).sum()) >= 1
    assert stats["virtual_rows"] == int(counts.sum())
    assert stats["padded_slots"] == slabs.padded_entries


def test_split_rows_are_near_equal_and_ordered():
    assert F.SPLIT_WIDTH == 64
    owner, lengths = G.split_rows(np.array([0, 130, 64, 65]))
    np.testing.assert_array_equal(owner, [0, 1, 1, 1, 2, 3, 3])
    np.testing.assert_array_equal(lengths, [0, 44, 43, 43, 64, 33, 32])


@pytest.mark.parametrize("c,sigma", [(8, 32), (32, 128)])
def test_hub_free_graph_keeps_todays_layout(c, sigma):
    """No row above the split width: one row per node, holding exactly
    that node's row of the degree-padded reverse graph, in slices sorted
    and bucketed as before the split existed."""
    g = G.random_graph(400, avg_degree=6, seed=2)
    deg = G.in_degree(g)
    assert deg.max() <= F.SPLIT_WIDTH
    owner, lengths = G.split_rows(deg)
    np.testing.assert_array_equal(owner, np.arange(g.n_nodes))
    np.testing.assert_array_equal(lengths, deg)
    got = G.graph_to_sell_slabs(g, c=c, sigma=sigma, reverse=True)
    order = F.sigma_sort_order(deg, sigma)
    bwidths = F.next_pow2(F.slice_widths(deg, order, c))
    assert got.widths == tuple(int(w) for w in np.unique(bwidths))
    lanes = np.full(len(bwidths) * c, g.n_nodes)
    lanes[:g.n_nodes] = order
    lanes = lanes.reshape(-1, c)
    radj = g.transpose().adj
    for w, adj, nodes in zip(got.widths, got.bucket_adj, got.bucket_nodes):
        want_nodes = lanes[bwidths == w]
        np.testing.assert_array_equal(nodes, want_nodes)
        want = np.full(adj.shape, G.PAD, np.int32)
        for s, c_ in zip(*np.nonzero(want_nodes < g.n_nodes)):
            row = radj[want_nodes[s, c_]]
            row = row[row != G.PAD]
            want[s, c_, :len(row)] = row
        np.testing.assert_array_equal(adj, want)
    assert G.split_stats(got) == {
        "split_width": None, "split_nodes": 0, "virtual_rows": g.n_nodes,
        "padded_slots": got.padded_entries}


@pytest.mark.parametrize("n_shards", [2, 3])
def test_sharded_split_keeps_a_nodes_rows_in_its_shard(n_shards):
    width = F.SPLIT_WIDTH
    g = hub_graph(seed=1)
    deg = G.in_degree(g)
    sg = G.shard_graph_slabs(g, c=16, n_shards=n_shards, reverse=True)
    assert G.split_stats(sg)["split_nodes"] >= 1
    counts = np.zeros(g.n_nodes + 1, np.int64)
    for d in range(sg.n_shards):
        lo, hi = sg.node_starts[d], sg.node_starts[d] + sg.node_counts[d]
        for adj, nodes in zip(sg.bucket_adj, sg.bucket_nodes):
            owners = nodes[d].reshape(-1)
            real = owners < g.n_nodes
            assert ((owners[real] >= lo) & (owners[real] < hi)).all()
            assert (adj[d].reshape(len(owners), -1)[~real] == G.PAD).all()
            assert ((adj[d] != G.PAD).sum(axis=-1) <= width).all()
            counts += np.bincount(owners, minlength=g.n_nodes + 1)
    np.testing.assert_array_equal(counts[:-1],
                                  np.maximum(-(-deg // width), 1))
