"""SELL-C-sigma coverage: packers, bucketed slabs, device kernels, tuner.

Property tests (hypothesis, degrading to the deterministic fixed-example
grid via tests/_hypothesis_fallback.py) assert that every layout —
ELLPACK, ragged SELL, width-bucketed SELL slabs — computes the same matvec
as the CSR reference across a (C, sigma, skew) grid, including empty rows
and single-slice matrices; plus the ops-level dispatch, the repack-instead-
of-raise path, the (C, sigma) tuner, and the sigma-sorted graph kernels.
"""
import warnings

import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core.autotune import measured_pad_factor, tune_sell_layout
from repro.graphs import gen as G
from repro.kernels import ops
from repro.sparse import formats as F

RNG = np.random.default_rng(99)


# ---------------------------------------------------------------------------
# Layout equivalence: every format's matvec == CSR reference
# ---------------------------------------------------------------------------


@given(
    n=st.integers(min_value=1, max_value=90),
    c=st.sampled_from([4, 16, 32]),
    sigma_factor=st.sampled_from([1, 4, 8]),
    skew=st.sampled_from([0.0, 0.8, 1.6]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_all_layouts_matvec_match_csr(n, c, sigma_factor, skew, seed):
    csr = F.random_csr(n, n + 2, 4.0, seed=seed, skew=skew)
    x = np.random.default_rng(seed).standard_normal(n + 2)
    want = csr.matvec(x)
    ell = F.csr_to_ellpack(csr, c=c)
    sell = F.csr_to_sell(csr, c=c, sigma=sigma_factor * c)
    slabs = F.csr_to_sell_slabs(csr, c=c, sigma=sigma_factor * c)
    np.testing.assert_allclose(ell.matvec(x), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(sell.matvec(x), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(slabs.matvec(x), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(
        F.sell_to_slabs(sell).matvec(x), want, rtol=1e-12, atol=1e-12
    )


def test_empty_rows_and_single_slice():
    dense = np.zeros((6, 5))
    dense[0, 1] = 2.0
    dense[3, [0, 2, 4]] = [1.0, -1.5, 3.0]   # rows 1,2,4,5 empty
    csr = F.csr_from_dense(dense)
    x = RNG.standard_normal(5)
    want = dense @ x
    for c, sigma in [(4, 8), (8, 8), (16, 16)]:  # c=8,16 > n_rows: single slice
        slabs = F.csr_to_sell_slabs(csr, c=c, sigma=sigma)
        np.testing.assert_allclose(slabs.matvec(x), want, atol=1e-12)
        got = np.asarray(ops.spmv(slabs, x, vl=c))
        np.testing.assert_allclose(got, want, atol=1e-10)


def test_all_empty_matrix():
    csr = F.csr_from_dense(np.zeros((5, 4)))
    slabs = F.csr_to_sell_slabs(csr, c=4)
    x = RNG.standard_normal(4)
    np.testing.assert_allclose(slabs.matvec(x), np.zeros(5), atol=1e-15)
    np.testing.assert_allclose(np.asarray(ops.spmv(slabs, x, vl=4)), np.zeros(5), atol=1e-15)


# ---------------------------------------------------------------------------
# Format round trips
# ---------------------------------------------------------------------------


@given(
    n=st.integers(min_value=1, max_value=70),
    c=st.sampled_from([4, 16]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_to_csr_round_trips(n, c, seed):
    csr = F.random_csr(n, n, 3.0, seed=seed, skew=1.0)
    for packed in (
        F.csr_to_ellpack(csr, c=c),
        F.csr_to_sell_slabs(csr, c=c),
        F.csr_to_sell(csr, c=c),
    ):
        back = F.to_csr(packed)
        np.testing.assert_array_equal(back.indptr, csr.indptr)
        np.testing.assert_array_equal(back.indices, csr.indices)
        np.testing.assert_allclose(back.data, csr.data)


# ---------------------------------------------------------------------------
# Device kernel: bucketed SELL through pallas_call
# ---------------------------------------------------------------------------


@given(
    n=st.integers(min_value=1, max_value=120),
    vl=st.sampled_from([8, 16, 64]),
    skew=st.sampled_from([0.0, 1.2]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_spmv_sell_kernel_vs_csr(n, vl, skew, seed):
    m = F.random_csr(n, n + 3, 5.0, seed=seed, skew=skew)
    x = np.random.default_rng(seed).standard_normal(n + 3)
    got = np.asarray(ops.spmv(m, x, vl=vl))       # CSR dispatches to slabs
    np.testing.assert_allclose(got, m.matvec(x), rtol=1e-10, atol=1e-10)


def test_spmv_sell_cage10_matches_csr():
    """Acceptance: bucketed SELL through pallas on the paper's input."""
    m = F.cage10_like(seed=0)
    slabs, tuned = ops.pack_tuned(m)
    assert slabs.pad_factor < 2.0                  # sigma-sort earns its keep
    x = RNG.standard_normal(m.n_cols)
    got = np.asarray(ops.spmv(slabs, x, vl=tuned.c, w_block=tuned.w_block))
    np.testing.assert_allclose(got, m.matvec(x), rtol=1e-10, atol=1e-10)


def test_spmv_repacks_on_vl_mismatch_and_records_it():
    """A C/vl mismatch repacks (correct result, no warning spam) and records
    the event + layout in the TuneCache; see test_service.py for the
    no-second-repack regression."""
    from repro.service.tunecache import TuneCache

    m = F.random_csr(100, 100, 5.0, seed=0)
    ell = F.csr_to_ellpack(m, c=32)
    x = RNG.standard_normal(100)
    cache = TuneCache()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = np.asarray(ops.spmv(ell, x, vl=64, cache=cache))
    assert not any("repack" in str(w.message) for w in caught)
    np.testing.assert_allclose(got, m.matvec(x), rtol=1e-10, atol=1e-10)
    assert sum(cache.repacks.values()) == 1
    assert cache.stats["packed"] == 1              # the slabs were kept


def test_bucketed_sell_pads_less_than_ellpack_on_skew():
    """Acceptance: pad_factor(bucketed SELL) < pad_factor(ELLPACK) on skew."""
    csr = F.random_csr(2000, 2000, 8.0, seed=3, skew=1.2)
    ell = F.csr_to_ellpack(csr, c=128)
    slabs = F.csr_to_sell_slabs(csr, c=128, sigma=1024)
    assert slabs.pad_factor < ell.pad_factor / 2   # >= 2x padded-FLOP cut
    assert slabs.n_buckets <= int(np.log2(ell.width)) + 2


# ---------------------------------------------------------------------------
# Tuner
# ---------------------------------------------------------------------------


def test_measured_pad_factor_matches_packer():
    csr = F.random_csr(500, 500, 6.0, seed=5, skew=1.0)
    for c, sigma in [(16, 64), (64, 512)]:
        slabs = F.csr_to_sell_slabs(csr, c=c, sigma=sigma)
        assert measured_pad_factor(csr.row_lengths, c, sigma) == pytest.approx(
            slabs.pad_factor
        )


def test_tune_sell_layout_picks_feasible_winner():
    csr = F.random_csr(4000, 4000, 8.0, seed=1, skew=1.3)
    tuned = tune_sell_layout(csr.row_lengths, n_cols=csr.n_cols)
    assert tuned.c in {r[0] for r in tuned.table}
    assert tuned.cycles == min(r[3] for r in tuned.table)
    assert 1.0 <= tuned.pad_factor < 10.0
    assert tuned.w_block >= 1
    # sigma-sorting a skewed distribution must beat the unsorted worst case
    worst_pf = max(r[2] for r in tuned.table)
    assert tuned.pad_factor <= worst_pf


# ---------------------------------------------------------------------------
# Graph kernels on the sigma-sorted layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vl", [32, 64])
def test_bfs_sell_matches_reference(vl):
    g = G.rmat_graph(n_nodes=256, avg_degree=6, seed=11)
    want = G.bfs_reference(g, 1)
    got = ops.bfs(g, 1, vl=vl, layout="sell")
    np.testing.assert_array_equal(got, want)


def test_bfs_sell_unreachable_stay_inf():
    adj = np.full((8, 2), -1, np.int32)
    adj[0, 0] = 1
    g = G.EllpackGraph(adj=adj, n_nodes=8)
    got = ops.bfs(g, 0, vl=8, layout="sell")
    assert got[0] == 0 and got[1] == 1
    assert all(got[i] == G.INF for i in range(2, 8))


@pytest.mark.parametrize("vl", [32, 128])
def test_pagerank_sell_matches_reference(vl):
    g = G.random_graph(n_nodes=320, avg_degree=5, seed=vl)
    want = G.pagerank_reference(g, iters=12)
    got = ops.pagerank(g, iters=12, vl=vl, layout="sell")
    np.testing.assert_allclose(got, want, rtol=1e-10)


def test_pagerank_sell_mass_conserved_on_skewed_graph():
    g = G.rmat_graph(n_nodes=512, avg_degree=8, seed=2)
    got = ops.pagerank(g, iters=15, vl=128, layout="sell")
    assert got.sum() == pytest.approx(1.0, rel=1e-9)
    assert (got > 0).all()


def _hub_graph(hubs=(3,), seed=5):
    """An R-MAT digraph on 256 nodes plus an edge from every other node to
    each of ``hubs`` (in-degree about 255 each, split into virtual rows);
    with no hubs, every in-degree stays at or below the split width."""
    g = G.rmat_graph(n_nodes=256, avg_degree=6, seed=seed)
    n = g.n_nodes
    if not hubs:
        g = G.random_graph(n_nodes=n, avg_degree=6, seed=seed)
        assert G.in_degree(g).max() <= F.SPLIT_WIDTH
        return g
    extra = np.full((n, len(hubs)), G.PAD, np.int32)
    for j, hub in enumerate(hubs):
        extra[np.arange(n) != hub, j] = hub
    adj = np.concatenate([g.adj, extra], axis=1)
    return G.EllpackGraph(adj=adj, n_nodes=n)


def _device_slabs(g, c=16, sigma=64):
    import jax.numpy as jnp

    slabs = G.graph_to_sell_slabs(g, c=c, sigma=sigma, reverse=True)
    return (tuple(jnp.asarray(a) for a in slabs.bucket_adj),
            tuple(jnp.asarray(m) for m in slabs.bucket_nodes))


#: no hub (one row per node), one hub, three hubs
HUBS = [(), (3,), (3, 40, 200)]


@pytest.mark.parametrize("hubs", HUBS, ids=["hub-free", "hub", "hubs3"])
@pytest.mark.parametrize("k", [1, 8])
def test_bfs_sell_split_layout_matches_host_bfs(hubs, k):
    """A hub split into virtual rows merges by minimum: distances equal
    the host BFS for one source and for 8 stacked ones."""
    from repro.kernels import bfs as bfs_k

    g = _hub_graph(hubs)
    sources = [1, 3, 7, 40, 100, 150, 200, 255][:k]
    adj, nodes = _device_slabs(g)
    got = np.asarray(bfs_k.bfs_sell(
        adj, nodes, g.n_nodes, sources[0] if k == 1 else sources))
    want = np.stack([G.bfs_reference(g, s) for s in sources], axis=1)
    np.testing.assert_array_equal(got, want[:, 0] if k == 1 else want)


@pytest.mark.parametrize("hubs", HUBS, ids=["hub-free", "hub", "hubs3"])
@pytest.mark.parametrize("configs", [(0.85, 12), ((0.85, 0.6), (12, 7))])
def test_pagerank_sell_split_layout_matches_reference(hubs, configs):
    """A hub's pull-sum added up over its virtual rows, then damped: the
    ranks agree with the float64 reference (x64 on the CPU, hence the
    tight tolerance) for one config and for stacked ones."""
    import jax.numpy as jnp

    from repro.kernels import pagerank as pr_k

    g = _hub_graph(hubs, seed=6)
    damping, iters = configs
    adj, nodes = _device_slabs(g)
    got = np.asarray(pr_k.pagerank_sell(
        adj, nodes, jnp.asarray(g.out_degree, jnp.float64), g.n_nodes,
        damping=damping, iters=iters))
    want = np.stack([G.pagerank_reference(g, damping=d, iters=i)
                     for d, i in zip(*pr_k.broadcast_configs(damping, iters))],
                    axis=1)
    np.testing.assert_allclose(
        got, want[:, 0] if np.ndim(damping) == 0 else want, rtol=1e-10)


@pytest.mark.parametrize("hubs", HUBS[1:], ids=["hub", "hubs3"])
def test_ops_sell_path_splits_hub_rows(hubs):
    """``ops.bfs``/``ops.pagerank`` on the SELL layout pack the split rows
    themselves (no registry): stacked BFS and PageRank equal the host
    references."""
    g = _hub_graph(hubs, seed=7)
    slabs = G.graph_to_sell_slabs(g, c=32, reverse=True)
    assert G.split_stats(slabs)["split_nodes"] >= len(hubs)
    assert max(slabs.widths) <= F.SPLIT_WIDTH
    sources = [0, 3, 99]
    np.testing.assert_array_equal(
        ops.bfs(g, sources, vl=32, layout="sell"),
        np.stack([G.bfs_reference(g, s) for s in sources], axis=1))
    np.testing.assert_allclose(
        ops.pagerank(g, iters=9, vl=32, layout="sell"),
        G.pagerank_reference(g, iters=9), rtol=1e-10)


def test_graph_sell_slabs_pad_less_on_skewed_degrees():
    g = G.rmat_graph(n_nodes=1 << 10, avg_degree=8, seed=0)
    rg = g.transpose()
    slabs = G.graph_to_sell_slabs(rg, c=64, sigma=512)
    ell_entries = rg.adj.shape[0] * rg.adj.shape[1]
    assert slabs.padded_entries < ell_entries
    assert slabs.n_edges == g.n_edges


# ---------------------------------------------------------------------------
# Vectorized generators
# ---------------------------------------------------------------------------


@given(
    n=st.integers(min_value=1, max_value=200),
    skew=st.sampled_from([0.0, 1.0]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_random_csr_invariants(n, skew, seed):
    m = F.random_csr(n, n, 4.0, seed=seed, skew=skew)
    assert (m.row_lengths >= 1).all()
    rows = np.repeat(np.arange(n), m.row_lengths)
    # strictly increasing (hence distinct, sorted) within every row
    brk = np.nonzero(np.diff(rows) == 0)[0]
    assert (np.diff(m.indices.astype(np.int64))[brk] > 0).all()
    assert (m.indices >= 0).all() and (m.indices < n).all()


def test_random_csr_skew_is_heavy_tailed():
    m = F.random_csr(5000, 5000, 8.0, seed=0, skew=1.5)
    lengths = m.row_lengths
    assert lengths.max() >= 5 * lengths.mean()
    assert abs(lengths.mean() - 8.0) < 2.5


def test_generators_scale_without_python_loops():
    """1e5-row generation + packing: array ops, not minutes of row loops.

    The bound is deliberately loose (the vectorized path takes well under a
    second; the old per-row loops took minutes) so a loaded CI box can't
    flake it.
    """
    import time

    t0 = time.perf_counter()
    m = F.random_csr(100_000, 100_000, 10.0, seed=0, skew=1.0)
    F.csr_to_sell_slabs(m, c=256)
    assert time.perf_counter() - t0 < 60.0


@pytest.mark.parametrize("c,sigma", [(8, None), (64, 512), (256, 8192)])
def test_reverse_sell_slabs_match_transpose_route(c, sigma):
    """Packing the in-neighbours from the edge list builds exactly the
    slabs of the degree-padded transpose route (same buckets, node maps
    and in-neighbour order) without materializing the reverse graph."""
    for g in (G.rmat_graph(1 << 12, avg_degree=16, seed=0),
              G.random_graph(500, avg_degree=6, seed=3)):
        want = G.graph_to_sell_slabs(g.transpose(), c=c, sigma=sigma)
        got = G.graph_to_sell_slabs(g, c=c, sigma=sigma, reverse=True)
        assert got.widths == want.widths and got.sigma == want.sigma
        for a, b in zip(got.bucket_adj, want.bucket_adj):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(got.bucket_nodes, want.bucket_nodes):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            G.in_degree(g), (g.transpose().adj != G.PAD).sum(axis=1))


@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_shard_graph_slabs_reverse_matches_transpose_route(n_shards):
    """The sharded layout packs the in-neighbours from the edge list too:
    identical to sharding the degree-padded reverse graph."""
    g = G.rmat_graph(1 << 10, avg_degree=8, seed=4)
    want = G.shard_graph_slabs(g.transpose(), c=16, n_shards=n_shards)
    got = G.shard_graph_slabs(g, c=16, n_shards=n_shards, reverse=True)
    assert got.widths == want.widths
    np.testing.assert_array_equal(got.node_starts, want.node_starts)
    np.testing.assert_array_equal(got.node_counts, want.node_counts)
    for a, b in zip(got.bucket_adj + got.bucket_nodes,
                    want.bucket_adj + want.bucket_nodes):
        np.testing.assert_array_equal(a, b)
    assert got.pad_factor >= 1.0
