"""The benchmark's peaks table and its shape-only work counters
(``bench/peaks.json``, ``bench/counters.py``)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import counters as C  # noqa: E402


def test_peaks_of_the_v5e_and_unknown_kinds_raise():
    v5e = C.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            C.peaks(kind)


def tiny_graph():
    """0 -> 1, 0 -> 2, 1 -> 2, 2 -> 3; vertex 4 is isolated."""
    from repro.graphs.gen import EllpackGraph

    adj = np.array([[1, 2], [2, -1], [3, -1], [-1, -1], [-1, -1]], np.int32)
    return EllpackGraph(adj=adj, n_nodes=5)


def tiny_csr():
    """[[1, 0, 2], [0, 0, 3]]: 2 rows, 3 columns, 3 stored entries."""
    from repro.sparse.formats import CSRMatrix

    return CSRMatrix(indptr=np.array([0, 2, 3]), indices=np.array(
        [0, 2, 2], np.int32), data=np.array([1.0, 2.0, 3.0], np.float32),
        n_cols=3)


def test_graph_counters_by_hand():
    g = tiny_graph()
    assert C.graph_shape(g) == (5, 4)
    out = g.out_degree
    # a search from 1 reaches 1, 2, 3: of the tuples (0,1), (0,2), (1,2),
    # (2,3) two start there; the stored entries out of them are 1 + 1 + 0
    reached = np.array([False, True, True, True, False])
    assert C.bfs_edges(np.array([0, 0, 1, 2]), reached) == 2
    assert C.bfs_edges(np.array([1, 1, 4]), reached) == 2   # a repeat counts
    assert C.bfs_entries(out, reached) == 2
    assert C.bfs_bytes(2, 5) == 2 * 4 + 5 * 4
    assert C.pagerank_edges(4, 20) == 80
    assert C.pagerank_bytes(5, 4, 20) == 20 * (4 * 4 + 5 * 8)


def test_matrix_counters_by_hand():
    m = tiny_csr()
    assert C.matrix_shape(m) == (2, 3, 3)
    assert C.csr_bytes(2, 3, 3) == 3 * 8 + 3 * 4
    # two launches, five requests: the matrix twice, x (3) and y (2) each
    assert C.spmv_bytes((2, 3, 3), 2, 5) == 2 * 36 + 5 * (3 + 2) * 4


@pytest.mark.parametrize("c,sigma", [(8, 8), (8, 64), (32, 32), (128, 512)])
def test_counts_do_not_depend_on_the_packed_layout(c, sigma):
    from repro.graphs.gen import graph_to_sell_slabs, rmat_graph
    from repro.sparse.formats import csr_to_sell_slabs, random_csr

    g = rmat_graph(1 << 9, avg_degree=8, seed=3)
    slabs = graph_to_sell_slabs(g, c=c, sigma=sigma, reverse=True)
    assert slabs.padded_entries > g.n_edges
    assert C.graph_shape(slabs) == C.graph_shape(g)
    m = random_csr(300, 200, 5.0, seed=4, skew=1.0)
    mslabs = csr_to_sell_slabs(m, c=c, sigma=sigma)
    assert C.matrix_shape(mslabs) == C.matrix_shape(m)
    assert C.spmv_bytes(C.matrix_shape(mslabs), 3, 7) == C.spmv_bytes(
        C.matrix_shape(m), 3, 7)
