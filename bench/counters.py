"""Algorithmic work of a request, counted from an operand's shape alone.

These counts are the numerators of the benchmark's rates and roofline
shares.  They read the logical operand (rows, columns, stored entries,
nodes, edges) and never the packed layout: a different slice height C, sort
window sigma, bucket set or padded slot count gives the same numbers, so a
change to the layout or the gather cannot move them.

Bytes are those the algorithm has to move at least once per call, in the
float32 / int32 types the chip serves.
"""
from __future__ import annotations

import json
import os

#: bytes of one value (float32) and of one index (int32)
VAL_BYTES = 4
IDX_BYTES = 4

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_PATH) -> dict:
    """Peak rates of one chip of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def matrix_shape(m) -> tuple[int, int, int]:
    """(rows, columns, stored entries) of a CSR matrix or of its SELL
    slabs (``nnz`` counts stored entries, not padding)."""
    return int(m.n_rows), int(m.n_cols), int(m.nnz)


def graph_shape(g) -> tuple[int, int]:
    """(nodes, stored edges) of an adjacency or of its SELL slabs."""
    return int(g.n_nodes), int(g.n_edges)


def csr_bytes(n_rows: int, n_cols: int, nnz: int) -> int:
    """Values, column indices and row pointers of a CSR matrix."""
    del n_cols
    return nnz * (VAL_BYTES + IDX_BYTES) + (n_rows + 1) * IDX_BYTES


def spmv_bytes(shape: tuple[int, int, int], launches: int,
               requests: int) -> int:
    """The matrix once per launch, plus each request's x and y."""
    n_rows, n_cols, _ = shape
    return (launches * csr_bytes(*shape)
            + requests * (n_cols + n_rows) * VAL_BYTES)


def bfs_edges(tuple_src, reached) -> int:
    """Edges a search traverses, as Graph500 counts them: the input edge
    tuples within the component it reaches (repeated tuples and self-loops
    included), given each tuple's first vertex."""
    return int(reached[tuple_src].sum())


def bfs_entries(degree, reached) -> int:
    """Stored adjacency entries out of the reached vertices: each one the
    search reads once."""
    return int(degree[reached].sum())


def bfs_bytes(entries: int, n_nodes: int) -> int:
    """One neighbour id per stored entry read plus one distance per
    node."""
    return entries * IDX_BYTES + n_nodes * IDX_BYTES


def pagerank_edges(n_edges: int, iters: int) -> int:
    """Every power step sweeps every edge."""
    return iters * n_edges


def pagerank_bytes(n_nodes: int, n_edges: int, iters: int) -> int:
    """Per step: one neighbour id per edge, and one contribution read plus
    one rank written per node."""
    return iters * (n_edges * IDX_BYTES + n_nodes * 2 * VAL_BYTES)
