"""Graph substrate: generators, SELL slab packing and host references for
BFS / PageRank."""
from repro.graphs.gen import (
    EllpackGraph,
    SellGraphSlabs,
    bfs_reference,
    graph_to_sell_slabs,
    in_degree,
    pagerank_reference,
    random_graph,
    rmat_graph,
)

__all__ = [
    "EllpackGraph",
    "SellGraphSlabs",
    "bfs_reference",
    "graph_to_sell_slabs",
    "in_degree",
    "pagerank_reference",
    "random_graph",
    "rmat_graph",
]
