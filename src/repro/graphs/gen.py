"""Graph generation + host references for the paper's BFS / PageRank (§3.1).

The paper evaluates both on a 2^15-node graph.  Long-vector graph kernels
(Vizcaino's thesis [13]) use padded adjacency so one vector instruction scans
VL neighbors: we store ELLPACK adjacency (degree-padded, PAD = -1), the same
layout class the SpMV kernel uses.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PAD = -1
INF = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class EllpackGraph:
    """Degree-padded adjacency: ``adj[v, k]`` = k-th out-neighbor of v or PAD."""

    adj: np.ndarray          # (n, width) int32
    n_nodes: int

    @property
    def width(self) -> int:
        return self.adj.shape[1]

    @property
    def n_edges(self) -> int:
        return int((self.adj != PAD).sum())

    @property
    def out_degree(self) -> np.ndarray:
        return (self.adj != PAD).sum(axis=1)

    def transpose(self) -> "EllpackGraph":
        """Reverse graph (in-neighbors), used by pull-style PageRank.

        Vectorized (stable sort by destination + one scatter), so reversing
        stays cheap at millions of edges.
        """
        src, k = np.nonzero(self.adj != PAD)
        dst = self.adj[src, k]
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        counts = np.bincount(dst, minlength=self.n_nodes)
        width = max(1, int(counts.max()) if len(counts) else 1)
        radj = np.full((self.n_nodes, width), PAD, np.int32)
        starts = np.zeros(self.n_nodes + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        within = np.arange(len(src), dtype=np.int64) - starts[dst]
        radj[dst, within] = src
        return EllpackGraph(adj=radj, n_nodes=self.n_nodes)


@dataclasses.dataclass(frozen=True)
class SellGraphSlabs:
    """Width-bucketed SELL-C-sigma adjacency for the pull-style kernels.

    Nodes are sorted by degree within sigma windows and grouped into
    C-node slices; slices are padded to the next power-of-two width and
    bucketed by that width.  ``bucket_adj[b]`` is (n_slices_b, C, W_b) —
    node-major, matching the (vl, width) orientation of the BFS/PageRank
    kernels — and ``bucket_nodes[b]`` is (n_slices_b, C) mapping each lane
    to its original node id (``n_nodes`` = padding/dump slot).

    A node whose degree exceeds :data:`repro.sparse.formats.SPLIT_WIDTH`
    is packed as several virtual rows of near-equal length
    (:func:`split_rows`), so no slice is padded to a hub's degree: its
    lanes all map to that node, and the node step combines their partial
    results.
    """

    bucket_adj: tuple[np.ndarray, ...]    # each (n_slices_b, C, W_b) int32
    bucket_nodes: tuple[np.ndarray, ...]  # each (n_slices_b, C) int32
    n_nodes: int
    sigma: int

    @property
    def c(self) -> int:
        return self.bucket_adj[0].shape[1]

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(a.shape[2] for a in self.bucket_adj)

    @property
    def n_edges(self) -> int:
        return int(sum((a != PAD).sum() for a in self.bucket_adj))

    @property
    def padded_entries(self) -> int:
        return sum(a.size for a in self.bucket_adj)

    @property
    def pad_factor(self) -> float:
        return self.padded_entries / max(self.n_edges, 1)


def _edges(g: EllpackGraph, reverse: bool = False
           ) -> tuple[np.ndarray, np.ndarray]:
    """(node, neighbour) of every stored edge, grouped by node in ascending
    order: each node's out-neighbours in adjacency order, or with
    ``reverse`` its in-neighbours by ascending source id (the rows of
    ``g.transpose()``)."""
    src, k = np.nonzero(g.adj != PAD)
    dst = g.adj[src, k].astype(np.int64)
    if not reverse:
        return src, dst
    by_dst = np.argsort(dst, kind="stable")
    return dst[by_dst], src[by_dst]


def split_rows(deg: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(owner, length) of each virtual row: a node of degree d above
    :data:`repro.sparse.formats.SPLIT_WIDTH` becomes ``ceil(d /
    SPLIT_WIDTH)`` consecutive rows of near-equal length, every other node
    one row of its own degree, all in node order."""
    from repro.sparse.formats import SPLIT_WIDTH

    deg = np.asarray(deg, np.int64)
    n = len(deg)
    if not n or deg.max() <= SPLIT_WIDTH:
        return np.arange(n, dtype=np.int64), deg
    pieces = np.maximum(-(-deg // SPLIT_WIDTH), 1)
    owner = np.repeat(np.arange(n, dtype=np.int64), pieces)
    piece = np.arange(len(owner), dtype=np.int64) - (
        np.cumsum(pieces) - pieces)[owner]
    d, p = deg[owner], pieces[owner]
    return owner, d // p + (piece < d % p)


def split_stats(slabs) -> dict:
    """What the split engaged in :class:`SellGraphSlabs` or
    :class:`ShardedGraphSlabs`: the split width (None when no node was
    split), the nodes cut into more than one virtual row, the virtual rows
    and the padded slots a node step sweeps."""
    from repro.sparse.formats import SPLIT_WIDTH

    n = slabs.n_nodes
    owners = np.concatenate([m.reshape(-1) for m in slabs.bucket_nodes])
    rows = np.bincount(owners, minlength=n + 1)[:n]
    split_nodes = int(np.count_nonzero(rows > 1))
    return {"split_width": SPLIT_WIDTH if split_nodes else None,
            "split_nodes": split_nodes,
            "virtual_rows": int(rows.sum()),
            "padded_slots": int(sum(a.size for a in slabs.bucket_adj))}


def _pack_edges(nodes: np.ndarray, nbrs: np.ndarray, n: int, c: int,
                sigma: int) -> SellGraphSlabs:
    """SELL slabs of an adjacency given as (node, neighbour) pairs grouped
    by ascending node: each virtual row's (:func:`split_rows`) neighbours
    fill its lane left to right.

    Only the padded slots are allocated — never the n x (max degree)
    degree-padded matrix, which for a skewed graph whose hub has tens of
    thousands of neighbours is tens of GB.
    """
    from repro.sparse.formats import next_pow2, sigma_sort_order, slice_widths

    deg = np.bincount(nodes, minlength=n).astype(np.int64)
    owner, lengths = split_rows(deg)
    n_rows = len(owner)
    # a node's virtual rows are consecutive, so the edge list grouped by
    # node is already grouped by virtual row
    vrow = np.repeat(np.arange(n_rows, dtype=np.int64), lengths)
    starts = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lengths, out=starts[1:])
    within = np.arange(len(nodes), dtype=np.int64) - starts[vrow]
    order = sigma_sort_order(lengths, sigma)
    bwidths = next_pow2(slice_widths(lengths, order, c))
    n_slices = len(bwidths)
    nodes_padded = np.full(n_slices * c, n, np.int64)
    nodes_padded[:n_rows] = owner[order]
    nodes_by_slice = nodes_padded.reshape(n_slices, c).astype(np.int32)
    pos = np.empty(n_rows, np.int64)
    pos[order] = np.arange(n_rows)
    e_slice, e_lane = pos[vrow] // c, pos[vrow] % c
    bucket_adj, bucket_nodes = [], []
    for w in np.unique(bwidths):
        ids = np.nonzero(bwidths == w)[0]
        local = np.full(n_slices, -1, np.int64)
        local[ids] = np.arange(len(ids))
        sel = local[e_slice] >= 0
        adj = np.full((len(ids), c, int(w)), PAD, np.int32)
        adj[local[e_slice[sel]], e_lane[sel], within[sel]] = nbrs[sel]
        bucket_adj.append(adj)
        bucket_nodes.append(nodes_by_slice[ids])
    return SellGraphSlabs(
        bucket_adj=tuple(bucket_adj),
        bucket_nodes=tuple(bucket_nodes),
        n_nodes=n,
        sigma=sigma,
    )


def graph_to_sell_slabs(
    g: EllpackGraph, c: int, sigma: int | None = None, *,
    reverse: bool = False,
) -> SellGraphSlabs:
    """Bucket a graph's adjacency into SELL slabs (vectorized).

    ``reverse=True`` packs the in-neighbours — the slabs of
    ``graph_to_sell_slabs(g.transpose(), ...)``, which the pull-style BFS
    and PageRank kernels read — straight from the edge list, without the
    degree-padded reverse graph.  Rows longer than the split width are
    packed as virtual rows (:func:`split_rows`).
    """
    return _pack_edges(*_edges(g, reverse), g.n_nodes, c,
                       int(sigma or 8 * c))


def in_degree(g: EllpackGraph) -> np.ndarray:
    """In-degree of every node (the row lengths of the reverse graph)."""
    return np.bincount(g.adj[g.adj != PAD], minlength=g.n_nodes).astype(
        np.int64)


@dataclasses.dataclass(frozen=True)
class ShardedGraphSlabs:
    """Node-partitioned :class:`SellGraphSlabs`, stacked along a device axis.

    Shard ``d`` owns the contiguous node range ``[node_starts[d],
    node_starts[d] + node_counts[d])`` and carries that range's in-degree
    sorted adjacency as a common bucket structure (same widths and slice
    counts on every shard, PAD-padded), so one shard_map body serves all
    devices.  Unlike the matrix case, ids stay GLOBAL: ``bucket_adj`` holds
    global neighbor ids (the frontier/rank state is replicated, so every
    shard gathers from the full vector) and ``bucket_nodes`` holds global
    owned-node ids (padding lanes map to ``n_nodes``, the shared dump slot)
    — each shard scatters only its own nodes, and the cross-device combine
    (BFS ``pmin`` frontier union, PageRank ``psum`` rank exchange) merges
    the disjoint updates.  A split node's virtual rows stay in its shard.
    """

    bucket_adj: tuple[np.ndarray, ...]    # each (n_shards, S_b, C, W_b) int32
    bucket_nodes: tuple[np.ndarray, ...]  # each (n_shards, S_b, C) int32
    node_starts: np.ndarray               # (n_shards,) int64
    node_counts: np.ndarray               # (n_shards,) int64
    n_nodes: int
    sigma: int

    @property
    def c(self) -> int:
        return self.bucket_adj[0].shape[2]

    @property
    def n_shards(self) -> int:
        return self.bucket_adj[0].shape[0]

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(a.shape[3] for a in self.bucket_adj)

    @property
    def slices_per_shard(self) -> tuple[int, ...]:
        return tuple(a.shape[1] for a in self.bucket_adj)

    @property
    def pad_factor(self) -> float:
        edges = sum(int((a != PAD).sum()) for a in self.bucket_adj)
        return sum(a.size for a in self.bucket_adj) / max(edges, 1)


def shard_graph_slabs(
    g: EllpackGraph, c: int, n_shards: int, sigma: int | None = None, *,
    reverse: bool = False,
) -> ShardedGraphSlabs:
    """Node-partition a graph's adjacency (in-neighbours with ``reverse``,
    as for :func:`graph_to_sell_slabs`) into per-device SELL slabs.

    Nodes split into contiguous degree-balanced ranges; each range is
    degree-sorted and bucketed *locally* (so no slice mixes nodes across
    the partition), then the per-shard structures are padded to the union
    bucket layout exactly as :func:`repro.sparse.formats.shard_slabs` does
    for matrices.
    """
    from repro.sparse.formats import shard_row_ranges

    sigma = int(sigma or 8 * c)
    n = g.n_nodes
    nodes, nbrs = _edges(g, reverse)
    ranges = shard_row_ranges(np.bincount(nodes, minlength=n), n_shards)
    n_shards = len(ranges)
    shards = []
    for lo, hi in ranges:
        a, b = np.searchsorted(nodes, [lo, hi])
        shards.append((lo, _pack_edges(nodes[a:b] - lo, nbrs[a:b], hi - lo,
                                       c, sigma)))

    per_shard = [dict(zip(s.widths, range(len(s.bucket_adj))))
                 for _, s in shards]
    union_w = sorted({w for _, s in shards for w in s.widths})
    smax = {
        w: max(
            (s.bucket_adj[per_shard[d][w]].shape[0]
             if w in per_shard[d] else 0)
            for d, (_, s) in enumerate(shards))
        for w in union_w
    }
    bucket_adj, bucket_nodes = [], []
    for w in union_w:
        s_b = smax[w]
        adj = np.full((n_shards, s_b, c, w), PAD, np.int32)
        nodes = np.full((n_shards, s_b, c), n, np.int32)
        for d, (lo, s) in enumerate(shards):
            if w not in per_shard[d]:
                continue  # empty per-device bucket: stays all-PAD
            b = per_shard[d][w]
            sa, sn = s.bucket_adj[b], s.bucket_nodes[b]
            nb = sa.shape[0]
            adj[d, :nb] = sa                    # neighbor ids already global
            # owned nodes: local sorted ids -> global; pads -> global dump
            nodes[d, :nb] = np.where(sn == s.n_nodes, n, sn + lo)
        bucket_adj.append(adj)
        bucket_nodes.append(nodes)
    return ShardedGraphSlabs(
        bucket_adj=tuple(bucket_adj),
        bucket_nodes=tuple(bucket_nodes),
        node_starts=np.array([lo for lo, _ in ranges], np.int64),
        node_counts=np.array([hi - lo for lo, hi in ranges], np.int64),
        n_nodes=n,
        sigma=sigma,
    )


def random_graph(
    n_nodes: int = 1 << 15,
    avg_degree: int = 16,
    seed: int = 0,
    connected_ring: bool = True,
) -> EllpackGraph:
    """Uniform random digraph, optional ring to guarantee reachability."""
    rng = np.random.default_rng(seed)
    deg = np.clip(rng.poisson(avg_degree - 1, n_nodes) + 1, 1, 4 * avg_degree)
    width = int(deg.max()) + (1 if connected_ring else 0)
    adj = np.full((n_nodes, width), PAD, np.int32)
    for v in range(n_nodes):
        k = int(deg[v])
        nbrs = rng.choice(n_nodes, size=k, replace=False)
        adj[v, :k] = nbrs
        if connected_ring:
            adj[v, k] = (v + 1) % n_nodes
    return EllpackGraph(adj=adj, n_nodes=n_nodes)


def rmat_graph(
    n_nodes: int = 1 << 15,
    avg_degree: int = 16,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    degree_cap_factor: int = 8,
) -> EllpackGraph:
    """R-MAT (Graph500-style skewed) generator, degree-capped for ELLPACK."""
    rng = np.random.default_rng(seed)
    scale = int(np.log2(n_nodes))
    n_edges = n_nodes * avg_degree
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    for bit in range(scale):
        r = rng.random(n_edges)
        s_bit = r >= a + b                     # lower half for source
        r2 = rng.random(n_edges)
        d_bit = np.where(s_bit, r2 >= c / max(c + (1 - a - b - c), 1e-9),
                         r2 >= a / max(a + b, 1e-9))
        src |= s_bit.astype(np.int64) << bit
        dst |= d_bit.astype(np.int64) << bit
    # keep each source's first ``cap`` non-loop edges in generation order
    cap = degree_cap_factor * avg_degree
    keep = src != dst
    src, dst = src[keep], dst[keep]
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    starts = np.searchsorted(src, np.arange(n_nodes))
    slot = np.arange(len(src)) - starts[src]
    keep = slot < cap
    src, dst, slot = src[keep], dst[keep], slot[keep]
    width = max(1, int(slot.max()) + 1 if len(slot) else 1)
    adj = np.full((n_nodes, width), PAD, np.int32)
    adj[src, slot] = dst
    return EllpackGraph(adj=adj, n_nodes=n_nodes)


# ---------------------------------------------------------------------------
# Host references
# ---------------------------------------------------------------------------


def bfs_reference(g: EllpackGraph, source: int = 0) -> np.ndarray:
    """Level-synchronous BFS distances (int32, INF = unreachable)."""
    dist = np.full(g.n_nodes, INF, np.int32)
    dist[source] = 0
    frontier = np.array([source], np.int64)
    level = 0
    while len(frontier):
        level += 1
        nbrs = g.adj[frontier].reshape(-1)
        nbrs = nbrs[nbrs != PAD]
        nbrs = np.unique(nbrs)
        new = nbrs[dist[nbrs] == INF]
        dist[new] = level
        frontier = new
    return dist


def pagerank_reference(
    g: EllpackGraph,
    damping: float = 0.85,
    iters: int = 20,
    dtype=np.float64,
) -> np.ndarray:
    """Pull-style power iteration with dangling-mass redistribution (each
    node pulls rank / out-degree from its in-neighbours, summed over the
    edge list)."""
    n = g.n_nodes
    out_deg = g.out_degree.astype(dtype)
    src, dst = _edges(g)
    rank = np.full(n, 1.0 / n, dtype)
    for _ in range(iters):
        contrib = np.where(out_deg > 0, rank / np.maximum(out_deg, 1), 0.0)
        dangling = rank[out_deg == 0].sum()
        pulled = np.bincount(dst, weights=contrib[src], minlength=n)
        rank = ((1.0 - damping) / n
                + damping * (pulled + dangling / n)).astype(dtype)
    return rank
