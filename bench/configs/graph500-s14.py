"""Graph500 Kronecker graph: BFS and PageRank.

The generator follows Graph500's reference Kronecker generator (vertex
labels permuted, tuples shuffled); the graph is undirected, as Graph500's
kernel 1 builds it: every tuple is stored in both directions, self-loops
are left out of the adjacency, and repeated tuples are kept.  Search keys
are sampled as Graph500 samples them: distinct vertices of degree one or
more, drawn once from the configuration's ``key_seed``; ``--seed`` draws the
order in which a run searches them.  The generator and both references are plain
numpy kept with the benchmark, so a change to the program cannot move the
workload or the comparison.  The graph is the deployment's data set and is
fixed by the configuration (``graph_seed``): every seed runs the same
compiled shapes and the same searches.
"""
from __future__ import annotations

import numpy as np

from bench import counters

INF = np.iinfo(np.int32).max


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """(2, M) int64 edge tuples, M = ``edge_factor * 2**scale``: one
    quadrant of the Kronecker initiator per bit, then the vertex labels
    permuted and the tuples shuffled, as Graph500's reference generator
    does."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edge_factor << scale
    ij = np.zeros((2, m), np.int64)
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] |= ii.astype(np.int64) << bit
        ij[1] |= jj.astype(np.int64) << bit
    ij = rng.permutation(n)[ij]
    return ij[:, rng.permutation(m)]


def undirected(ij: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) of the stored adjacency: each tuple but a self-loop in
    both directions, sorted by source."""
    loop = ij[0] == ij[1]
    u, v = ij[0][~loop], ij[1][~loop]
    src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    order = np.argsort(src, kind="stable")
    return src[order], dst[order]


def bfs_reference(indptr: np.ndarray, dst: np.ndarray, root: int
                  ) -> np.ndarray:
    """Level-synchronous BFS distances (int32, INF = unreachable) over the
    adjacency in CSR form (``indptr``, ``dst``)."""
    n = len(indptr) - 1
    dist = np.full(n, INF, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while len(frontier):
        level += 1
        lo, cnt = indptr[frontier], np.diff(indptr)[frontier]
        offs = (np.arange(cnt.sum()) - np.repeat(np.cumsum(cnt) - cnt, cnt)
                + np.repeat(lo, cnt))
        nbrs = np.unique(dst[offs])
        frontier = nbrs[dist[nbrs] == INF]
        dist[frontier] = level
    return dist


def bfs_control(dist: np.ndarray) -> np.ndarray:
    """The reference stopped one level early: its deepest level is left
    unreached.  Breaks the stated guarantee of exact distances."""
    reached = dist[dist < INF]
    out = dist.copy()
    if reached.max() > 0:
        out[out == reached.max()] = INF
    return out


def pagerank_reference(src: np.ndarray, dst: np.ndarray, n: int,
                       damping: float, iters: int) -> np.ndarray:
    """Pull-style power iteration in float64, with the dangling mass
    spread evenly over all nodes."""
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.where(out_deg > 0, rank / np.maximum(out_deg, 1), 0.0)
        dangling = rank[out_deg == 0].sum()
        pulled = np.bincount(dst, weights=contrib[src], minlength=n)
        rank = (1.0 - damping) / n + damping * (pulled + dangling / n)
    return rank


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Widest relative gap of any node's rank (every rank is at least
    (1 - d) / n, so the quotient is defined)."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - want) / want).max())


class Workload:
    """The graph, its search keys in the order the seed draws, and the checks of each
    op."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.name = spec["name"]
        scale = int(spec["scale"])
        self.n = 1 << scale
        self.tuples = kronecker_edges(
            scale, int(spec["edge_factor"]), float(spec["a"]),
            float(spec["b"]), float(spec["c"]), int(spec["graph_seed"]))
        self.src, self.dst = undirected(self.tuples, self.n)
        self.degree = np.bincount(self.src, minlength=self.n)
        self.indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(self.degree, out=self.indptr[1:])
        keys = np.random.default_rng(int(spec["key_seed"])).choice(
            np.nonzero(self.degree > 0)[0], int(spec["search_keys"]),
            replace=False)
        self.roots = keys[np.random.default_rng(seed).permutation(len(keys))]
        self._dist: dict[int, np.ndarray] = {}
        self.edges: dict[int, int] = {}

    @property
    def shape(self) -> tuple[int, int]:
        """(vertices, stored adjacency entries)."""
        return self.n, len(self.src)

    def root(self, i: int) -> int:
        return int(self.roots[i % len(self.roots)])

    def adjacency(self):
        """The graph in the program's input type (neighbour lists, padded
        with -1 to the widest)."""
        from repro.graphs.gen import EllpackGraph

        width = max(1, int(self.degree.max()))
        adj = np.full((self.n, width), -1, np.int32)
        slot = np.arange(len(self.src)) - self.indptr[self.src]
        adj[self.src, slot] = self.dst
        return EllpackGraph(adj=adj, n_nodes=self.n)

    def register(self, registry) -> None:
        registry.register_graph(self.name, self.adjacency())

    def request(self, op: str, i: int) -> tuple:
        if op == "bfs":
            return None, {"source": self.root(i)}
        return None, {"damping": float(self.spec["damping"]),
                      "iters": int(self.spec["iters"])}

    def warmup(self, op: str, widths) -> list[list[tuple]]:
        """The cheapest request of the timed shape: BFS from a vertex of
        degree 0 ends after one level; one PageRank step runs the same
        programs as twenty."""
        del widths
        if op == "bfs":
            leaf = int(np.nonzero(self.degree == 0)[0][0])
            return [[(None, {"source": leaf})]]
        return [[(None, {"damping": float(self.spec["damping"]),
                         "iters": 1})]]

    def keep(self, op: str, i: int) -> bool:
        return True

    def distances(self, i: int) -> np.ndarray:
        """The reference's distances from request ``i``'s search key."""
        root = self.root(i)
        if root not in self._dist:
            self._dist[root] = bfs_reference(self.indptr, self.dst, root)
        return self._dist[root]

    def check(self, op: str, results: dict) -> list[dict]:
        """Every completed request against the reference."""
        if op == "bfs":
            bad = 0
            for i, got in results.items():
                want = self.distances(i)
                self.edges[i] = counters.bfs_edges(self.tuples[0],
                                                   want < INF)
                got = np.asarray(got)
                bad += (int((got != want).sum()) if got.shape == want.shape
                        else self.n)
            return [{"name": "bfs_wrong_distances", "value": bad,
                     "limit": self.spec["limits"]["bfs_wrong_distances"]}]
        want = self.pagerank_want()
        err = max((max_rel_err(got, want) for got in results.values()),
                  default=0.0)
        return [{"name": "pagerank_max_rel_err", "value": err,
                 "limit": self.spec["limits"]["pagerank_max_rel_err"]}]

    def pagerank_want(self) -> np.ndarray:
        return pagerank_reference(self.src, self.dst, self.n,
                                  float(self.spec["damping"]),
                                  int(self.spec["iters"]))

    def control(self, op: str, indices) -> dict:
        """The control in the program's place.  BFS: the reference stopped
        one level early.  PageRank: the reference computed in bfloat16 (the
        precision below the configuration's float32) on the default
        device."""
        if op == "bfs":
            return {i: bfs_control(self.distances(i)) for i in indices}
        import jax
        import jax.numpy as jnp

        bf16, n = jnp.bfloat16, self.n
        src, dst = jnp.asarray(self.src), jnp.asarray(self.dst)
        deg = jnp.asarray(self.degree, bf16)
        d = bf16(self.spec["damping"])

        @jax.jit
        def step(rank):
            contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0)
            dangling = jnp.sum(jnp.where(deg == 0, rank, 0))
            pulled = jax.ops.segment_sum(contrib[src], dst, n)
            return ((1 - d) / n + d * (pulled + dangling / n)).astype(bf16)

        rank = jnp.full((n,), 1.0 / n, bf16)
        for _ in range(int(self.spec["iters"])):
            rank = step(rank)
        out = np.asarray(rank, np.float32)
        return {i: out for i in indices}

    def work(self, op: str, done: list[int], stats: dict) -> dict:
        """Edges and algorithmic bytes of the completed requests."""
        n, m = self.shape
        if op == "bfs":
            return {"edges": sum(self.edges[i] for i in done),
                    "bytes": sum(counters.bfs_bytes(
                        counters.bfs_entries(self.degree,
                                             self.distances(i) < INF), n)
                        for i in done)}
        iters = int(self.spec["iters"])
        return {"edges": len(done) * counters.pagerank_edges(m, iters),
                "bytes": len(done) * counters.pagerank_bytes(n, m, iters)}
