"""HPCG's sparse matrix: SpMV requests.

The matrix is HPCG's (``GenerateProblem`` of its reference code): the
27-point stencil on an ``nx`` x ``ny`` x ``nz`` grid, 26 on the diagonal
and -1 for each neighbour inside the grid, rows and columns in the grid's
x-fastest order.  The structure and the values are the configuration's, so
every seed runs the same compiled shapes; ``--seed`` draws the x vectors.
The reference is float64 ``scipy.sparse``.
"""
from __future__ import annotations

import numpy as np

from bench import counters

#: distinct x vectors per run; request i multiplies pool[i % POOL]
POOL = 256
#: share of the requests whose results are kept and compared
SAMPLE = 1 / 32
#: request indices the sample is drawn over (far beyond any window)
MAX_REQUESTS = 1 << 20


def stencil_27(nx: int, ny: int, nz: int, diagonal: float, off: float
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(indptr, indices, values) of HPCG's matrix: row ``(iz*ny + iy)*nx +
    ix`` holds every grid neighbour at offsets in {-1, 0, 1}^3, columns in
    ascending order."""
    iz, iy, ix = (a.reshape(-1) for a in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    n = nx * ny * nz
    cols, vals = [], []
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                jz, jy, jx = iz + sz, iy + sy, ix + sx
                inside = ((0 <= jz) & (jz < nz) & (0 <= jy) & (jy < ny)
                          & (0 <= jx) & (jx < nx))
                cols.append(np.where(inside, (jz * ny + jy) * nx + jx, -1))
                vals.append(diagonal if (sz, sy, sx) == (0, 0, 0) else off)
    cols = np.stack(cols, axis=1)                      # (n, 27), ascending
    vals = np.broadcast_to(np.array(vals), cols.shape)
    inside = cols >= 0
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(inside.sum(axis=1), out=indptr[1:])
    return indptr, cols[inside].astype(np.int32), vals[inside]


def row_err(want: np.ndarray, scale: np.ndarray, got) -> float:
    """Widest row-normalized error of ``got`` against ``want`` = A @ x in
    float64: |y - A x| / (|A| |x|) per row, ``scale`` = |A| |x|."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - want) / np.maximum(scale, 1e-300)).max())


class Workload:
    """The matrix, its x vectors from the seed, and the SpMV check."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.name = spec["name"]
        nx, ny, nz = int(spec["nx"]), int(spec["ny"]), int(spec["nz"])
        self.n = nx * ny * nz
        self.indptr, self.indices, values = stencil_27(
            nx, ny, nz, float(spec["diagonal"]), float(spec["off_diagonal"]))
        self.data = values.astype(np.float32)
        rng = np.random.default_rng(seed)
        self.xs = rng.standard_normal((POOL, self.n)).astype(np.float32)
        self.sample = rng.random(MAX_REQUESTS) < SAMPLE

    @property
    def a(self):
        """The reference's float64 matrix, built when the check needs it,
        outside the set-up."""
        import scipy.sparse as sp

        return sp.csr_matrix(
            (self.data.astype(np.float64), self.indices, self.indptr),
            shape=(self.n, self.n))

    @property
    def shape(self) -> tuple[int, int, int]:
        return self.n, self.n, len(self.indices)

    def register(self, registry) -> None:
        from repro.sparse.formats import CSRMatrix

        registry.register_matrix(self.name, CSRMatrix(
            indptr=self.indptr, indices=self.indices, data=self.data,
            n_cols=self.n))

    def request(self, op: str, i: int) -> tuple:
        return self.xs[i % POOL], {}

    def warmup(self, op: str, widths) -> list[list[tuple]]:
        """One group of each width the traffic can form."""
        return [[(self.xs[j], {}) for j in range(w)] for w in widths]

    def keep(self, op: str, i: int) -> bool:
        return i < MAX_REQUESTS and bool(self.sample[i])

    def check(self, op: str, results: dict) -> list[dict]:
        """The sampled requests against the float64 reference."""
        a, x = self.a, self.xs.T.astype(np.float64)
        want, scale = a @ x, abs(a) @ np.abs(x)
        err = max((row_err(want[:, i % POOL], scale[:, i % POOL], y)
                   for i, y in results.items()), default=0.0)
        return [{"name": "spmv_row_err", "value": err,
                 "limit": self.spec["limits"]["spmv_row_err"]}]

    def control(self, op: str, indices) -> dict:
        """The control: the reference in the program's place, computed in
        bfloat16 (the precision below the configuration's float32) on the
        default device."""
        import jax
        import jax.numpy as jnp

        rows = jnp.asarray(np.repeat(np.arange(self.n), np.diff(self.indptr)))
        cols = jnp.asarray(self.indices)
        vals = jnp.asarray(self.data, jnp.bfloat16)

        @jax.jit
        def spmv(x):
            prod = vals * x.astype(jnp.bfloat16)[cols]
            return jax.ops.segment_sum(prod, rows, self.n)

        return {i: np.asarray(spmv(jnp.asarray(self.xs[i % POOL])),
                              np.float32) for i in indices}

    def work(self, op: str, done: list[int], stats: dict) -> dict:
        """Algorithmic bytes of the window's launches and requests."""
        return {"bytes": counters.spmv_bytes(self.shape, stats["launches"],
                                             len(done))}
