"""Sparse formats for long-vector SpMV (paper §3.1, Gómez et al. [2]).

Long-vector SpMV wants a layout where one vector instruction processes VL
*rows* at once: ELLPACK transposed into (slice, column-step, row-in-slice)
order, and its padding-reducing refinement SELL-C-sigma (sort rows by nnz in
windows of sigma, slice in chunks of C=VL, pad each slice to its own width).

Two SELL containers exist:

* :class:`SellCSigmaMatrix` — the ragged host tuple (one array per slice),
  the textbook form; good for inspection, not runnable on device.
* :class:`SellSlabs` — the device layout: slices grouped into power-of-two
  width buckets, each bucket a dense (n_slices_b, W_b, C) slab a Pallas
  kernel can consume directly, plus the row scatter map that restores the
  original row order.

Everything here is host-side numpy (the data pipeline); kernels consume the
padded device arrays.  All conversion paths are vectorized — no per-row
Python loops — so packing stays cheap at millions of rows.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PAD = -1  # column padding sentinel


@dataclasses.dataclass(frozen=True)
class CSRMatrix:
    """Compressed Sparse Row."""

    indptr: np.ndarray    # (n_rows + 1,) int64
    indices: np.ndarray   # (nnz,) int32
    data: np.ndarray      # (nnz,) float
    n_cols: int

    @property
    def n_rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def row_lengths(self) -> np.ndarray:
        return np.diff(self.indptr)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference host SpMV."""
        y = np.zeros(self.n_rows, dtype=np.result_type(self.data, x))
        np.add.at(y, np.repeat(np.arange(self.n_rows), self.row_lengths),
                  self.data * x[self.indices])
        return y


@dataclasses.dataclass(frozen=True)
class EllpackMatrix:
    """Uniform-width ELLPACK in slice-transposed (kernel) layout.

    ``cols``/``vals`` have shape (n_slices, width, C): element (s, w, c) is
    the w-th nonzero of row ``s*C + c``; padding has ``cols == PAD`` and
    ``vals == 0``.  One Pallas grid step processes one slice (VL=C rows).
    """

    cols: np.ndarray      # (n_slices, width, C) int32
    vals: np.ndarray      # (n_slices, width, C) float
    n_rows: int
    n_cols: int
    nnz: int

    @property
    def c(self) -> int:
        return self.cols.shape[2]

    @property
    def width(self) -> int:
        return self.cols.shape[1]

    @property
    def n_slices(self) -> int:
        return self.cols.shape[0]

    @property
    def padded_nnz(self) -> int:
        return self.cols.size

    @property
    def pad_factor(self) -> float:
        return self.padded_nnz / max(self.nnz, 1)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference host SpMV over the padded layout."""
        xg = np.concatenate([x, np.zeros(1, x.dtype)])  # PAD -> 0 via index -1
        safe = np.where(self.cols == PAD, len(x), self.cols)
        y = np.einsum("swc,swc->sc", self.vals, xg[safe])
        return y.reshape(-1)[: self.n_rows]


@dataclasses.dataclass(frozen=True)
class SellCSigmaMatrix:
    """SELL-C-sigma: per-slice width, rows sigma-window sorted by length.

    ``slice_cols[s]`` has shape (width_s, C).  ``perm`` maps sorted position
    -> original row id (y must be scattered back through it).
    """

    slice_cols: tuple[np.ndarray, ...]
    slice_vals: tuple[np.ndarray, ...]
    perm: np.ndarray
    n_rows: int
    n_cols: int
    nnz: int

    @property
    def c(self) -> int:
        return self.slice_cols[0].shape[1]

    @property
    def padded_nnz(self) -> int:
        return sum(c.size for c in self.slice_cols)

    @property
    def pad_factor(self) -> float:
        return self.padded_nnz / max(self.nnz, 1)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        xg = np.concatenate([x, np.zeros(1, x.dtype)])
        y_sorted = []
        for cols, vals in zip(self.slice_cols, self.slice_vals):
            safe = np.where(cols == PAD, len(x), cols)
            y_sorted.append(np.einsum("wc,wc->c", vals, xg[safe]))
        y_sorted = np.concatenate(y_sorted)[: self.n_rows]
        y = np.zeros_like(y_sorted)
        y[self.perm] = y_sorted
        return y


@dataclasses.dataclass(frozen=True)
class SellSlabs:
    """Device-executable SELL-C-sigma: width-bucketed uniform slabs.

    Slices of the sigma-sorted matrix are grouped by padded width rounded up
    to a power of two; every bucket ``b`` is a dense slice-transposed slab
    ``bucket_cols[b]``/``bucket_vals[b]`` of shape (n_slices_b, W_b, C) that
    a single ``pallas_call`` can stream, with ``bucket_rows[b]`` of shape
    (n_slices_b, C) mapping each lane back to its original row id (padding
    lanes map to ``n_rows``, a dump slot the kernel wrapper trims).

    The number of kernel launches is bounded by log2(max_width) while the
    padded-FLOP count tracks the per-slice widths instead of the global max.
    """

    bucket_cols: tuple[np.ndarray, ...]   # each (n_slices_b, W_b, C) int32
    bucket_vals: tuple[np.ndarray, ...]   # each (n_slices_b, W_b, C) float
    bucket_rows: tuple[np.ndarray, ...]   # each (n_slices_b, C) int32
    n_rows: int
    n_cols: int
    nnz: int
    sigma: int

    @property
    def c(self) -> int:
        return self.bucket_cols[0].shape[2]

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_cols)

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(c.shape[1] for c in self.bucket_cols)

    @property
    def n_slices(self) -> int:
        return sum(c.shape[0] for c in self.bucket_cols)

    @property
    def padded_nnz(self) -> int:
        return sum(c.size for c in self.bucket_cols)

    @property
    def pad_factor(self) -> float:
        return self.padded_nnz / max(self.nnz, 1)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference host SpMV: per-bucket gather-MAC + row scatter."""
        xg = np.concatenate([x, np.zeros(1, x.dtype)])
        y = np.zeros(self.n_rows + 1, dtype=np.result_type(self.bucket_vals[0], x))
        for cols, vals, rows in zip(self.bucket_cols, self.bucket_vals, self.bucket_rows):
            safe = np.where(cols == PAD, len(x), cols)
            yb = np.einsum("swc,swc->sc", vals, xg[safe])
            y[rows.reshape(-1)] = yb.reshape(-1)
        return y[: self.n_rows]


# ---------------------------------------------------------------------------
# Conversions (vectorized: numpy argsort/scatter, no per-row Python loops)
# ---------------------------------------------------------------------------


def csr_from_dense(dense: np.ndarray) -> CSRMatrix:
    n_rows, n_cols = dense.shape
    rows, cols = np.nonzero(dense)
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return CSRMatrix(
        indptr=indptr,
        indices=cols.astype(np.int32),
        data=dense[rows, cols],
        n_cols=n_cols,
    )


def csr_to_dense(m: CSRMatrix) -> np.ndarray:
    out = np.zeros((m.n_rows, m.n_cols), dtype=m.data.dtype)
    rows = np.repeat(np.arange(m.n_rows), m.row_lengths)
    out[rows, m.indices] = m.data
    return out


def _nnz_coords(m: CSRMatrix) -> tuple[np.ndarray, np.ndarray]:
    """(row, within-row offset) of every stored entry, in CSR order."""
    rows = np.repeat(np.arange(m.n_rows, dtype=np.int64), m.row_lengths)
    offs = np.arange(m.nnz, dtype=np.int64) - m.indptr[rows]
    return rows, offs


def sigma_sort_order(lengths: np.ndarray, sigma: int) -> np.ndarray:
    """Row order: descending length within each sigma window, stable.

    The single definition of the SELL-C-sigma sort — the packers, the graph
    slab builder, and the tuner's pad model all share it so they can never
    disagree about the layout.
    """
    n = len(lengths)
    win = np.arange(n, dtype=np.int64) // max(int(sigma), 1)
    return np.lexsort((np.arange(n), -np.asarray(lengths), win))


def csr_to_ellpack(m: CSRMatrix, c: int, width: int | None = None) -> EllpackMatrix:
    """Pad CSR to uniform-width slice-transposed ELLPACK with slice size c."""
    lengths = m.row_lengths
    w = int(width if width is not None else (lengths.max() if m.n_rows else 0))
    w = max(w, 1)
    n_slices = -(-m.n_rows // c)
    cols = np.full((n_slices, w, c), PAD, np.int32)
    vals = np.zeros((n_slices, w, c), m.data.dtype)
    rows, offs = _nnz_coords(m)
    keep = offs < w
    r, k = rows[keep], offs[keep]
    cols[r // c, k, r % c] = m.indices[keep]
    vals[r // c, k, r % c] = m.data[keep]
    return EllpackMatrix(cols=cols, vals=vals, n_rows=m.n_rows, n_cols=m.n_cols, nnz=m.nnz)


def _sell_flat_pack(
    m: CSRMatrix, c: int, order: np.ndarray, slice_base: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Scatter every nnz into a flat buffer of concatenated (W_s, C) slices.

    ``slice_base[s]`` is the flat offset of slice ``s``'s buffer; within a
    slice, entry (w, lane) lives at ``w * c + lane``.
    """
    total = int(slice_base[-1])
    cols_flat = np.full(total, PAD, np.int32)
    vals_flat = np.zeros(total, m.data.dtype)
    if m.nnz:
        pos_of_row = np.empty(m.n_rows, np.int64)
        pos_of_row[order] = np.arange(m.n_rows)
        rows, offs = _nnz_coords(m)
        pos = pos_of_row[rows]
        flat = slice_base[pos // c] + offs * c + pos % c
        cols_flat[flat] = m.indices
        vals_flat[flat] = m.data
    return cols_flat, vals_flat


def slice_widths(lengths: np.ndarray, order: np.ndarray, c: int) -> np.ndarray:
    """Max row length per C-slice of the sorted order (>= 1), vectorized."""
    n = len(order)
    n_slices = max(-(-n // c), 1)
    padded = np.zeros(n_slices * c, np.int64)
    if n:
        padded[:n] = lengths[order]
    return np.maximum(padded.reshape(n_slices, c).max(axis=1), 1)


def csr_to_sell(m: CSRMatrix, c: int, sigma: int | None = None) -> SellCSigmaMatrix:
    """SELL-C-sigma conversion (sigma defaults to 8*c as in Gómez et al.)."""
    sigma = sigma or 8 * c
    order = sigma_sort_order(m.row_lengths, sigma)
    widths = slice_widths(m.row_lengths, order, c)
    slice_base = np.zeros(len(widths) + 1, np.int64)
    np.cumsum(widths * c, out=slice_base[1:])
    cols_flat, vals_flat = _sell_flat_pack(m, c, order, slice_base)
    slice_cols = tuple(
        cols_flat[slice_base[s] : slice_base[s + 1]].reshape(int(widths[s]), c)
        for s in range(len(widths))
    )
    slice_vals = tuple(
        vals_flat[slice_base[s] : slice_base[s + 1]].reshape(int(widths[s]), c)
        for s in range(len(widths))
    )
    return SellCSigmaMatrix(
        slice_cols=slice_cols,
        slice_vals=slice_vals,
        perm=order,
        n_rows=m.n_rows,
        n_cols=m.n_cols,
        nnz=m.nnz,
    )


def pow2_ceil(x: int) -> int:
    """Smallest power of two >= x (>= 1) — the scalar form of
    :func:`next_pow2`, shared by the batched-kernel RHS tiling and the
    tuner's width cap so the rounding rule exists once."""
    return 1 << max(int(x) - 1, 0).bit_length()


#: sublanes of a TPU vreg: the second-minor dim of every kernel block must
#: be a multiple of it or span the whole array axis
SUBLANES = 8

#: longest row of the graph SELL layout: a node of higher degree is packed
#: as near-equal virtual rows of at most this many entries
#: (:func:`repro.graphs.gen.split_rows`), so no slice is padded to a hub's
#: degree.  On the Graph500 scale-14 graph, wider rows leave more padding
#: (800 lane-gather blocks a step at 256 against 664 at 64); narrower ones
#: cut under a tenth more while the virtual rows multiply (21,750 at 64,
#: 76,229 at 8), and each row costs an output lane and a scatter update
SPLIT_WIDTH = 64


def k_tile_for(k: int, k_block: int) -> int:
    """The RHS tile one SpMM grid cell processes.

    A stack of at most 8 columns runs as one tile of ``pow2_ceil(k)``
    columns, so its block spans the whole padded k axis; a wider stack
    tiles by ``min(k_block, pow2_ceil(k))`` but never below 8.  Either way
    the (k_tile, C) block meets the TPU (8, 128) tiling rule.  Both cases
    are powers of two that divide ``pow2_ceil(k)``: a caller that
    pow2-pads its stack (the service's ``_pow2_pad``) hands the core a k
    the core never pads again (:func:`padded_k` is the identity on powers
    of two).
    """
    p = pow2_ceil(max(int(k), 1))
    if p <= SUBLANES:
        return p
    return max(SUBLANES, min(max(int(k_block), 1), p))


def widest_k_tile(k_block: int) -> int:
    """The widest RHS tile :func:`k_tile_for` gives a stack of any width
    under this ``k_block``: a group of up to 8 columns runs whole even when
    ``k_block`` is smaller, so a launch plan priced at this many columns
    bounds every launch the operand can serve."""
    return max(SUBLANES, int(k_block))


def padded_k(k: int, k_block: int) -> int:
    """The k the SpMM core actually runs: ``k`` rounded up to the k tile.

    ``padded_k(pow2, k_block) == pow2`` for every pow2/k_block pair — the
    ops boundary asserts this fixpoint so the pow2 padding applied by the
    service and the tile padding applied by the core can never stack.
    """
    kp = k_tile_for(k, k_block)
    return kp * -(-max(int(k), 1) // kp)


def w_tile_for(width: int, w_block: int) -> int:
    """Slab rows (W) one grid cell reads from a bucket of this width.

    Buckets at most 8 wide are read whole; wider ones by
    ``min(w_block, width)`` rows but never fewer than 8, so the (w, C)
    block meets the TPU (8, 128) tiling rule.
    """
    width = int(width)
    if width <= SUBLANES:
        return width
    return max(SUBLANES, min(max(int(w_block), 1), width))


def next_pow2(x: np.ndarray) -> np.ndarray:
    """Element-wise next power of two (>= 1): the bucket width rounding
    (array form of :func:`pow2_ceil`)."""
    return (2 ** np.ceil(np.log2(np.maximum(x, 1)))).astype(np.int64)


def csr_to_sell_slabs(m: CSRMatrix, c: int, sigma: int | None = None) -> SellSlabs:
    """Pack CSR into width-bucketed device slabs (see :class:`SellSlabs`).

    Slices are sigma-sorted as in :func:`csr_to_sell`, then padded up to the
    next power-of-two width and grouped by that width, keeping slice order
    stable within a bucket.
    """
    sigma = int(sigma or 8 * c)
    lengths = m.row_lengths
    order = sigma_sort_order(lengths, sigma)
    bwidths = next_pow2(slice_widths(lengths, order, c))
    n_slices = len(bwidths)

    # Destination of each slice: buckets ordered by ascending width, slices
    # in original (sorted-position) order within a bucket.
    uniq = np.unique(bwidths)
    dest = np.lexsort((np.arange(n_slices), bwidths))   # bucket-major slice order
    rank_of = np.empty(n_slices, np.int64)
    rank_of[dest] = np.arange(n_slices)
    sizes_in_dest = bwidths[dest] * c
    slice_base_dest = np.zeros(n_slices + 1, np.int64)
    np.cumsum(sizes_in_dest, out=slice_base_dest[1:])
    slice_base = slice_base_dest[rank_of]               # flat offset per slice
    base_full = np.concatenate([slice_base, [slice_base_dest[-1]]])
    cols_flat, vals_flat = _sell_flat_pack(m, c, order, base_full)

    # Row scatter map: sorted position -> original row, pads -> n_rows.
    order_padded = np.full(n_slices * c, m.n_rows, np.int64)
    order_padded[: m.n_rows] = order
    rows_by_slice = order_padded.reshape(n_slices, c).astype(np.int32)

    bucket_cols, bucket_vals, bucket_rows = [], [], []
    for w in uniq:
        ids = np.nonzero(bwidths == w)[0]               # ascending = dest order
        lo = slice_base_dest[rank_of[ids[0]]]
        hi = lo + len(ids) * w * c
        bucket_cols.append(cols_flat[lo:hi].reshape(len(ids), int(w), c))
        bucket_vals.append(vals_flat[lo:hi].reshape(len(ids), int(w), c))
        bucket_rows.append(rows_by_slice[ids])
    return SellSlabs(
        bucket_cols=tuple(bucket_cols),
        bucket_vals=tuple(bucket_vals),
        bucket_rows=tuple(bucket_rows),
        n_rows=m.n_rows,
        n_cols=m.n_cols,
        nnz=m.nnz,
        sigma=sigma,
    )


def sell_to_slabs(sell: SellCSigmaMatrix) -> SellSlabs:
    """Bucket a ragged :class:`SellCSigmaMatrix` into device slabs."""
    c = sell.c
    n_slices = len(sell.slice_cols)
    bwidths = next_pow2(np.array([sc.shape[0] for sc in sell.slice_cols]))
    order_padded = np.full(n_slices * c, sell.n_rows, np.int64)
    order_padded[: sell.n_rows] = sell.perm
    rows_by_slice = order_padded.reshape(n_slices, c).astype(np.int32)
    bucket_cols, bucket_vals, bucket_rows = [], [], []
    for w in np.unique(bwidths):
        ids = np.nonzero(bwidths == w)[0]
        cols = np.full((len(ids), int(w), c), PAD, np.int32)
        vals = np.zeros((len(ids), int(w), c), sell.slice_vals[0].dtype)
        for j, s in enumerate(ids):
            ws = sell.slice_cols[s].shape[0]
            cols[j, :ws] = sell.slice_cols[s]
            vals[j, :ws] = sell.slice_vals[s]
        bucket_cols.append(cols)
        bucket_vals.append(vals)
        bucket_rows.append(rows_by_slice[ids])
    return SellSlabs(
        bucket_cols=tuple(bucket_cols),
        bucket_vals=tuple(bucket_vals),
        bucket_rows=tuple(bucket_rows),
        n_rows=sell.n_rows,
        n_cols=sell.n_cols,
        nnz=sell.nnz,
        sigma=0,
    )


def _coo_to_csr(
    rows: np.ndarray, offs: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    n_rows: int, n_cols: int,
) -> CSRMatrix:
    """Rebuild CSR from (row, within-row offset, col, val) tuples."""
    key = np.lexsort((offs, rows))
    rows, cols, vals = rows[key], cols[key], vals[key]
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return CSRMatrix(indptr=indptr, indices=cols.astype(np.int32),
                     data=vals, n_cols=n_cols)


def ellpack_to_csr(ell: EllpackMatrix) -> CSRMatrix:
    """Invert :func:`csr_to_ellpack` (drops nothing: pads are masked out)."""
    s, w, cc = np.nonzero(ell.cols != PAD)
    rows = s * ell.c + cc
    return _coo_to_csr(rows, w, ell.cols[s, w, cc], ell.vals[s, w, cc],
                       ell.n_rows, ell.n_cols)


def sell_slabs_to_csr(slabs: SellSlabs) -> CSRMatrix:
    """Invert :func:`csr_to_sell_slabs`: un-sort and re-pack as CSR."""
    all_rows, all_offs, all_cols, all_vals = [], [], [], []
    for cols, vals, rowmap in zip(slabs.bucket_cols, slabs.bucket_vals, slabs.bucket_rows):
        s, w, lane = np.nonzero(cols != PAD)
        all_rows.append(rowmap[s, lane].astype(np.int64))
        all_offs.append(w)
        all_cols.append(cols[s, w, lane])
        all_vals.append(vals[s, w, lane])
    if not all_rows:
        return CSRMatrix(np.zeros(slabs.n_rows + 1, np.int64),
                         np.empty(0, np.int32),
                         np.empty(0), slabs.n_cols)
    return _coo_to_csr(
        np.concatenate(all_rows), np.concatenate(all_offs),
        np.concatenate(all_cols), np.concatenate(all_vals),
        slabs.n_rows, slabs.n_cols,
    )


def to_csr(matrix) -> CSRMatrix:
    """Normalize any supported format back to CSR (for repacking)."""
    if isinstance(matrix, CSRMatrix):
        return matrix
    if isinstance(matrix, EllpackMatrix):
        return ellpack_to_csr(matrix)
    if isinstance(matrix, SellSlabs):
        return sell_slabs_to_csr(matrix)
    if isinstance(matrix, SellCSigmaMatrix):
        return sell_slabs_to_csr(sell_to_slabs(matrix))
    raise TypeError(f"unsupported sparse format: {type(matrix).__name__}")


# ---------------------------------------------------------------------------
# Multi-device row partitioning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedSlabs:
    """Row-partitioned :class:`SellSlabs`, stacked along a device axis.

    Every shard owns a contiguous, nnz-balanced range of rows and is packed
    independently at the parent's (C, sigma); the per-shard slabs are then
    padded to one COMMON bucket structure (union of power-of-two widths,
    per-bucket slice counts padded with PAD-only slabs) so a single SPMD
    program — one ``shard_map`` body — runs every device.  ``bucket_cols[b]``
    is (n_shards, S_b, W_b, C), ``bucket_rows[b]`` is (n_shards, S_b, C)
    holding *shard-local* row ids (padding lanes map to ``rows_max``, the
    shared local dump slot).

    The boundary-column gather metadata: shard ``d`` only references
    columns in the window ``[col_starts[d], col_starts[d] + window_cols)``,
    so the shard_map body gathers one uniform ``window_cols``-wide slice of
    the replicated X instead of the whole operand; stored column indices
    are already rebased into that window.  ``boundary_cols`` is the worst
    per-shard count of referenced columns outside the shard's even
    ``n_cols / n_shards`` share — the volume a column-exchange collective
    would move, priced by ``plan_spmm_sell_sharded``.
    """

    bucket_cols: tuple[np.ndarray, ...]   # each (n_shards, S_b, W_b, C) int32
    bucket_vals: tuple[np.ndarray, ...]   # each (n_shards, S_b, W_b, C) float
    bucket_rows: tuple[np.ndarray, ...]   # each (n_shards, S_b, C) int32, local
    row_starts: np.ndarray                # (n_shards,) int64: first global row
    row_counts: np.ndarray                # (n_shards,) int64: rows owned
    col_starts: np.ndarray                # (n_shards,) int32: X window start
    window_cols: int                      # uniform X window width
    boundary_cols: int                    # worst out-of-share column count
    n_rows: int
    n_cols: int
    nnz: int
    sigma: int

    @property
    def c(self) -> int:
        return self.bucket_cols[0].shape[3]

    @property
    def n_shards(self) -> int:
        return self.bucket_cols[0].shape[0]

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(c.shape[2] for c in self.bucket_cols)

    @property
    def slices_per_shard(self) -> tuple[int, ...]:
        return tuple(c.shape[1] for c in self.bucket_cols)

    @property
    def rows_max(self) -> int:
        """Rows of the widest shard — the local dump-slot index."""
        return int(self.row_counts.max()) if len(self.row_counts) else 0

    @property
    def padded_nnz(self) -> int:
        return sum(c.size for c in self.bucket_cols)

    @property
    def pad_factor(self) -> float:
        return self.padded_nnz / max(self.nnz, 1)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference host SpMV mirroring the sharded schedule exactly:
        per-shard window gather + local scatter, shards concatenated."""
        out = np.zeros(self.n_rows, dtype=np.result_type(self.bucket_vals[0], x))
        for d in range(self.n_shards):
            lo = int(self.col_starts[d])
            xw = x[lo : lo + self.window_cols]
            xg = np.concatenate([xw, np.zeros(1, x.dtype)])
            y = np.zeros(self.rows_max + 1, out.dtype)
            for cols, vals, rows in zip(self.bucket_cols, self.bucket_vals,
                                        self.bucket_rows):
                safe = np.where(cols[d] == PAD, len(xw), cols[d])
                yb = np.einsum("swc,swc->sc", vals[d], xg[safe])
                y[rows[d].reshape(-1)] = yb.reshape(-1)
            r0, cnt = int(self.row_starts[d]), int(self.row_counts[d])
            out[r0 : r0 + cnt] = y[:cnt]
        return out


def shard_row_ranges(lengths: np.ndarray, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous row ranges [lo, hi) balancing nnz across ``n_shards``.

    The weight is ``nnz + 1`` per row so all-empty stretches still spread
    instead of collapsing into one shard.  Ranges partition [0, n_rows)
    exactly; a shard may be empty (lo == hi) when rows run out.
    """
    lengths = np.asarray(lengths, np.int64)
    n = len(lengths)
    n_shards = max(int(n_shards), 1)
    cum = np.zeros(n + 1, np.int64)
    np.cumsum(lengths + 1, out=cum[1:])
    targets = cum[-1] * np.arange(1, n_shards) / n_shards
    cuts = np.searchsorted(cum, targets)
    bounds = np.maximum.accumulate(np.concatenate([[0], cuts, [n]]))
    return [(int(bounds[i]), int(bounds[i + 1])) for i in range(n_shards)]


def _csr_row_slice(m: CSRMatrix, lo: int, hi: int) -> CSRMatrix:
    """Rows [lo, hi) of ``m`` as a standalone CSR (column ids unchanged)."""
    s, e = int(m.indptr[lo]), int(m.indptr[hi])
    return CSRMatrix(
        indptr=(m.indptr[lo : hi + 1] - m.indptr[lo]),
        indices=m.indices[s:e],
        data=m.data[s:e],
        n_cols=m.n_cols,
    )


def shard_slabs(slabs: SellSlabs, n_shards: int) -> ShardedSlabs:
    """Row-partition slabs into ``n_shards`` device slabs (see
    :class:`ShardedSlabs` for the layout contract).

    Each shard re-packs its contiguous nnz-balanced row range at the
    parent's (C, sigma) — the sigma-sort is *local*, so a shard's slices
    never mix rows across the partition — and the shard structures are
    unified so one kernel program serves every device.
    """
    csr = sell_slabs_to_csr(slabs)
    c = slabs.c
    sigma = int(slabs.sigma or 8 * c)
    ranges = shard_row_ranges(csr.row_lengths, n_shards)
    n_shards = len(ranges)
    shards = [
        csr_to_sell_slabs(_csr_row_slice(csr, lo, hi), c=c, sigma=sigma)
        for lo, hi in ranges
    ]
    rows_max = max(s.n_rows for s in shards)

    # Per-shard referenced-column window + out-of-share boundary count.
    col_starts = np.zeros(n_shards, np.int32)
    window = 1
    boundary = 0
    n_cols = max(csr.n_cols, 1)
    for d, ((lo, hi), s) in enumerate(zip(ranges, shards)):
        ref = csr.indices[int(csr.indptr[lo]) : int(csr.indptr[hi])]
        if len(ref):
            c_lo, c_hi = int(ref.min()), int(ref.max()) + 1
        else:
            c_lo, c_hi = 0, 1
        col_starts[d] = c_lo
        window = max(window, c_hi - c_lo)
        fair_lo = d * csr.n_cols // n_shards
        fair_hi = (d + 1) * csr.n_cols // n_shards
        outside = np.unique(ref[(ref < fair_lo) | (ref >= fair_hi)])
        boundary = max(boundary, len(outside))
    window = min(window, n_cols)
    col_starts = np.minimum(col_starts, n_cols - window).astype(np.int32)

    # Union bucket structure: every width any shard produced, slice counts
    # padded to the per-width max with PAD-only slabs.
    per_shard = [dict(zip(s.widths, range(s.n_buckets))) for s in shards]
    union_w = sorted({w for s in shards for w in s.widths})
    smax = {
        w: max(
            (s.bucket_cols[per_shard[d][w]].shape[0]
             if w in per_shard[d] else 0)
            for d, s in enumerate(shards))
        for w in union_w
    }
    val_dtype = slabs.bucket_vals[0].dtype if slabs.bucket_vals else np.float64
    bucket_cols, bucket_vals, bucket_rows = [], [], []
    for w in union_w:
        s_b = smax[w]
        cols = np.full((n_shards, s_b, w, c), PAD, np.int32)
        vals = np.zeros((n_shards, s_b, w, c), val_dtype)
        rows = np.full((n_shards, s_b, c), rows_max, np.int32)
        for d, s in enumerate(shards):
            if w not in per_shard[d]:
                continue  # empty per-device bucket: stays all-PAD
            b = per_shard[d][w]
            sc, sv, sr = s.bucket_cols[b], s.bucket_vals[b], s.bucket_rows[b]
            nb = sc.shape[0]
            # rebase columns into the shard's X window; PAD stays PAD
            cols[d, :nb] = np.where(sc == PAD, PAD, sc - col_starts[d])
            vals[d, :nb] = sv
            # local ids; the shard's own dump slot remaps to the shared one
            rows[d, :nb] = np.where(sr == s.n_rows, rows_max, sr)
        bucket_cols.append(cols)
        bucket_vals.append(vals)
        bucket_rows.append(rows)

    return ShardedSlabs(
        bucket_cols=tuple(bucket_cols),
        bucket_vals=tuple(bucket_vals),
        bucket_rows=tuple(bucket_rows),
        row_starts=np.array([lo for lo, _ in ranges], np.int64),
        row_counts=np.array([hi - lo for lo, hi in ranges], np.int64),
        col_starts=col_starts,
        window_cols=int(window),
        boundary_cols=int(boundary),
        n_rows=csr.n_rows,
        n_cols=csr.n_cols,
        nnz=csr.nnz,
        sigma=sigma,
    )


# ---------------------------------------------------------------------------
# Generators (vectorized: distinct sorted column draws via order statistics)
# ---------------------------------------------------------------------------


def _segment_sort(values: np.ndarray, seg: np.ndarray, n_vals: int) -> np.ndarray:
    """Sort ``values`` within each segment (``seg`` nondecreasing)."""
    key = seg * np.int64(n_vals + 1) + values
    return np.sort(key) - seg * np.int64(n_vals + 1)


def _distinct_sorted_draws(
    rng: np.random.Generator, lengths: np.ndarray, domain: np.ndarray
) -> np.ndarray:
    """For each row r, ``lengths[r]`` distinct sorted ints in [0, domain[r]).

    Classic order-statistics trick, fully vectorized: draw k iid samples
    from [0, domain - k], sort within the row, add 0..k-1 — the result is
    strictly increasing, hence distinct.
    """
    rows = np.repeat(np.arange(len(lengths), dtype=np.int64), lengths)
    if not len(rows):
        return np.empty(0, np.int64)
    high = (domain - lengths + 1)[rows]           # exclusive upper bound
    draws = rng.integers(0, high)
    draws = _segment_sort(draws, rows, int(domain.max()) + 1)
    starts = np.zeros(len(lengths) + 1, np.int64)
    np.cumsum(lengths, out=starts[1:])
    pos = np.arange(len(rows), dtype=np.int64) - starts[rows]
    return draws + pos


def random_csr(
    n_rows: int,
    n_cols: int,
    avg_nnz_row: float,
    seed: int = 0,
    dtype=np.float64,
    skew: float = 0.0,
) -> CSRMatrix:
    """Random sparse matrix with Poisson-ish row lengths.

    ``skew > 0`` switches the row-length law to a lognormal with that sigma
    (heavy-tailed, mean ~``avg_nnz_row``), the shape SELL-C-sigma exists for.
    Fully vectorized: packing a 10^6-row matrix is a few array ops, not a
    Python loop.
    """
    rng = np.random.default_rng(seed)
    if skew > 0:
        raw = rng.lognormal(np.log(max(avg_nnz_row, 1.0)) - skew**2 / 2, skew, n_rows)
        lengths = np.clip(np.round(raw).astype(np.int64), 1, n_cols)
    else:
        lengths = np.clip(rng.poisson(avg_nnz_row, n_rows), 1, n_cols).astype(np.int64)
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lengths, out=indptr[1:])
    indices = _distinct_sorted_draws(
        rng, lengths, np.full(n_rows, n_cols, np.int64)
    ).astype(np.int32)
    data = rng.standard_normal(indptr[-1]).astype(dtype)
    return CSRMatrix(indptr=indptr, indices=indices, data=data, n_cols=n_cols)


def cage10_like(seed: int = 0, dtype=np.float64) -> CSRMatrix:
    """CAGE10-shaped matrix (11,397 x 11,397, ~150,645 nnz, avg 13.2/row).

    The SuiteSparse file is not bundled offline; this generator reproduces its
    *structural statistics* (dimension, nnz, near-banded locality), which is
    what the memory-behavior study depends on.  Each row holds its diagonal
    plus distinct entries from a +-200 band, drawn vectorized.
    """
    n = 11_397
    target_nnz = 150_645
    avg = target_nnz / n            # ~13.2
    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.poisson(avg - 1, n) + 1, 1, 33)  # cage10 max ~33
    # Scale to hit the target nnz closely.
    scale = (target_nnz - n) / max((lengths - 1).sum(), 1)
    lengths = 1 + np.round((lengths - 1) * scale).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=indptr[1:])

    r = np.arange(n, dtype=np.int64)
    lo = np.maximum(0, r - 200)
    band = np.minimum(n, r + 201) - lo            # band size per row (>= 201)
    k_off = lengths - 1                           # off-diagonal entries
    # Distinct draws from the band minus the diagonal slot, then shift the
    # values at/after the diagonal's in-band offset up by one to skip it.
    draws = _distinct_sorted_draws(rng, k_off, band - 1)
    rows_off = np.repeat(r, k_off)
    diag_off = (r - lo)[rows_off]
    draws = np.where(draws >= diag_off, draws + 1, draws) + lo[rows_off]

    # Interleave: k-1 band entries then the diagonal, re-sorted per row.
    indices = np.empty(indptr[-1], np.int64)
    rows_all = np.repeat(r, lengths)
    off_slots = np.arange(indptr[-1]) - indptr[rows_all]
    indices[off_slots < (lengths - 1)[rows_all]] = draws
    indices[indptr[1:] - 1] = r                   # diagonal in the last slot
    indices = _segment_sort(indices, rows_all, n)
    data = rng.standard_normal(indptr[-1]).astype(dtype)
    return CSRMatrix(indptr=indptr, indices=indices.astype(np.int32),
                     data=data, n_cols=n)
