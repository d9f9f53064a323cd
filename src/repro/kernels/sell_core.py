"""The one batched SELL execution core: multi-RHS gather kernels + scatter.

The paper's amortization argument — long vectors hide memory latency by
keeping many independent element streams in flight — applies across
*requests* just as it applies across rows: k right-hand sides against one
matrix fill the lane dimension that a single RHS leaves idle.  This module
is the single device-execution core every SELL-layout kernel drives:

* :func:`spmm_sell` — ``Y[:, k] = A @ X[:, k]`` over width-bucketed SELL
  slabs, the k = 1 column of which is exactly the old ``spmv_sell``.  The
  RHS axis is tiled by ``k_block`` (co-tuned with (C, sigma, w_block) by
  :func:`repro.core.autotune.tune_sell_layout`) as a third grid axis, so a
  whole coalesced request group runs as ONE launch set instead of a Python
  loop of per-request calls.
* :func:`spmm_sell_stream` — the same contraction for operands that do NOT
  fit VMEM whole: slabs, ``X`` and ``Y`` stay HBM-resident (``ANY`` memory
  space) and the kernel hand-pipelines (column-tile x k-tile x w-block)
  working sets through VMEM scratch with double-buffered async copies —
  tile t+1 is in flight while tile t computes.  This is the paper's
  latency-tolerance thesis at production sizes: many independent element
  streams hide the HBM round-trip, so one node hosts million-row operands.
* :func:`bucketed_node_step` — the shared per-bucket launch + scatter loop
  of the graph kernels: BFS and PageRank supply only their combine kernels
  (frontier test, pull-sum), the scatter that merges a split node's
  virtual rows (min, add) and their per-step state as stacked (n + 1, k)
  columns; the slice/scatter plumbing lives here once.

The gather form.  Every kernel reads X (or the graph state) from VMEM
through :func:`gather`: the vector is held as a (k, R, lanes) table
(:func:`lane_table`) and an index tile is resolved one ``lanes``-wide chunk
at a time with Mosaic's in-vreg lane gather, keeping the chunk's values
where the index's high part names that chunk.  It moves no extra HBM bytes
(X is read into VMEM once per k tile) and costs
``ceil(n_cols / lanes)`` gather passes over every 8-row index block, so
its VPU work grows with the number of columns (summed over the column
tiles on the streaming schedule).

Both SpMM entry points keep the SELL contract of :mod:`repro.kernels.sell`:
every real row/node appears in exactly one bucket, padding lanes scatter
into a dump slot (index ``n``) that drivers trim — and they share one RHS
padding policy (:func:`k_tile_for` / :func:`padded_k`): the k axis is
padded at most once, to the k tile one grid cell processes, and a stack
whose k is already a power of two is never re-padded.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import compiler_params, resolve_interpret
from repro.sparse.formats import (
    SUBLANES,
    k_tile_for,
    padded_k,
    pow2_ceil,
    w_tile_for,
)

PAD = -1
#: lanes of a TPU vreg: the widest table chunk one gather pass reads
LANES = 128

__all__ = [
    "LANES",
    "PAD",
    "bucketed_node_step",
    "gather",
    "k_tile_for",
    "lane_table",
    "lane_width",
    "padded_k",
    "pow2_ceil",
    "spmm_bucket",
    "spmm_sell",
    "spmm_sell_stream",
    "take_lanes",
]


# ---------------------------------------------------------------------------
# The in-VMEM gather
# ---------------------------------------------------------------------------


def lane_width(c: int) -> int:
    """Table chunk width for index tiles ``c`` lanes wide (the slice
    height C): the whole tile when it is at most one vreg wide, else one
    vreg — ``c`` must then be a multiple of 128, which every power-of-two
    slice height above 128 is."""
    c = int(c)
    if c > LANES and c % LANES:
        raise ValueError(
            f"slice height {c} above {LANES} must be a multiple of {LANES}")
    return min(c, LANES)


def lane_table(x: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """(n, k) -> (k, R, lanes): each column of ``x`` zero-padded to
    ``R * lanes`` entries and cut into rows of ``lanes`` — the layout
    :func:`gather` reads."""
    n, k = x.shape
    r = max(-(-n // lanes), 1)
    if r * lanes != n:
        x = jnp.pad(x, ((0, r * lanes - n), (0, 0)))
    return x.T.reshape(k, r, lanes)


_LANE_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def take_lanes(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``out[i, j] = x[i, idx[i, j]]`` for 2-D ``x``/``idx`` of one shape:
    the lane gather Mosaic lowers (within one vreg's lanes, so
    ``idx < min(x.shape[1], 128)``)."""
    return jax.lax.gather(
        x, idx[..., None], _LANE_GATHER, (1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def gather(table_ref, kk, idx: jnp.ndarray) -> jnp.ndarray:
    """``table_ref[kk]`` read as a flat vector at ``idx`` (rows, C).

    Mosaic gathers only among the lanes of one vreg, so the flat vector is
    read one ``lanes``-wide chunk at a time: pass r gathers lane
    ``idx % lanes`` of chunk r and keeps it where ``idx // lanes == r``.
    ``idx`` must lie in [0, R * lanes); callers map PAD to 0 and mask.
    """
    rows, c = idx.shape
    n_chunks, lanes = table_ref.shape[-2:]
    if lanes & (lanes - 1):
        hi, lo = idx // lanes, idx % lanes
    else:
        hi, lo = idx >> (lanes.bit_length() - 1), idx & (lanes - 1)
    pieces = c // lanes

    def chunk(r, acc):
        row = jnp.broadcast_to(table_ref[kk, pl.ds(r, 1), :], (rows, lanes))
        out = []
        for q in range(pieces):
            part = slice(q * lanes, (q + 1) * lanes)
            got = take_lanes(row, lo[:, part])
            out.append(jnp.where(hi[:, part] == r, got, acc[q]))
        return tuple(out)

    init = tuple(jnp.zeros((rows, lanes), table_ref.dtype)
                 for _ in range(pieces))
    acc = jax.lax.fori_loop(0, n_chunks, chunk, init)
    return acc[0] if pieces == 1 else jnp.concatenate(acc, axis=1)


def _row_blocks(width: int) -> tuple[int, int]:
    """(rows per block, blocks): a (width, C) tile is reduced 8 rows at a
    time so one gather's working set stays a few vregs."""
    rows = min(int(width), SUBLANES)
    return rows, int(width) // rows


# ---------------------------------------------------------------------------
# Multi-RHS SpMM
# ---------------------------------------------------------------------------


def _spmm_kernel(cols_ref, vals_ref, xt_ref, y_ref):
    """Gather-MAC over one (w_tile, C) slab tile for a k_tile of RHS.

    Grid is (n_slices, n_ktiles, n_wtiles) with the W axis innermost so
    the revisited (1, k_tile, C) y block accumulates across W tiles per
    (slice, k-tile).
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    rows, n_blocks = _row_blocks(cols_ref.shape[1])
    c = cols_ref.shape[2]

    def column(kk, carry):
        def block(b, acc):
            cols = cols_ref[0, pl.ds(b * rows, rows), :]
            vals = vals_ref[0, pl.ds(b * rows, rows), :]
            mask = cols != PAD
            x = gather(xt_ref, kk, jnp.where(mask, cols, 0))
            return acc + jnp.sum(jnp.where(mask, vals * x, 0), axis=0,
                                 keepdims=True)

        acc = jax.lax.fori_loop(0, n_blocks, block,
                                jnp.zeros((1, c), y_ref.dtype))
        y_ref[0, pl.ds(kk, 1), :] += acc
        return carry

    jax.lax.fori_loop(0, xt_ref.shape[0], column, 0)


def _spmm_bucket(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    xt: jnp.ndarray,
    *,
    w_block: int,
    k_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    """One bucket: (n_slices, W_b, C) slab x (k, R, lanes) RHS table ->
    (n_slices*C, k).

    ``xt``'s k axis must already be padded to a multiple of ``k_tile`` (the
    caller owns the k_block policy so every bucket of a launch shares one
    RHS tiling).
    """
    n_slices, width, c = cols.shape
    kp = xt.shape[0]
    wt = w_tile_for(width, w_block)
    if width % wt:
        pad = wt - width % wt
        cols = jnp.pad(cols, ((0, 0), (0, pad), (0, 0)), constant_values=PAD)
        vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0)))
        width += pad
    grid = (n_slices, kp // k_tile, width // wt)
    out = pl.pallas_call(
        _spmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, wt, c), lambda i, kk, j: (i, j, 0)),
            pl.BlockSpec((1, wt, c), lambda i, kk, j: (i, j, 0)),
            pl.BlockSpec((k_tile,) + xt.shape[1:], lambda i, kk, j: (kk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, k_tile, c), lambda i, kk, j: (i, kk, 0)),
        out_shape=jax.ShapeDtypeStruct((n_slices, kp, c), vals.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(cols, vals, xt)
    return out.transpose(0, 2, 1).reshape(n_slices * c, kp)


def spmm_bucket(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    xt: jnp.ndarray,
    *,
    w_block: int,
    k_tile: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Public handle on the per-bucket resident launch.

    The sharded executor (:mod:`repro.kernels.sell_shard`) drives buckets
    one at a time inside a ``shard_map`` body — each device runs this same
    program over its own slab block — so the single-bucket contraction is
    part of the core's contract, not an implementation detail.  ``xt`` is
    the RHS as a :func:`lane_table` at ``lane_width(C)``, its k axis
    already a ``k_tile`` multiple (the caller owns the :func:`padded_k`
    policy).
    """
    return _spmm_bucket(
        cols, vals, xt, w_block=w_block, k_tile=k_tile,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("n_rows", "w_block", "k_block", "interpret")
)
def spmm_sell(
    bucket_cols: tuple[jnp.ndarray, ...],
    bucket_vals: tuple[jnp.ndarray, ...],
    bucket_rows: tuple[jnp.ndarray, ...],
    x: jnp.ndarray,
    *,
    n_rows: int,
    w_block: int = 8,
    k_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Y = A @ X over width-bucketed SELL slabs; X is (n_cols, k).

    Returns Y of shape (n_rows, k).  ``k_block`` caps the RHS tile: the k
    axis is padded internally to the tile one grid cell processes —
    **at most once** (the shared policy of :func:`k_tile_for`): a stack
    whose k is already a power of two (the service's ``_pow2_pad`` output)
    is a fixpoint of :func:`padded_k` and is never re-padded here, so the
    service-side pow2 pad and the core-side tile pad can never stack.
    Note that jit still specializes on the *incoming* (n_cols, k) shape —
    callers serving variable group sizes should pow2-pad their RHS stack
    first so group sizes share log2 compiled programs.

    Every grid cell maps the whole (k_tile, n_cols) RHS table into VMEM —
    the *resident* schedule.  Operands whose RHS table (double-buffered by
    the pipeline) would blow the VMEM budget belong to
    :func:`spmm_sell_stream`; ``ops.spmm`` dispatches on the static
    preflight plan.  ``interpret=None`` runs what the backend runs.
    """
    interpret = resolve_interpret(interpret)
    k = x.shape[1]
    kp = k_tile_for(k, k_block)
    xk = padded_k(k, k_block)
    if xk != k:
        x = jnp.pad(x, ((0, 0), (0, xk - k)))
    dtype = bucket_vals[0].dtype if bucket_vals else x.dtype
    y = jnp.zeros((n_rows + 1, xk), dtype)  # +1 dump slot for pads
    if bucket_cols:
        xt = lane_table(x.astype(dtype), lane_width(bucket_cols[0].shape[2]))
    for cols, vals, rows in zip(bucket_cols, bucket_vals, bucket_rows):
        yb = _spmm_bucket(
            cols, vals, xt, w_block=w_block, k_tile=kp, interpret=interpret
        )
        y = y.at[rows.reshape(-1)].set(yb)
    return y[:n_rows, :k]


# ---------------------------------------------------------------------------
# Out-of-VMEM streaming SpMM: double-buffered tile pipeline
# ---------------------------------------------------------------------------


def _spmm_stream_kernel(cols_ref, vals_ref, xt_ref, y_ref,
                        cbuf, vbuf, xbuf, yacc, csem, vsem, xsem, ysem,
                        *, row_tile, w_tile, col_chunks, k_tile, n_w, n_ct):
    """One (row-tile, k-tile) grid cell of the streaming schedule.

    Every ref lives in ``ANY`` (HBM); the cell owns four VMEM scratch
    buffers — double-buffered slab tiles (``cbuf``/``vbuf``), a
    double-buffered (k_tile, col_chunks, lanes) RHS table tile (``xbuf``)
    and the (row_tile, k_tile, C) output accumulator (``yacc``) — and
    hand-rolls the pipeline: while step g computes, the DMAs for step g+1
    are already in flight (and the next column tile of X prefetches as the
    current one starts its first slab pass), so the HBM round-trip hides
    behind the gather-MAC exactly as the paper's latency-tolerance argument
    says it should.  Step order is (col-tile, slice, w-block)
    innermost-last: one X tile is reused across every slice of the row
    tile before the next tile streams in, amortizing the dominant X
    traffic ``row_tile``-fold.
    """
    i = pl.program_id(0)
    k0 = pl.program_id(1)
    base_s = i * row_tile
    steps_per_tile = row_tile * n_w              # slab steps per X tile
    n_steps = n_ct * steps_per_tile
    col_tile = col_chunks * xbuf.shape[-1]
    rows, n_blocks = _row_blocks(w_tile)
    c = cbuf.shape[-1]

    def x_dma(slot, t):
        return pltpu.make_async_copy(
            xt_ref.at[pl.ds(k0 * k_tile, k_tile),
                      pl.ds(t * col_chunks, col_chunks), :],
            xbuf.at[slot], xsem.at[slot])

    def c_dma(slot, s, j):
        return pltpu.make_async_copy(
            cols_ref.at[base_s + s, pl.ds(j * w_tile, w_tile), :],
            cbuf.at[slot], csem.at[slot])

    def v_dma(slot, s, j):
        return pltpu.make_async_copy(
            vals_ref.at[base_s + s, pl.ds(j * w_tile, w_tile), :],
            vbuf.at[slot], vsem.at[slot])

    yacc[...] = jnp.zeros_like(yacc)
    x_dma(0, 0).start()                          # warm the pipeline
    c_dma(0, 0, 0).start()
    v_dma(0, 0, 0).start()

    def body(g, carry):
        t = g // steps_per_tile                  # X column tile
        q = g % steps_per_tile
        s = q // n_w                             # slice within the row tile
        j = q % n_w                              # w-block within the slice
        xslot = t % 2
        slot = g % 2

        @pl.when(q == 0)
        def _wait_x():                           # first touch of X tile t
            x_dma(xslot, t).wait()

        @pl.when((q == 0) & (t + 1 < n_ct))
        def _prefetch_x():                       # overlap tile t+1's copy
            x_dma((t + 1) % 2, t + 1).start()    # with ALL of tile t's work

        @pl.when(g + 1 < n_steps)
        def _prefetch_slab():                    # next slab tile in flight
            q1 = (g + 1) % steps_per_tile        # while this one computes
            c_dma((g + 1) % 2, q1 // n_w, q1 % n_w).start()
            v_dma((g + 1) % 2, q1 // n_w, q1 % n_w).start()

        c_dma(slot, s, j).wait()
        v_dma(slot, s, j).wait()
        lo = t * col_tile
        xtile = xbuf.at[xslot]

        def column(kk, carry2):
            def block(b, acc):
                cols = cbuf[slot, pl.ds(b * rows, rows), :]
                vals = vbuf[slot, pl.ds(b * rows, rows), :]
                local = cols - lo
                # PAD (-1) never lands in a tile: lo >= 0 makes cols >= lo
                # false
                mask = (cols >= lo) & (local < col_tile)
                x = gather(xtile, kk, jnp.where(mask, local, 0))
                return acc + jnp.sum(jnp.where(mask, vals * x, 0.0), axis=0,
                                     keepdims=True)

            acc = jax.lax.fori_loop(0, n_blocks, block,
                                    jnp.zeros((1, c), yacc.dtype))
            yacc[s, pl.ds(kk, 1), :] += acc
            return carry2

        jax.lax.fori_loop(0, k_tile, column, 0)
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    out = pltpu.make_async_copy(
        yacc,
        y_ref.at[pl.ds(base_s, row_tile), pl.ds(k0 * k_tile, k_tile), :],
        ysem)
    out.start()
    out.wait()


def _spmm_bucket_stream(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    xt: jnp.ndarray,
    *,
    w_block: int,
    k_tile: int,
    col_tile: int,
    row_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    """One bucket of the streaming schedule: nothing resident but scratch.

    ``xt`` arrives as a :func:`lane_table` already padded by the caller —
    k to a multiple of ``k_tile`` and columns to a multiple of
    ``col_tile`` (zero entries, which no stored index can reach) — so every
    DMA moves a full static tile.  Slices are padded to a multiple of
    ``row_tile`` with PAD-only slabs whose accumulators stay zero and are
    trimmed before the scatter.
    """
    n_slices, width, c = cols.shape
    kp, n_chunks, lanes = xt.shape
    col_chunks = col_tile // lanes
    wt = w_tile_for(width, w_block)
    if width % wt:
        pad = wt - width % wt
        cols = jnp.pad(cols, ((0, 0), (0, pad), (0, 0)), constant_values=PAD)
        vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0)))
        width += pad
    row_tile = min(row_tile, n_slices)
    s_pad = -n_slices % row_tile
    if s_pad:
        cols = jnp.pad(cols, ((0, s_pad), (0, 0), (0, 0)),
                       constant_values=PAD)
        vals = jnp.pad(vals, ((0, s_pad), (0, 0), (0, 0)))
    grid = ((n_slices + s_pad) // row_tile, kp // k_tile)
    kernel = functools.partial(
        _spmm_stream_kernel, row_tile=row_tile, w_tile=wt,
        col_chunks=col_chunks, k_tile=k_tile, n_w=width // wt,
        n_ct=n_chunks // col_chunks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_slices + s_pad, kp, c), vals.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, wt, c), cols.dtype),               # slab cols x2
            pltpu.VMEM((2, wt, c), vals.dtype),               # slab vals x2
            pltpu.VMEM((2, k_tile, col_chunks, lanes), xt.dtype),  # X x2
            pltpu.VMEM((row_tile, k_tile, c), vals.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(cols, vals, xt)
    return out[:n_slices].transpose(0, 2, 1).reshape(n_slices * c, kp)


def _stream_col_tile(col_tile: int, n_cols: int, c: int) -> int:
    """The X column tile the streaming schedule runs: ``col_tile`` as a
    power of two, no wider than the padded column count and no narrower
    than one table chunk (``lane_width(c)``)."""
    ct = min(pow2_ceil(max(int(col_tile), 1)), pow2_ceil(max(int(n_cols), 1)))
    return max(ct, lane_width(c))


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "w_block", "k_block", "col_tile", "row_tile",
                     "interpret"),
)
def spmm_sell_stream(
    bucket_cols: tuple[jnp.ndarray, ...],
    bucket_vals: tuple[jnp.ndarray, ...],
    bucket_rows: tuple[jnp.ndarray, ...],
    x: jnp.ndarray,
    *,
    n_rows: int,
    w_block: int = 8,
    k_block: int = 8,
    col_tile: int = 1 << 16,
    row_tile: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Y = A @ X with HBM-resident operands: the out-of-VMEM schedule.

    Same contract and results as :func:`spmm_sell` (the column-tile split
    only adds masked-out zeros to each row's sum), but nothing is
    VMEM-resident: slabs, X and Y live in ``ANY`` memory and the kernel
    double-buffers (k_tile x col_tile) RHS table tiles and (w_tile, C)
    slab tiles through scratch, with a row-tile outer grid axis so slabs
    too large for VMEM stream too.  ``col_tile``/``row_tile`` are co-tuned
    by :func:`repro.core.autotune.pick_stream_tiles` and persisted in the
    TuneCache next to (C, sigma, w_block, k_block).

    The k axis follows the same single-padding policy as the resident path
    (:func:`padded_k`); the n_cols axis is padded to a column-tile multiple
    (:func:`_stream_col_tile`) with zero rows no stored index reaches.
    """
    interpret = resolve_interpret(interpret)
    k = x.shape[1]
    kp = k_tile_for(k, k_block)
    xk = padded_k(k, k_block)
    if xk != k:
        x = jnp.pad(x, ((0, 0), (0, xk - k)))
    dtype = bucket_vals[0].dtype if bucket_vals else x.dtype
    y = jnp.zeros((n_rows + 1, xk), dtype)  # +1 dump slot for pads
    if not bucket_cols:
        return y[:n_rows, :k]
    c = bucket_cols[0].shape[2]
    ct = _stream_col_tile(col_tile, x.shape[0], c)
    if x.shape[0] % ct:
        x = jnp.pad(x, ((0, ct - x.shape[0] % ct), (0, 0)))
    xt = lane_table(x.astype(dtype), lane_width(c))
    for cols, vals, rows in zip(bucket_cols, bucket_vals, bucket_rows):
        yb = _spmm_bucket_stream(
            cols, vals, xt, w_block=w_block, k_tile=kp, col_tile=ct,
            row_tile=max(int(row_tile), 1), interpret=interpret,
        )
        y = y.at[rows.reshape(-1)].set(yb)
    return y[:n_rows, :k]


# ---------------------------------------------------------------------------
# Shared bucket-launch + scatter loop for the graph kernels
# ---------------------------------------------------------------------------


def bucketed_node_step(
    kernel: Callable,
    bucket_adj: tuple[jnp.ndarray, ...],
    bucket_nodes: tuple[jnp.ndarray, ...],
    state: jnp.ndarray,
    scalars: jnp.ndarray | None,
    out_init: jnp.ndarray,
    *,
    combine: str,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Run ``kernel`` over every (n_slices_b, C, W_b) bucket and scatter.

    ``kernel(adj_ref, nodes_ref, table_ref, scal_ref, out_ref)`` sees one
    (1, W_b, C) adjacency tile (the bucket transposed so C sits on lanes),
    its (1, 1, C) owning-node map, the (n + 1, k) ``state`` columns whole
    as a :func:`lane_table`, the step's ``scalars`` in SMEM (no
    ``scal_ref`` when ``scalars`` is None), and writes a (1, k, C) output
    tile — the per-kernel combine op.  The per-bucket results are
    scattered into ``out_init`` (shape (n + 1, k); padding lanes land in
    its dump slot n) through the node maps with the scatter ``combine``
    (``"min"`` or ``"add"``), which merges the lanes of a node split into
    several virtual rows; this loop is the one copy of the slice/scatter
    plumbing shared by BFS and PageRank.
    """
    interpret = resolve_interpret(interpret)
    out = out_init
    k = state.shape[1]
    if not bucket_adj:
        return out
    table = lane_table(state, lane_width(bucket_adj[0].shape[1]))
    smem = () if scalars is None else (scalars,)
    for adj, nodes in zip(bucket_adj, bucket_nodes):
        s, c, w = adj.shape
        res = pl.pallas_call(
            kernel,
            grid=(s,),
            in_specs=[
                pl.BlockSpec((1, w, c), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
                pl.BlockSpec(table.shape, lambda i: (0, 0, 0)),
            ] + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem),
            out_specs=pl.BlockSpec((1, k, c), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((s, k, c), out.dtype),
            compiler_params=compiler_params(),
            interpret=interpret,
        )(adj.transpose(0, 2, 1), nodes.reshape(s, 1, c), table, *smem)
        scatter = getattr(out.at[nodes.reshape(-1)], combine)
        out = scatter(res.transpose(0, 2, 1).reshape(s * c, k))
    return out
