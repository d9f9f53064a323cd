"""Launch-plan builders for every Pallas entry point (engine 1).

Each ``plan_*`` function mirrors the launch arithmetic of its kernel wrapper
(:func:`repro.kernels.sell_core.spmm_sell`,
:func:`repro.kernels.sell_core.spmm_sell_stream`,
:func:`repro.kernels.sell_core.bucketed_node_step` as driven by the BFS /
PageRank kernels, :func:`repro.kernels.fft.fft_stockham` and the NPB FT
step's passes of it) without importing
or executing any of them: the grid dims, block shapes and per-cell VMEM
footprints are derived from operand *metadata* (:class:`SlabMeta`) and the
tuned tile sizes alone.  The footprint model matches the one
:func:`repro.core.autotune.pick_k_block` / ``pick_w_block`` greedily fill —
VMEM-resident RHS block plus double-buffered streamed slab tile plus output
tile — so a plan that violates the budget means the tuner's heuristic (or a
stale cached tune, or a hand-passed block shape) has drifted out of the
modeled envelope and the launch must be rejected *before* XLA sees it.

Checked contracts:

* per-cell VMEM footprint <= ``vmem_budget`` (default: the single source of
  truth :data:`repro.core.autotune.VMEM_BUDGET_BYTES`);
* pow2 padding invariants: requested ``w_block``/``k_block`` and every
  packed bucket width must be powers of two;
* column/adjacency index bounds: every stored index in [PAD, n_cols)
  (``SlabMeta.from_slabs(check_bounds=True)`` scans once, at registration);
* dtype flow: slab buckets agree with each other and with the RHS; indices
  are int32;
* HBM (NPB FT, :func:`plan_ft`): the resident spectrum and a group's
  transient fields fit the chip's memory.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.analysis.launchplan import (
    VMEM_BUDGET_BYTES,
    BlockPlan,
    LaunchPlan,
    is_pow2,
)
from repro.sparse.formats import (
    PAD,
    SUBLANES,
    k_tile_for,
    pow2_ceil,
    w_tile_for,
)

__all__ = [
    "FtPlan",
    "HBM_BUDGET_BYTES",
    "SlabMeta",
    "plan_bfs_sell",
    "plan_fft_stockham",
    "plan_ft",
    "plan_moe_dispatch",
    "plan_pagerank_sell",
    "plan_spmm_sell",
    "plan_spmm_sell_sharded",
    "plan_spmm_sell_stream",
]

_IDX_BYTES = 4                       # int32 column / adjacency indices
_LANES = 128                         # lanes of a TPU vreg

#: HBM of one TPU v5e chip: 16 GB, its published capacity
HBM_BUDGET_BYTES = 16 * 10**9
#: whole fields (re and im planes) one NPB FT step holds beside the
#: resident spectrum, per field in its group: a pass's input and output,
#: and the evolve factor (2.5 fields a member in the compiled program's
#: temporaries at 512^3 on a v5e), rounded up
FT_TRANSIENT_FIELDS = 3
#: widest signal block of an NPB FT pass
FT_B_BLOCK_MAX = 256


def _dtype_bytes(dtype: str) -> int:
    return int(np.dtype(dtype).itemsize)


def _lane_table_bytes(n: int, k: int, c: int, itemsize: int) -> int:
    """VMEM bytes of a (k, R, lanes) gather table over ``n`` entries
    (:func:`repro.kernels.sell_core.lane_table`): R rows of
    ``min(c, 128)`` entries, each (R, lanes) plane padded to whole
    (8, 128) vreg tiles."""
    lanes = min(max(int(c), 1), _LANES)
    r = max(math.ceil(max(int(n), 1) / lanes), 1)
    return int(k) * (SUBLANES * math.ceil(r / SUBLANES)) * _LANES * itemsize


@dataclasses.dataclass(frozen=True)
class SlabMeta:
    """The launch-relevant metadata of a packed SELL operand.

    Cheap to extract (O(n_buckets) shape reads; the optional index-bounds
    scan is one vectorized min/max over the stored indices, done once at
    registration, never per request).  Works for both slab containers:
    matrix :class:`repro.sparse.formats.SellSlabs` (buckets (S, W, C)) and
    graph :class:`repro.graphs.gen.SellGraphSlabs` (buckets (S, C, W)).
    """

    kind: str                       # "matrix" | "graph"
    c: int
    widths: tuple[int, ...]         # padded W per bucket
    n_slices: tuple[int, ...]       # slices per bucket
    n_rows: int                     # rows / nodes
    n_cols: int                     # RHS length (n_cols / n_nodes)
    val_dtype: str | None           # None for graphs (index-only slabs)
    idx_dtype: str
    idx_min: int | None = None      # None = bounds not scanned
    idx_max: int | None = None

    @classmethod
    def from_slabs(cls, slabs, check_bounds: bool = False) -> "SlabMeta":
        """Extract metadata from SellSlabs or SellGraphSlabs (duck-typed)."""
        if hasattr(slabs, "bucket_cols"):       # matrix slabs: (S, W, C)
            idx_arrays = slabs.bucket_cols
            widths = tuple(int(a.shape[1]) for a in idx_arrays)
            c = int(idx_arrays[0].shape[2]) if idx_arrays else 0
            kind, n_rows, n_cols = "matrix", slabs.n_rows, slabs.n_cols
            val_dtype = str(slabs.bucket_vals[0].dtype) if slabs.bucket_vals \
                else None
        elif hasattr(slabs, "bucket_adj"):      # graph slabs: (S, C, W)
            idx_arrays = slabs.bucket_adj
            widths = tuple(int(a.shape[2]) for a in idx_arrays)
            c = int(idx_arrays[0].shape[1]) if idx_arrays else 0
            kind, n_rows, n_cols = "graph", slabs.n_nodes, slabs.n_nodes
            val_dtype = None
        else:
            raise TypeError(
                f"expected SellSlabs or SellGraphSlabs, got "
                f"{type(slabs).__name__}")
        idx_min = idx_max = None
        if check_bounds and idx_arrays:
            idx_min = min(int(np.min(a)) for a in idx_arrays if a.size)
            idx_max = max(int(np.max(a)) for a in idx_arrays if a.size)
        return cls(
            kind=kind, c=c, widths=widths,
            n_slices=tuple(int(a.shape[0]) for a in idx_arrays),
            n_rows=int(n_rows), n_cols=int(n_cols), val_dtype=val_dtype,
            idx_dtype=str(idx_arrays[0].dtype) if idx_arrays else "int32",
            idx_min=idx_min, idx_max=idx_max,
        )

    def describe(self) -> str:
        return (f"{self.kind} {self.n_rows}x{self.n_cols} "
                f"C={self.c} buckets={list(self.widths)}")


def _shared_slab_contracts(meta: SlabMeta, violations: list[str]) -> None:
    """Contracts every SELL launch shares: bucket pow2 widths, index dtype
    and (when scanned) index bounds."""
    for i, w in enumerate(meta.widths):
        if not is_pow2(w):
            violations.append(
                f"bucket {i} width {w} is not a power of two (packer "
                "invariant broken)")
    if meta.idx_dtype != "int32":
        violations.append(
            f"index dtype {meta.idx_dtype} != int32 (kernel gather contract)")
    if meta.idx_max is not None and meta.idx_max >= meta.n_cols:
        violations.append(
            f"stored index {meta.idx_max} out of bounds for n_cols="
            f"{meta.n_cols} (gather would clamp and return garbage)")
    if meta.idx_min is not None and meta.idx_min < PAD:
        violations.append(
            f"stored index {meta.idx_min} below the PAD sentinel ({PAD})")


def plan_spmm_sell(
    meta: SlabMeta,
    k: int = 1,
    x_dtype: str | None = None,
    *,
    w_block: int = 8,
    k_block: int = 8,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan ``spmm_sell`` for a (n_cols, k) RHS stack against these slabs.

    Mirrors the wrapper's tiling: per bucket the W axis is padded to a
    multiple of ``min(w_block, W)`` and the k axis to a multiple of
    ``min(k_block, pow2_ceil(k))``; one grid cell holds the double-buffered
    (w_eff, C) cols+vals tiles, the (n_cols, k_tile) RHS block, and the
    (C, k_tile) output tile.  Pallas pipelines *every* BlockSpec operand
    through a pair of VMEM buffers — the RHS block and output tile are
    priced at 2x just like the slab tiles, so the plan honestly rejects
    operands whose "resident" X only fits once.  Operands rejected here
    belong on the streaming schedule (:func:`plan_spmm_sell_stream`).
    """
    violations: list[str] = []
    if not is_pow2(w_block):
        violations.append(f"w_block {w_block} is not a power of two")
    if not is_pow2(k_block):
        violations.append(f"k_block {k_block} is not a power of two")
    if k < 1:
        violations.append(f"RHS stack must have k >= 1 columns, got {k}")
    _shared_slab_contracts(meta, violations)
    val_dtype = meta.val_dtype or "float64"
    vb = _dtype_bytes(val_dtype)
    if x_dtype is not None:
        if not np.issubdtype(np.dtype(x_dtype), np.floating):
            violations.append(f"RHS dtype {x_dtype} is not floating")
        elif meta.val_dtype is not None and x_dtype != meta.val_dtype:
            violations.append(
                f"RHS dtype {x_dtype} != slab value dtype {meta.val_dtype}")
    k_tile = k_tile_for(k, k_block)
    k_pad = k_tile * math.ceil(max(k, 1) / k_tile)
    xb = _dtype_bytes(x_dtype) if x_dtype is not None else vb
    blocks = []
    for i, (s, w) in enumerate(zip(meta.n_slices, meta.widths)):
        w_eff = w_tile_for(w, w_block)
        w_pad = w_eff * math.ceil(w / w_eff)
        grid = (s, k_pad // k_tile, w_pad // w_eff)
        footprint = (
            2 * w_eff * meta.c * (vb + _IDX_BYTES)   # double-buffered slab tile
            + 2 * _lane_table_bytes(meta.n_cols, k_tile, meta.c, xb)
            + 2 * meta.c * k_tile * vb               # pipelined output pair
        )                                            # (RHS table pair above)
        if footprint > vmem_budget:
            violations.append(
                f"bucket {i} (W={w}): per-cell footprint {footprint} B "
                f"exceeds VMEM budget {vmem_budget} B "
                f"(w_block={w_block}, k_block={k_block})")
        blocks.append(BlockPlan(
            label=f"bucket{i}[W={w}]",
            grid=grid,
            blocks=(
                ("cols", (1, w_eff, meta.c), meta.idx_dtype),
                ("vals", (1, w_eff, meta.c), val_dtype),
                ("x", (k_tile, meta.n_cols), x_dtype or val_dtype),
                ("y", (1, k_tile, meta.c), val_dtype),
            ),
            vmem_bytes=footprint,
        ))
    return LaunchPlan(
        kernel="spmm_sell", operand=meta.describe(), dtype=val_dtype,
        vmem_budget=int(vmem_budget), blocks=tuple(blocks),
        violations=tuple(violations),
    )


def plan_moe_dispatch(
    meta: SlabMeta,
    k: int = 1,
    x_dtype: str | None = None,
    *,
    top_k: int,
    w_block: int = 8,
    k_block: int = 8,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan the MoE expert-dispatch SpMM (:func:`repro.kernels.ops.moe_dispatch`).

    The dispatch operand is the per-step token<->slot routing matrix packed
    into SELL slabs: one row per token (combine direction) or per expert
    capacity slot (gather direction), at most ``top_k`` stored entries per
    row, RHS = the ``(rows, d_model)`` activation stack.  Execution is the
    plain resident ``spmm_sell`` schedule, so the launch arithmetic is
    :func:`plan_spmm_sell` verbatim; on top of the shared slab contracts the
    routing shape itself is enforced:

    * every packed bucket width must stay within ``pow2_ceil(top_k)`` — a
      wider bucket means a row claims more assignments than the router's
      top-k can produce (a corrupt pack, or weights folded in twice);
    * the operand must be a matrix pack (value-carrying slabs), never a
      graph adjacency.
    """
    base = plan_spmm_sell(
        meta, k=k, x_dtype=x_dtype, w_block=w_block, k_block=k_block,
        vmem_budget=vmem_budget)
    violations = list(base.violations)
    if meta.kind != "matrix":
        violations.append(
            f"routing operand kind {meta.kind!r} != 'matrix' (the dispatch "
            "SpMM needs value-carrying slabs, not an adjacency pack)")
    if top_k < 1:
        violations.append(f"top_k must be >= 1, got {top_k}")
    w_max = pow2_ceil(max(int(top_k), 1))
    for i, w in enumerate(meta.widths):
        if w > w_max:
            violations.append(
                f"bucket {i} width {w} exceeds pow2_ceil(top_k={top_k})="
                f"{w_max}: a routing row carries at most top_k entries")
    return dataclasses.replace(
        base, kernel="moe_dispatch", violations=tuple(violations))


def plan_spmm_sell_sharded(
    meta: SlabMeta,
    k: int = 1,
    x_dtype: str | None = None,
    *,
    n_devices: int = 1,
    w_block: int = 8,
    k_block: int = 8,
    window_cols: int | None = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan the row-sharded ``spmm_sell_sharded`` launch across devices.

    Per device the launch is the resident bucket schedule of
    :func:`plan_spmm_sell` on roughly ``1/n_devices`` of the slices, with
    one decisive difference: the RHS block a device keeps VMEM-resident is
    its ``window_cols``-wide boundary-column gather, not the full
    ``n_cols`` — row partitioning shrinks the X term, which is exactly why
    an operand the single-device resident plan rejects can be *accepted*
    sharded.  The plan also prices the collective volume as a zero-VMEM
    pseudo-block: the replicated X broadcast each device reads
    (``window_cols x k_pad``) and the disjoint output rows it contributes
    to the host concatenation (``~n_rows/n_devices x k_pad``) — the wire
    budget a scaling sweep should watch, not a VMEM contract.
    """
    violations: list[str] = []
    nd = int(n_devices)
    if nd < 1:
        violations.append(f"n_devices must be >= 1, got {n_devices}")
        nd = 1
    if not is_pow2(w_block):
        violations.append(f"w_block {w_block} is not a power of two")
    if not is_pow2(k_block):
        violations.append(f"k_block {k_block} is not a power of two")
    if k < 1:
        violations.append(f"RHS stack must have k >= 1 columns, got {k}")
    win = int(window_cols) if window_cols is not None else meta.n_cols
    if win < 1 or win > max(meta.n_cols, 1):
        violations.append(
            f"window_cols {win} outside [1, n_cols={meta.n_cols}]")
    _shared_slab_contracts(meta, violations)
    val_dtype = meta.val_dtype or "float64"
    vb = _dtype_bytes(val_dtype)
    if x_dtype is not None:
        if not np.issubdtype(np.dtype(x_dtype), np.floating):
            violations.append(f"RHS dtype {x_dtype} is not floating")
        elif meta.val_dtype is not None and x_dtype != meta.val_dtype:
            violations.append(
                f"RHS dtype {x_dtype} != slab value dtype {meta.val_dtype}")
    k_tile = k_tile_for(k, k_block)
    k_pad = k_tile * math.ceil(max(k, 1) / k_tile)
    xb = _dtype_bytes(x_dtype) if x_dtype is not None else vb
    blocks = []
    for i, (s, w) in enumerate(zip(meta.n_slices, meta.widths)):
        s_dev = math.ceil(max(s, 1) / nd)        # slices on the busiest shard
        w_eff = w_tile_for(w, w_block)
        w_pad = w_eff * math.ceil(w / w_eff)
        grid = (s_dev, k_pad // k_tile, w_pad // w_eff)
        footprint = (
            2 * w_eff * meta.c * (vb + _IDX_BYTES)   # double-buffered slab tile
            + 2 * _lane_table_bytes(win, k_tile, meta.c, xb)  # RHS window
            + 2 * meta.c * k_tile * vb               # pipelined output pair
        )
        if footprint > vmem_budget:
            violations.append(
                f"bucket {i} (W={w}): per-device footprint {footprint} B "
                f"exceeds VMEM budget {vmem_budget} B (n_devices={nd}, "
                f"window_cols={win}, w_block={w_block}, k_block={k_block})")
        blocks.append(BlockPlan(
            label=f"bucket{i}[W={w}]/dev",
            grid=grid,
            blocks=(
                ("cols", (1, w_eff, meta.c), meta.idx_dtype),
                ("vals", (1, w_eff, meta.c), val_dtype),
                ("x_window", (k_tile, win), x_dtype or val_dtype),
                ("y", (1, k_tile, meta.c), val_dtype),
            ),
            vmem_bytes=footprint,
        ))
    rows_dev = math.ceil(max(meta.n_rows, 1) / nd)
    blocks.append(BlockPlan(
        label="collectives",
        grid=(nd,),
        blocks=(
            ("x_broadcast", (win, k_pad), x_dtype or val_dtype),
            ("y_gather", (rows_dev, k_pad), val_dtype),
        ),
        vmem_bytes=0,                            # wire volume, not VMEM
    ))
    return LaunchPlan(
        kernel="spmm_sell_sharded", operand=meta.describe(), dtype=val_dtype,
        vmem_budget=int(vmem_budget), blocks=tuple(blocks),
        violations=tuple(violations),
    )


def plan_spmm_sell_stream(
    meta: SlabMeta,
    k: int = 1,
    x_dtype: str | None = None,
    *,
    w_block: int = 8,
    k_block: int = 8,
    col_tile: int = 1 << 16,
    row_tile: int = 8,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan ``spmm_sell_stream`` — the out-of-VMEM schedule for these slabs.

    Nothing is VMEM-resident: slabs, X and Y stay in HBM (``ANY`` memory)
    and the kernel owns its buffers as explicit scratch, so the per-cell
    footprint is exactly the scratch it allocates — double-buffered
    (w_eff, C) cols+vals tile *pairs*, a double-buffered
    (col_tile, k_tile) RHS tile pair, and one (row_tile, C, k_tile)
    accumulator — independent of ``n_cols`` and ``n_rows``.  The wrapper
    coerces ``col_tile`` to a power of two clamped at ``pow2_ceil(n_cols)``
    and clamps ``row_tile`` per bucket at its slice count; the plan mirrors
    both, so a giant operand the resident plan rejects produces a *valid*
    streaming plan here (the rejection -> acceptance pair the analysis CLI
    self-check proves).
    """
    violations: list[str] = []
    if not is_pow2(w_block):
        violations.append(f"w_block {w_block} is not a power of two")
    if not is_pow2(k_block):
        violations.append(f"k_block {k_block} is not a power of two")
    if col_tile < 1:
        violations.append(f"col_tile must be >= 1, got {col_tile}")
    if row_tile < 1:
        violations.append(f"row_tile must be >= 1, got {row_tile}")
    if k < 1:
        violations.append(f"RHS stack must have k >= 1 columns, got {k}")
    _shared_slab_contracts(meta, violations)
    val_dtype = meta.val_dtype or "float64"
    vb = _dtype_bytes(val_dtype)
    if x_dtype is not None:
        if not np.issubdtype(np.dtype(x_dtype), np.floating):
            violations.append(f"RHS dtype {x_dtype} is not floating")
        elif meta.val_dtype is not None and x_dtype != meta.val_dtype:
            violations.append(
                f"RHS dtype {x_dtype} != slab value dtype {meta.val_dtype}")
    k_tile = k_tile_for(k, k_block)
    k_pad = k_tile * math.ceil(max(k, 1) / k_tile)
    xb = _dtype_bytes(x_dtype) if x_dtype is not None else vb
    ct = max(min(pow2_ceil(max(int(col_tile), 1)),
                 pow2_ceil(max(meta.n_cols, 1))),
             min(meta.c, _LANES))             # at least one table chunk
    blocks = []
    for i, (s, w) in enumerate(zip(meta.n_slices, meta.widths)):
        w_eff = w_tile_for(w, w_block)
        w_pad = w_eff * math.ceil(w / w_eff)
        rt = min(max(int(row_tile), 1), max(s, 1))
        s_pad = rt * math.ceil(max(s, 1) / rt)
        grid = (s_pad // rt, k_pad // k_tile)
        footprint = (
            2 * w_eff * meta.c * (vb + _IDX_BYTES)   # slab tile pairs
            + 2 * _lane_table_bytes(ct, k_tile, meta.c, xb)  # RHS tile pair
            + rt * meta.c * k_tile * vb              # accumulator
        )
        if footprint > vmem_budget:
            violations.append(
                f"bucket {i} (W={w}): per-cell scratch {footprint} B "
                f"exceeds VMEM budget {vmem_budget} B "
                f"(w_block={w_block}, k_block={k_block}, col_tile={ct}, "
                f"row_tile={rt})")
        blocks.append(BlockPlan(
            label=f"bucket{i}[W={w}]",
            grid=grid,
            blocks=(
                ("cols_buf", (2, w_eff, meta.c), meta.idx_dtype),
                ("vals_buf", (2, w_eff, meta.c), val_dtype),
                ("x_buf", (2, k_tile, ct), x_dtype or val_dtype),
                ("y_acc", (rt, k_tile, meta.c), val_dtype),
            ),
            vmem_bytes=footprint,
        ))
    return LaunchPlan(
        kernel="spmm_sell_stream", operand=meta.describe(), dtype=val_dtype,
        vmem_budget=int(vmem_budget), blocks=tuple(blocks),
        violations=tuple(violations),
    )


def _plan_node_step(
    kernel: str,
    meta: SlabMeta,
    k: int,
    state_dtype: str,
    vmem_budget: int,
) -> LaunchPlan:
    """Shared plan for the ``bucketed_node_step`` drivers (BFS, PageRank):
    per bucket one (1, W, C) adjacency tile, the whole (n + 1, k) state as
    a lane table, and a (1, k, C) output tile — each double-buffered by
    the pipeline.  BFS's level sits in SMEM; PageRank's damping is
    applied after the scatter."""
    violations: list[str] = []
    if k < 1:
        violations.append(f"state stack must have k >= 1 columns, got {k}")
    _shared_slab_contracts(meta, violations)
    sb = _dtype_bytes(state_dtype)
    table = _lane_table_bytes(meta.n_rows + 1, max(k, 1), meta.c, sb)
    blocks = []
    for i, (s, w) in enumerate(zip(meta.n_slices, meta.widths)):
        footprint = (
            2 * meta.c * w * _IDX_BYTES              # adjacency tile pair
            + 2 * table                              # state table pair
            + 2 * meta.c * max(k, 1) * sb            # output tile pair
        )
        if footprint > vmem_budget:
            violations.append(
                f"bucket {i} (W={w}): per-cell footprint {footprint} B "
                f"exceeds VMEM budget {vmem_budget} B (k={k})")
        blocks.append(BlockPlan(
            label=f"bucket{i}[W={w}]",
            grid=(s,),
            blocks=(
                ("adj", (1, w, meta.c), meta.idx_dtype),
                ("state", (max(k, 1), meta.n_rows + 1), state_dtype),
                ("out", (1, max(k, 1), meta.c), state_dtype),
            ),
            vmem_bytes=footprint,
        ))
    return LaunchPlan(
        kernel=kernel, operand=meta.describe(), dtype=state_dtype,
        vmem_budget=int(vmem_budget), blocks=tuple(blocks),
        violations=tuple(violations),
    )


def plan_bfs_sell(
    meta: SlabMeta,
    k: int = 1,
    *,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan one ``bfs_step_sell`` level for k stacked sources: the state
    is the (n + 1, k) int32 distance columns."""
    return _plan_node_step("bfs_sell", meta, k, "int32", vmem_budget)


def plan_pagerank_sell(
    meta: SlabMeta,
    k: int = 1,
    dtype: str = "float64",
    *,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan one ``pagerank_step_sell`` power step for k stacked configs:
    the state is the (n + 1, k) contribution columns in the rank dtype."""
    return _plan_node_step("pagerank_sell", meta, k, dtype, vmem_budget)


def plan_fft_stockham(
    n: int,
    batch: int = 1,
    *,
    b_block: int = 8,
    dtype: str = "float64",
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> LaunchPlan:
    """Plan ``fft_stockham`` for a (batch, n) split-plane signal block.

    One grid cell holds the re/im input and output blocks (each
    double-buffered), the two-deep re/im ping-pong scratch, and the
    (stages, n/2) re/im twiddle tables (double-buffered): twelve signal
    planes of (n/lanes, b_block, lanes) chunks plus four twiddle planes,
    each padded to whole (8, 128) vreg tiles.
    """
    violations: list[str] = []
    if n < 2 or not is_pow2(n):
        violations.append(f"fft length {n} is not a power of two >= 2")
    if b_block < 1:
        violations.append(f"b_block must be >= 1, got {b_block}")
    if batch < 1:
        violations.append(f"batch must be >= 1, got {batch}")
    b = _dtype_bytes(dtype)
    bb = max(int(b_block), 1)
    stages = int(math.log2(n)) if n >= 2 and is_pow2(n) else 0
    lanes = max(min(_LANES, n // 2), 1)
    chunks = max(n // lanes, 1)
    plane = chunks * SUBLANES * math.ceil(bb / SUBLANES) * _LANES * b
    twiddle = stages * SUBLANES * math.ceil(
        max(chunks // 2, 1) / SUBLANES) * _LANES * b
    footprint = 12 * plane + 4 * twiddle
    if footprint > vmem_budget:
        violations.append(
            f"per-cell footprint {footprint} B exceeds VMEM budget "
            f"{vmem_budget} B (n={n}, b_block={b_block})")
    grid = (math.ceil(max(batch, 1) / bb),)
    plan = LaunchPlan(
        kernel="fft_stockham", operand=f"fft n={n} batch={batch}",
        dtype=dtype, vmem_budget=int(vmem_budget),
        blocks=(BlockPlan(
            label="stockham",
            grid=grid,
            blocks=(
                ("re", (bb, n), dtype), ("im", (bb, n), dtype),
                ("wre", (stages, n // 2), dtype),
                ("wim", (stages, n // 2), dtype),
                ("out_re", (bb, n), dtype), ("out_im", (bb, n), dtype),
            ),
            vmem_bytes=footprint,
        ),),
        violations=tuple(violations),
    )
    return plan


@dataclasses.dataclass(frozen=True)
class FtPlan(LaunchPlan):
    """:class:`LaunchPlan` of one NPB FT step, with what the program takes
    from it: the signal block of the x, y and z passes, the widest group of
    steps one launch may stack (a power of two, 0 when not even one fits),
    and the HBM the plan's group holds."""

    b_blocks: tuple[int, int, int] = (1, 1, 1)
    max_group: int = 0
    hbm_bytes: int = 0
    hbm_budget: int = HBM_BUDGET_BYTES


def _ft_b_block(n: int, batch: int, dtype: str, vmem_budget: int) -> int:
    """Widest power-of-two block of ``batch`` length-``n`` signals, at most
    :data:`FT_B_BLOCK_MAX`, that divides the batch and fits VMEM."""
    bb = 1
    while (bb * 2 <= min(batch, FT_B_BLOCK_MAX) and batch % (bb * 2) == 0
           and plan_fft_stockham(n, batch, b_block=bb * 2, dtype=dtype,
                                 vmem_budget=vmem_budget).ok):
        bb *= 2
    return bb


def plan_ft(
    shape: tuple[int, int, int],
    k: int = 1,
    *,
    dtype: str = "float32",
    hbm_budget: int = HBM_BUDGET_BYTES,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> FtPlan:
    """Plan one NPB FT step (:func:`repro.kernels.fft.ft_step`) of ``k``
    stacked time steps on an (nx, ny, nz) field: one ``fft_stockham`` pass
    per axis over k * N / n signals of that axis's length n, each at the
    widest block that divides one field's N / n and fits VMEM (so every
    group width of the field runs the same blocks); and the chip's HBM,
    which holds the resident spectrum (two planes of the field) and
    :data:`FT_TRANSIENT_FIELDS` fields per stacked step."""
    violations: list[str] = []
    shape = tuple(int(n) for n in shape)
    if len(shape) != 3:
        violations.append(f"ft field must be 3-D, got shape {shape}")
        shape = (shape + (1, 1, 1))[:3]
    if k < 1:
        violations.append(f"group width must be >= 1, got {k}")
    total = math.prod(shape)
    field = 2 * total * _dtype_bytes(dtype)
    b_blocks, blocks = [], []
    for axis, n in enumerate(shape):
        # a block that divides one field's lines divides a group's too
        lines = total // max(n, 1)
        bb = _ft_b_block(n, lines, dtype, vmem_budget)
        sub = plan_fft_stockham(n, max(int(k), 1) * lines, b_block=bb,
                                dtype=dtype, vmem_budget=vmem_budget)
        violations.extend(f"axis {axis}: {v}" for v in sub.violations)
        b_blocks.append(bb)
        blocks.append(dataclasses.replace(
            sub.blocks[0], label=f"axis{axis}[n={n}]"))
    hbm = field + max(int(k), 1) * FT_TRANSIENT_FIELDS * field
    if hbm > hbm_budget:
        violations.append(
            f"resident spectrum {field} B + {k} x {FT_TRANSIENT_FIELDS} "
            f"transient fields of {field} B = {hbm} B exceeds HBM budget "
            f"{hbm_budget} B (field {shape})")
    room = (hbm_budget - field) // (FT_TRANSIENT_FIELDS * field)
    return FtPlan(
        kernel="ft_step", operand=f"ft {shape[0]}x{shape[1]}x{shape[2]} "
        f"k={k}", dtype=dtype, vmem_budget=int(vmem_budget),
        blocks=tuple(blocks), violations=tuple(violations),
        b_blocks=tuple(b_blocks),
        max_group=1 << (room.bit_length() - 1) if room >= 1 else 0,
        hbm_bytes=int(hbm), hbm_budget=int(hbm_budget))
