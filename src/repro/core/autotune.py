"""SDV-driven block-shape selection — the paper's co-design loop as a feature.

The paper's methodology is: expose VL / latency / bandwidth as knobs, measure,
and feed the result back into hardware-software co-design.  On TPU the
software-side knob is the Pallas block shape.  This module closes the loop in
software: given a kernel's traffic builder and the TPU machine constants, it
picks the block width ("vl") that minimizes SDV-modeled cycles subject to the
VMEM budget — i.e. it answers "how long should the vectors be on *this*
memory system" per kernel, which is exactly the question the paper's FPGA
sweeps answer per kernel on theirs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import numpy as np

from repro.core.sdv import MachineParams, SDVMachine, Trace, tpu_v5e_machine
from repro.core.traffic import SpMVProblem, spmv_trace
from repro.core.vconfig import VectorConfig

#: TPU v5e VMEM budget a single kernel invocation should stay under
#: (half of VMEM, leaving room for double buffering).
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
#: MXU/VPU-friendly lane multiple.
LANE = 128
SUBLANE = 8


@dataclasses.dataclass(frozen=True)
class TuneResult:
    vl: int
    cycles: float
    table: tuple[tuple[int, float], ...]   # (vl, modeled cycles) per candidate

    def speedup_over_worst(self) -> float:
        worst = max(c for _, c in self.table)
        return worst / self.cycles


def candidate_vls(
    max_vl: int = 4096,
    min_vl: int = SUBLANE,
    multiple: int = SUBLANE,
) -> list[int]:
    """Power-of-two candidates aligned to the TPU sublane multiple."""
    out = []
    v = min_vl
    while v <= max_vl:
        if v % multiple == 0:
            out.append(v)
        v *= 2
    return out


def vmem_footprint(bytes_per_vl_row: float, vl: int) -> float:
    """Working-set bytes a block of width ``vl`` pins in VMEM."""
    return bytes_per_vl_row * vl


def tune_vl(
    trace_builder: Callable[[VectorConfig], Trace],
    machine: MachineParams | None = None,
    candidates: Sequence[int] | None = None,
    bytes_per_vl_row: float = 0.0,
    vmem_budget: float = VMEM_BUDGET_BYTES,
) -> TuneResult:
    """Pick the block width minimizing modeled cycles under the VMEM budget.

    ``bytes_per_vl_row`` lets callers express the VMEM constraint: a block of
    width vl must fit ``bytes_per_vl_row * vl`` bytes of VMEM (0 = no bound).
    """
    machine = machine or tpu_v5e_machine()
    cands = list(candidates) if candidates is not None else candidate_vls()
    sdv = SDVMachine(machine)
    rows: list[tuple[int, float]] = []
    for vl in cands:
        if bytes_per_vl_row and vmem_footprint(bytes_per_vl_row, vl) > vmem_budget:
            continue
        cycles = sdv.run(trace_builder(VectorConfig(vl=vl, lanes=machine.lanes))).cycles
        rows.append((vl, cycles))
    if not rows:
        raise ValueError("no candidate vl fits the VMEM budget")
    best_vl, best_cycles = min(rows, key=lambda r: r[1])
    return TuneResult(vl=best_vl, cycles=best_cycles, table=tuple(rows))


# ---------------------------------------------------------------------------
# SELL-C-sigma layout co-selection: (C, sigma, w_block) against the
# *measured* per-bucket pad_factor of the actual row-length distribution.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SellTuneResult:
    c: int
    sigma: int
    w_block: int
    cycles: float
    pad_factor: float
    #: (c, sigma, measured pad_factor, modeled cycles) per candidate
    table: tuple[tuple[int, int, float, float], ...]
    #: RHS tile of the batched SpMM core (multi-RHS requests per grid cell);
    #: defaulted for tune entries persisted before the k axis existed
    k_block: int = 8
    #: streaming-schedule tiles (`spmm_sell_stream`): X column tile and the
    #: slab row tile per grid cell; defaulted for tune entries persisted
    #: before the out-of-VMEM path existed
    col_tile: int = 1 << 16
    row_tile: int = 8

    def speedup_over_worst(self) -> float:
        worst = max(cy for *_, cy in self.table)
        return worst / self.cycles


def measured_pad_factor(
    row_lengths: np.ndarray, c: int, sigma: int, pow2_buckets: bool = True
) -> float:
    """padded_nnz / nnz of the SELL-C-sigma layout on *these* row lengths.

    Computed with the packer's own helpers (sigma-window sort, per-C-slice
    max width, power-of-two bucket rounding) without building the layout,
    so the tuner can sweep (C, sigma) in microseconds and can never
    disagree with what :func:`repro.sparse.formats.csr_to_sell_slabs`
    actually builds.
    """
    from repro.sparse.formats import next_pow2, sigma_sort_order, slice_widths

    n = len(row_lengths)
    if n == 0:
        return 1.0
    lengths = np.asarray(row_lengths, np.int64)
    order = sigma_sort_order(lengths, sigma)
    widths = slice_widths(lengths, order, c)
    if pow2_buckets:
        widths = next_pow2(widths)
    return float(widths.sum() * c) / max(int(lengths.sum()), 1)


def pick_w_block(
    c: int,
    max_width: int,
    elem_bytes: int = 12,                      # f64 value + i32 col index
    vmem_budget: float = VMEM_BUDGET_BYTES / 8,
    multiple: int = SUBLANE,
) -> int:
    """Largest sublane-aligned W tile whose double-buffered slab fits VMEM."""
    from repro.sparse.formats import pow2_ceil

    w = multiple
    while (
        w * 2 <= max_width
        and 2 * (w * 2) * c * elem_bytes <= vmem_budget
    ):
        w *= 2
    # Never exceed the padded slab width, but stay a power of two so the
    # (w_block, C) tiles keep their sublane alignment.
    return max(1, min(w, pow2_ceil(max_width)))


def lane_waste(c: int) -> float:
    """VMEM bytes per useful byte of the RHS gather table for slice height
    ``c``: the (k, R, lanes) table holds ``min(c, 128)`` entries per row
    and every row is padded to the 128 lanes of a vreg."""
    return LANE / min(max(int(c), 1), LANE)


def pick_k_block(
    c: int,
    n_cols: int,
    vmem_budget: float = VMEM_BUDGET_BYTES,
    k_max: int = 32,
    w_block: int = SUBLANE,
) -> int:
    """Largest power-of-two RHS tile whose resident state fits the budget.

    The k axis of the batched SpMM core amortizes the slab traffic across
    right-hand sides, so wider is strictly better until the VMEM-resident
    X block, the (C, k) output tile, and the double-buffered slab tile
    stop fitting together — the co-tune is the greedy fill, capped at
    ``k_max`` (beyond the cap the amortization has flattened and
    compile-time variants multiply for no win).  Pallas pipelines every
    BlockSpec operand through a *pair* of VMEM buffers, so the honest
    per-column price of X is 16 B (2 x f64), not 8 — same for the output
    tile; this is the model :func:`repro.analysis.preflight.plan_spmm_sell`
    enforces, lanes padded to 128 (:func:`lane_waste`).  Pass the
    co-selected ``w_block`` so the slab tile term prices the tile that will
    actually run, keeping the (w_block, k_block) pair JOINTLY inside the
    budget rather than each fitting alone.
    """
    slab_tile = 2 * w_block * c * 12.0        # double-buffered cols+vals
    x_col = 16.0 * lane_waste(c)
    k = 1
    while (
        k * 2 <= k_max
        and x_col * (n_cols + c) * (k * 2) + slab_tile <= vmem_budget
    ):
        k *= 2
    return k


def pick_stream_tiles(
    c: int,
    w_block: int = SUBLANE,
    k_block: int = 8,
    vmem_budget: float = VMEM_BUDGET_BYTES,
    col_tile_max: int = 1 << 20,
    row_tile_max: int = 64,
) -> tuple[int, int]:
    """Greedy (col_tile, row_tile) fill for the streaming SpMM schedule.

    The out-of-VMEM path (:func:`repro.kernels.sell_core.spmm_sell_stream`)
    keeps nothing resident but scratch: a double-buffered
    (col_tile, k_tile) X tile (16 B/column at f64), a double-buffered
    (w_block, C) slab tile, and a (row_tile, C, k_tile) accumulator.
    The column tile dominates X traffic amortization (each tile is reused
    across ``row_tile`` slices), so it is grown first to half the budget;
    the row tile then fills what remains.  Both stay powers of two so the
    host-side padding in the wrapper is a single static pad.  The k tile
    is priced at its widest (:func:`repro.sparse.formats.widest_k_tile`):
    a group of 8 requests runs as one 8-column tile whatever ``k_block``.
    """
    from repro.sparse.formats import widest_k_tile

    kt = widest_k_tile(k_block)
    slab_tile = 2 * w_block * c * 12.0
    # double-buffered X bytes per column, table lanes padded to 128
    x_col = 16.0 * kt * lane_waste(c)
    acc_row = 8.0 * c * kt                    # accumulator bytes per slice
    ct = LANE
    while (
        ct * 2 <= col_tile_max
        and x_col * (ct * 2) + slab_tile + acc_row <= vmem_budget / 2
    ):
        ct *= 2
    rt = 1
    while (
        rt * 2 <= row_tile_max
        and x_col * ct + slab_tile + acc_row * (rt * 2) <= vmem_budget
    ):
        rt *= 2
    return ct, rt


def tune_sell_layout(
    row_lengths: np.ndarray,
    n_cols: int | None = None,
    machine: MachineParams | None = None,
    candidates_c: Sequence[int] | None = None,
    sigma_factors: Sequence[int] = (1, 4, 8, 32),
    vmem_budget: float = VMEM_BUDGET_BYTES,
    cache=None,
    cache_key: str | None = None,
    n_devices: int = 1,
) -> SellTuneResult:
    """Co-select (C, sigma, w_block) for the SELL SpMV kernel.

    For every candidate the tuner *measures* the pad_factor the packer would
    produce on the given row-length distribution, feeds it into the SpMV
    transaction trace, and scores SDV-modeled cycles — the paper's co-design
    loop driving a real layout choice instead of only printing a table.

    ``cache``/``cache_key`` plug in a persistent tune store (duck-typed
    ``get_sell``/``put_sell``, e.g. :class:`repro.service.tunecache.TuneCache`):
    the cache is consulted *before* any pad factor is measured, so a warm
    entry makes this call free, and a miss records its result for the next
    process.

    ``n_devices > 1`` tunes for the row-sharded launch: the layout each
    device executes is packed from its own row slice, so the tuner scores
    the *busiest shard* (largest nnz under the same balanced partition
    :func:`repro.sparse.formats.shard_row_ranges` produces) — that shard
    sets the critical path of the SPMD launch.  Callers must key the cache
    with the matching device count (``TuneCache.sell_key(n_devices=...)``)
    so sharded and single-device tunes never alias.
    """
    if cache is not None and cache_key is not None:
        hit = cache.get_sell(cache_key)
        if hit is not None:
            return hit
    machine = machine or tpu_v5e_machine()
    lengths = np.asarray(row_lengths, np.int64)
    if int(n_devices) > 1 and len(lengths):
        from repro.sparse.formats import shard_row_ranges

        ranges = shard_row_ranges(lengths, int(n_devices))
        lo, hi = max(
            ranges, key=lambda r: int(lengths[r[0]:r[1]].sum()))
        lengths = lengths[lo:hi]
    n_rows = len(lengths)
    nnz = int(lengths.sum())
    n_cols = int(n_cols if n_cols is not None else n_rows)
    cands = list(candidates_c) if candidates_c is not None else [
        v for v in candidate_vls(max_vl=1024) if v <= max(n_rows, SUBLANE)
    ] or [SUBLANE]
    # Honor the machine's declared ISA cap: a short-vector machine
    # (MachineParams.max_vl, e.g. the sve/avx512-like presets) must never
    # be handed a C it cannot execute.
    if machine.max_vl > 0:
        cands = [c for c in cands if machine.supports_vl(c)] or [machine.max_vl]
    sdv = SDVMachine(machine)

    def score(cands_c) -> list[tuple[int, int, float, float]]:
        out: list[tuple[int, int, float, float]] = []
        for c in cands_c:
            seen: set[int] = set()
            for f in sigma_factors:
                sigma = min(max(f * c, c), max(n_rows, 1))
                if sigma in seen:
                    continue
                seen.add(sigma)
                pf = measured_pad_factor(lengths, c, sigma)
                prob = SpMVProblem(
                    n_rows=n_rows, n_cols=n_cols, nnz=nnz, pad_factor=pf)
                trace = spmv_trace(prob, VectorConfig(vl=c, lanes=machine.lanes))
                out.append((c, sigma, pf, sdv.run(trace).cycles))
        return out

    # On the resident schedule the x block stays pinned for every candidate
    # (and Pallas double-buffers it: 16 B/column at f64); the slab tile is
    # double-buffered (cols i32 + vals f64 = 12 B/entry) at the smallest
    # usable W block.  Candidates that cannot afford that are only viable
    # on the streaming schedule, where X residency is a (col_tile, k_tile)
    # slice the tuner controls — so when *no* candidate fits resident, the
    # operand is stream-only and (C, sigma) is scored without the filter.
    def x_resident(c: int) -> float:
        return 16.0 * n_cols * lane_waste(c)

    rows = score(
        c for c in cands
        if x_resident(c) + 2 * SUBLANE * c * 12.0 <= vmem_budget
    )
    stream_only = not rows
    if stream_only:
        rows = score(cands)
    if not rows:
        raise ValueError("no (C, sigma) candidate fits the VMEM budget")
    best = min(rows, key=lambda r: r[3])
    max_w = int(lengths.max()) if n_rows else 1
    # Resident: the tile budget is whatever the x-resident vector leaves
    # over, so the returned triple is consistent with the candidate filter
    # above.  Stream-only: the slab tile competes with the streamed X tile
    # instead, which pick_w_block's default slab share models.  The RHS
    # tile is then priced against the slab tile w_block actually claims,
    # so (w_block, k_block) fit the budget together, not just each alone.
    w_block = pick_w_block(
        best[0], max(max_w, 1),
        vmem_budget=(
            vmem_budget / 8 if stream_only
            else max(vmem_budget - x_resident(best[0]),
                     2 * SUBLANE * best[0] * 12.0)
        ),
    )
    k_block = pick_k_block(
        best[0],
        # Stream-only operands price X at one column tile, not n_cols.
        min(n_cols, pick_stream_tiles(best[0], w_block)[0]) if stream_only
        else n_cols,
        vmem_budget=vmem_budget,
        w_block=w_block,
    )
    col_tile, row_tile = pick_stream_tiles(
        best[0], w_block, k_block, vmem_budget=vmem_budget)
    result = SellTuneResult(
        c=best[0],
        sigma=best[1],
        w_block=w_block,
        cycles=best[3],
        pad_factor=best[2],
        table=tuple(rows),
        k_block=k_block,
        col_tile=col_tile,
        row_tile=row_tile,
    )
    if cache is not None and cache_key is not None:
        cache.put_sell(cache_key, result)
    return result


def align_block(dim: int, multiple: int = LANE) -> int:
    """Round a block dimension up to a hardware-aligned multiple."""
    return multiple * math.ceil(dim / multiple)


def pick_2d_block(
    rows: int,
    cols: int,
    elem_bytes: int = 4,
    vmem_budget: float = VMEM_BUDGET_BYTES / 4,
    row_multiple: int = SUBLANE,
    col_multiple: int = LANE,
) -> tuple[int, int]:
    """Largest (row, col) tile with hardware-aligned dims fitting the budget.

    Greedy: prefer widening columns (lane dimension, burst-friendly = the
    paper's 'longer vectors first') before adding rows.
    """
    c = min(align_block(cols, col_multiple), cols if cols % col_multiple == 0
            else align_block(cols, col_multiple))
    c = min(c, 4096)
    while c > col_multiple and c * row_multiple * elem_bytes > vmem_budget:
        c //= 2
    r = row_multiple
    while r * 2 <= rows and c * r * 2 * elem_bytes <= vmem_budget:
        r *= 2
    return max(r, row_multiple), max(c, col_multiple)
