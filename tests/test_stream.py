"""Out-of-VMEM streaming SpMM: double-buffered tile pipeline.

The load-bearing guarantees: (1) ``spmm_sell_stream`` matches the resident
``spmm_sell`` schedule AND the dense reference over the whole
(C, sigma, w_block, k_block, col_tile) grid at 1e-10 — including prime
column counts, column tiles that do not divide n_cols, k = 1, empty rows
and the all-empty matrix; (2) the resident preflight prices the pipelined
X/Y buffer *pairs* (2x), so a ~600k-column operand the old 1x model waved
through is rejected and lands on the streaming schedule; (3) the
rejection→acceptance pair holds statically: a million-row operand
``plan_spmm_sell`` rejects, ``plan_spmm_sell_stream`` accepts with an
O(tiles) footprint; (4) ``ops.spmm``'s ``mode="auto"`` dispatch streams
exactly the operands the resident plan rejects; (5) a giant rectangular
operand registers as ``mode="stream"`` and serves end-to-end through
KernelService, counted by ``stats["streamed_launches"]``; (6) the single
k-padding policy: powers of two are fixpoints of ``padded_k``, so the
service's pow2-padded stacks are never re-padded by the core.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis.launchplan import LaunchPlanError
from repro.analysis.preflight import (
    SlabMeta,
    plan_spmm_sell,
    plan_spmm_sell_stream,
)
from repro.core.autotune import (
    VMEM_BUDGET_BYTES,
    pick_stream_tiles,
    tune_sell_layout,
)
from repro.kernels import ops, sell_core
from repro.service import KernelRegistry, KernelService
from repro.sparse import formats as F

RNG = np.random.default_rng(17)


def _slab_args(slabs):
    return (
        tuple(jnp.asarray(c) for c in slabs.bucket_cols),
        tuple(jnp.asarray(v) for v in slabs.bucket_vals),
        tuple(jnp.asarray(r) for r in slabs.bucket_rows),
    )


# ---------------------------------------------------------------------------
# Streaming vs resident vs dense over the tile grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c,sigma_factor,w_block", [(4, 1, 4), (16, 4, 8),
                                                    (32, 8, 8)])
@pytest.mark.parametrize("k,k_block,col_tile", [(1, 1, 32), (3, 2, 64),
                                                (5, 8, 16), (8, 4, 128)])
def test_stream_matches_resident_and_dense_grid(c, sigma_factor, w_block,
                                                k, k_block, col_tile):
    # 101 columns is prime: no col_tile in the grid divides it, so every
    # cell exercises the padded final X tile and its column mask.
    csr = F.random_csr(75, 101, 5.0, seed=c * 100 + k, skew=1.0)
    dense = F.csr_to_dense(csr)
    x = np.random.default_rng(k).standard_normal((101, k))
    slabs = F.csr_to_sell_slabs(csr, c=c, sigma=sigma_factor * c)
    args = _slab_args(slabs)
    resident = np.asarray(sell_core.spmm_sell(
        *args, jnp.asarray(x),
        n_rows=csr.n_rows, w_block=w_block, k_block=k_block, interpret=True,
    ))
    streamed = np.asarray(sell_core.spmm_sell_stream(
        *args, jnp.asarray(x),
        n_rows=csr.n_rows, w_block=w_block, k_block=k_block,
        col_tile=col_tile, row_tile=2, interpret=True,
    ))
    assert streamed.shape == (csr.n_rows, k)
    np.testing.assert_allclose(streamed, dense @ x, rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(streamed, resident, rtol=1e-10, atol=1e-10)


def test_stream_prime_cols_and_non_pow2_row_tile():
    """61 columns, col_tile 16 (4 ragged tiles), row_tile 3 (does not
    divide the slice count): every padding path at once."""
    csr = F.random_csr(64, 61, 4.0, seed=5, skew=1.1)
    dense = F.csr_to_dense(csr)
    x = RNG.standard_normal((61, 3))
    slabs = F.csr_to_sell_slabs(csr, c=8, sigma=32)
    got = np.asarray(sell_core.spmm_sell_stream(
        *_slab_args(slabs), jnp.asarray(x),
        n_rows=64, w_block=4, k_block=2, col_tile=16, row_tile=3,
        interpret=True,
    ))
    np.testing.assert_allclose(got, dense @ x, rtol=1e-10, atol=1e-10)


def test_stream_empty_rows_and_all_empty():
    dense = np.zeros((6, 5))
    dense[0, 1] = 2.0
    dense[3, [0, 2, 4]] = [1.0, -1.5, 3.0]   # rows 1,2,4,5 empty
    x = RNG.standard_normal((5, 3))
    for mat in (dense, np.zeros((6, 5))):
        csr = F.csr_from_dense(mat)
        slabs = F.csr_to_sell_slabs(csr, c=4, sigma=8)
        got = np.asarray(sell_core.spmm_sell_stream(
            *_slab_args(slabs), jnp.asarray(x),
            n_rows=6, w_block=8, k_block=2, col_tile=4, row_tile=2,
            interpret=True,
        ))
        np.testing.assert_allclose(got, mat @ x, atol=1e-10)


# ---------------------------------------------------------------------------
# Preflight: honest resident footprint + rejection→acceptance pair
# ---------------------------------------------------------------------------


def _meta(n_rows, n_cols, c=8, width=8, n_slices=4):
    return SlabMeta(kind="matrix", c=c, widths=(width,),
                    n_slices=(n_slices,), n_rows=n_rows, n_cols=n_cols,
                    val_dtype="float64", idx_dtype="int32")


def test_resident_plan_prices_pipelined_x_pair():
    """Regression for the X under-report: Pallas double-buffers every
    BlockSpec operand, so the resident X stack costs 2x.  At 600k columns
    and k_tile 8 the 1x model (38.4 MB) fit the 64 MB budget; the honest
    2x model (76.8 MB) must reject."""
    meta = _meta(32, 600_000)
    plan = plan_spmm_sell(meta, k=8, x_dtype="float64")
    assert not plan.ok
    one_x_model = 8.0 * meta.n_cols * 8       # what the old model charged
    assert one_x_model <= VMEM_BUDGET_BYTES   # i.e. it WOULD have accepted
    assert plan.peak_vmem_bytes >= 2 * meta.n_cols * 8 * 8


def test_giant_operand_rejected_resident_accepted_streaming():
    giant = _meta(1 << 20, 1 << 20, c=512, n_slices=1 << 11)
    assert not plan_spmm_sell(giant, k=8, x_dtype="float64").ok
    accept = plan_spmm_sell_stream(giant, k=8, x_dtype="float64")
    accept.raise_if_invalid()
    # the streaming footprint is O(tiles), independent of n_cols/n_rows
    assert accept.peak_vmem_bytes <= VMEM_BUDGET_BYTES


def test_stream_plan_rejects_oversized_tiles():
    meta = _meta(64, 1 << 20)
    bad = plan_spmm_sell_stream(meta, k=8, x_dtype="float64",
                                col_tile=1 << 24)
    assert not bad.ok
    with pytest.raises(LaunchPlanError):
        bad.raise_if_invalid()


# ---------------------------------------------------------------------------
# ops dispatch: auto streams what resident rejects
# ---------------------------------------------------------------------------


def test_ops_mode_dispatch_small_operand():
    csr = F.random_csr(96, 96, 5.0, seed=2, skew=1.0)
    slabs = F.csr_to_sell_slabs(csr, c=16, sigma=64)
    x = RNG.standard_normal((96, 4))
    auto = np.asarray(ops.spmm(slabs, x, vl=16))
    res = np.asarray(ops.spmm(slabs, x, vl=16, mode="resident"))
    stream = np.asarray(ops.spmm(slabs, x, vl=16, mode="stream"))
    # in-VMEM auto IS the resident schedule, not a near-miss of it
    np.testing.assert_array_equal(auto, res)
    np.testing.assert_allclose(stream, res, rtol=1e-10, atol=1e-10)
    with pytest.raises(ValueError, match="mode"):
        ops.spmm(slabs, x, vl=16, mode="turbo")
    ell = F.csr_to_ellpack(csr, c=16)
    with pytest.raises(ValueError, match="SELL"):
        ops.spmm(ell, x, vl=16, mode="stream")


def test_ops_auto_streams_what_resident_rejects():
    """A wide operand (600k columns, k=8) whose honest resident plan blows
    VMEM: mode="resident" raises the structured preflight error, while the
    default auto dispatch streams it and matches the host reference."""
    csr = F.random_csr(64, 600_000, 2.0, seed=11)
    slabs = F.csr_to_sell_slabs(csr, c=32, sigma=128)
    x = RNG.standard_normal((600_000, 8))
    with pytest.raises(LaunchPlanError):
        ops.spmm(slabs, x, vl=32, mode="resident")
    got = np.asarray(ops.spmm(slabs, x, vl=32))
    want = np.stack([csr.matvec(x[:, j]) for j in range(8)], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# Service: giant operand registers as stream, serves, and is counted
# ---------------------------------------------------------------------------


def test_service_streams_giant_rectangular_operand():
    csr = F.random_csr(8192, 4_300_000, 2.0, seed=3)
    reg = KernelRegistry()
    reg.register_matrix("giant", csr)
    rec = reg.get("giant")
    assert rec.mode == "stream"
    assert rec.plans["spmv"].ok
    svc = KernelService(reg, n_slots=2)
    x = RNG.standard_normal(4_300_000)
    req = svc.submit("spmv", "giant", x)
    svc.drain()
    np.testing.assert_allclose(svc.poll(req), csr.matvec(x),
                               rtol=1e-10, atol=1e-10)
    assert svc.stats["streamed_launches"] == 1
    assert svc.stats["served"] == 1 and svc.stats["failed"] == 0


# ---------------------------------------------------------------------------
# Single k-padding policy + stream-only co-tuning
# ---------------------------------------------------------------------------


def test_k_padding_pow2_fixpoint():
    """pow2 k is a fixpoint of ``padded_k`` for every k_block — the ops
    boundary asserts this, so the service's ``_pow2_pad`` output is never
    padded a second time by the core."""
    for k in (1, 2, 4, 8, 16, 64):
        for kb in (1, 2, 4, 8, 16, 32):
            assert sell_core.padded_k(k, kb) == k
            kt = sell_core.k_tile_for(k, kb)
            assert kt & (kt - 1) == 0 and k % kt == 0
    # non-pow2 k pads exactly once, up to a multiple of the tile; a stack
    # of at most 8 columns is one whole-k tile, wider ones tile by >= 8
    # rows so every (k_tile, C) block meets the TPU (8, 128) tiling rule
    assert sell_core.k_tile_for(3, 2) == 4
    assert sell_core.padded_k(3, 2) == 4
    assert sell_core.padded_k(5, 8) == 8
    assert sell_core.k_tile_for(32, 2) == 8
    assert sell_core.padded_k(20, 2) == 24


def test_tune_stream_only_fallback_and_tiles():
    """When no candidate fits the 2x-resident X filter, the tuner must
    still return a layout (scored for the streaming schedule) with
    in-budget stream tiles instead of raising."""
    rng = np.random.default_rng(1)
    lengths = rng.poisson(6, 4096).clip(1)
    n_cols = 4_300_000
    assert 16.0 * n_cols > VMEM_BUDGET_BYTES   # resident filter empty
    tuned = tune_sell_layout(lengths, n_cols=n_cols)
    assert tuned.k_block >= 1 and tuned.k_block & (tuned.k_block - 1) == 0
    assert tuned.col_tile >= 1 and tuned.row_tile >= 1
    ct, rt = pick_stream_tiles(tuned.c, tuned.w_block, tuned.k_block)
    assert (tuned.col_tile, tuned.row_tile) == (ct, rt)
    plan = plan_spmm_sell_stream(
        _meta(4096 * 64, n_cols, c=tuned.c, width=tuned.w_block,
              n_slices=4096 * 64 // tuned.c),
        k=tuned.k_block, x_dtype="float64", w_block=tuned.w_block,
        k_block=tuned.k_block, col_tile=tuned.col_tile,
        row_tile=tuned.row_tile)
    assert plan.ok


def test_registry_prices_matrix_plans_at_the_widest_group_tile():
    """A group of up to 8 requests runs as one 8-column RHS tile whatever
    ``k_block`` (``k_tile_for``), so registration and admission price that
    tile.  A million-column operand whose tuned ``k_block`` fits the
    resident schedule, but whose 8-column table does not, registers on the
    streaming schedule, and the service admits it against that plan."""
    csr = F.random_csr(4096, 1 << 20, 4.0, seed=9, dtype=np.float32)
    registry = KernelRegistry()
    rec = registry.register_matrix("wide", csr)
    kb = rec.tuned.k_block
    assert kb < F.SUBLANES
    assert F.k_tile_for(8, kb) == F.widest_k_tile(kb) == F.SUBLANES
    # the tuned tile alone would have passed: only the group tile rejects
    assert plan_spmm_sell(rec.slab_meta, k=kb, x_dtype="float32",
                          w_block=rec.tuned.w_block, k_block=kb).ok
    assert not plan_spmm_sell(rec.slab_meta, k=F.SUBLANES, x_dtype="float32",
                              w_block=rec.tuned.w_block, k_block=kb).ok
    assert rec.mode == "stream"
    plans = KernelService(registry, n_slots=8).plans()["wide"]
    assert plans["spmv"]["kernel"] == "spmm_sell_stream"
    assert plans["spmv"]["ok"]
