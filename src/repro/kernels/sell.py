"""Width-bucketed SELL-C-sigma SpMV (paper §3.1, Gómez et al. [2]).

Since the multi-RHS refactor this module is a thin driver: the bucketed
gather-MAC schedule, the RHS tiling, and the row scatter all live in
:mod:`repro.kernels.sell_core`; ``spmv_sell`` is the k = 1 column of
:func:`repro.kernels.sell_core.spmm_sell` and keeps its historical
signature so existing call sites (and the uniform-width comparisons in the
benchmarks) are untouched.

Bucketing bounds the number of kernel launches by log2(max_width) while the
padded-nnz tracks the sigma-sorted per-slice widths: on skewed row-length
distributions this is where the >=2x padded-FLOP cut comes from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.sell_core import spmm_sell

PAD = -1

__all__ = ["PAD", "spmm_sell", "spmv_sell"]


@functools.partial(
    jax.jit, static_argnames=("n_rows", "w_block", "interpret")
)
def spmv_sell(
    bucket_cols: tuple[jnp.ndarray, ...],
    bucket_vals: tuple[jnp.ndarray, ...],
    bucket_rows: tuple[jnp.ndarray, ...],
    x: jnp.ndarray,
    *,
    n_rows: int,
    w_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = A @ x over width-bucketed SELL slabs; returns y of shape (n_rows,).

    ``bucket_cols[b]``/``bucket_vals[b]``: (n_slices_b, W_b, C) slabs;
    ``bucket_rows[b]``: (n_slices_b, C) original-row scatter map with
    ``n_rows`` marking padding lanes.  The single-RHS column of the batched
    core: one lane of the k axis, identical tiles and scatter.
    """
    y = spmm_sell(
        bucket_cols, bucket_vals, bucket_rows, x[:, None],
        n_rows=n_rows, w_block=w_block, k_block=1, interpret=interpret,
    )
    return y[:, 0]
