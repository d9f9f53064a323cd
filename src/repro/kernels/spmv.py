"""Long-vector SpMV Pallas kernel (paper §3.1, SELL-C-sigma gather-MAC).

One grid step processes one slice of ``C = vl`` rows: it DMAs a
(1, W_blk, C) tile of values+column indices into VMEM, gathers the matching
x entries, and accumulates the masked FMA into the slice's y block — i.e.
one "vector instruction" worth of work per grid step, with VL = C.

Grid: (n_slices, n_wblocks).  The W axis is blocked so arbitrarily wide
matrices stream through a fixed VMEM budget; y accumulates across W blocks
(revisited output block, initialized at j == 0).

TPU notes: C should be a multiple of 128 (lane dim) and W_blk a multiple of
8 (sublane) for MXU/VPU alignment; x is held VMEM-resident (the CAGE10-class
problems the paper studies fit comfortably; larger matrices would add an
x-partitioning grid axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

PAD = -1


def _spmv_kernel(cols_ref, vals_ref, x_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    cols = cols_ref[0]                       # (W_blk, C) int32
    vals = vals_ref[0]                       # (W_blk, C)
    mask = cols != PAD
    safe = jnp.where(mask, cols, 0)
    gathered = x_ref[safe]                   # VMEM gather, (W_blk, C)
    acc = jnp.sum(jnp.where(mask, vals * gathered, 0), axis=0)
    y_ref[0] += acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w_block", "interpret"))
def spmv_ell(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    x: jnp.ndarray,
    *,
    w_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = A @ x for A in slice-transposed ELLPACK (n_slices, W, C).

    Returns y of shape (n_slices * C,); callers trim to n_rows.
    ``C`` (the slice width) is the paper's VL; ``w_block`` tiles the nnz axis.
    """
    n_slices, width, c = cols.shape
    if width % w_block:
        pad = w_block - width % w_block
        cols = jnp.pad(cols, ((0, 0), (0, pad), (0, 0)), constant_values=PAD)
        vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0)))
        width += pad
    n_wblocks = width // w_block
    grid = (n_slices, n_wblocks)
    out = pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w_block, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, w_block, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec(x.shape, lambda i, j: (0,)),          # x resident
        ],
        out_specs=pl.BlockSpec((1, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_slices, c), vals.dtype),
        interpret=resolve_interpret(interpret),
    )(cols, vals, x)
    return out.reshape(-1)
