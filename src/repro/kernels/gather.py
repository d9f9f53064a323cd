"""VL-blocked embedding gather — the paper's indexed-gather pattern applied
to the LM substrate (beyond-paper extension).

An LM embedding lookup is the same traffic class as the paper's SpMV
x-gather: T indexed reads of d_model-sized rows from a (V, d) table.  The
long-vector lesson transfers directly: gather VL rows per grid step so the
per-instruction round-trip amortizes and the row bursts saturate bandwidth.

One grid step = one "vector instruction": DMA a (vl,) id block + emit a
(vl, d) row block.  The table is held VMEM-resident here (valid for reduced/
mid vocab sizes; production-size tables keep the table in HBM and stream
row-DMAs per block — same schedule, different BlockSpec memory space — the
SDV traffic trace models both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _gather_kernel(ids_ref, table_ref, out_ref):
    ids = ids_ref[...]                       # (vl,) int32
    out_ref[...] = table_ref[ids]            # VMEM row gather


@functools.partial(jax.jit, static_argnames=("vl", "interpret"))
def embedding_gather(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    vl: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """out[i] = table[ids[i]].  ids: (T,) int32; table: (V, d)."""
    t = ids.shape[0]
    v, d = table.shape
    pad = (-t) % vl
    if pad:
        ids = jnp.pad(ids, (0, pad))
    grid = (ids.shape[0] // vl,)
    out = pl.pallas_call(
        _gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((vl,), lambda i: (i,)),
            pl.BlockSpec((v, d), lambda i: (0, 0)),   # resident table
        ],
        out_specs=pl.BlockSpec((vl, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ids.shape[0], d), table.dtype),
        interpret=resolve_interpret(interpret),
    )(ids, table)
    return out[:t]


def embedding_gather_ref(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Oracle: plain take."""
    return table[ids]
