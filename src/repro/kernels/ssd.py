"""Fused SSD (Mamba2 selective-scan) Pallas kernel — beyond-paper extension.

The §Roofline analysis shows mamba2-2.7b train/prefill cells are bound by
the HBM traffic of the chunked SSD einsums: the (q, q) intra-chunk decay
matrix and the (q, n)x(q, p) products materialize per (batch, head, chunk)
in HBM.  The long-vector lesson applied at the kernel level: fuse the whole
per-(batch, head) scan in VMEM — decay matrices live and die inside the
kernel, HBM sees only x/B/C in and y/state out (the arguments' byte floor).

Grid: (batch, heads) — embarrassingly parallel; the chunk recurrence is a
static python loop inside the kernel (n_chunks is compile-time), carrying
the (p, n) state in registers/VMEM.

VMEM budget per grid step (L=4096, p=64, n=128, f32):
x (L,p) 1 MB + B,C (L,n) 4 MB + y (L,p) 1 MB + chunk temporaries << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _ssd_fused_kernel(xd_ref, ad_ref, b_ref, c_ref, y_ref, fs_ref, *,
                      chunk: int, n_chunks: int):
    p = xd_ref.shape[-1]
    n = b_ref.shape[-1]
    acc_t = jnp.promote_types(xd_ref.dtype, jnp.float32)  # f32, or f64 in/out
    state = jnp.zeros((p, n), acc_t)
    for ci in range(n_chunks):
        sl = pl.ds(ci * chunk, chunk)
        xc = xd_ref[0, 0, sl, :].astype(acc_t)             # (q, p)
        ac = ad_ref[0, 0, sl].astype(acc_t)                # (q,)
        bc = b_ref[0, 0, sl, :].astype(acc_t)              # (q, n)
        cc = c_ref[0, 0, sl, :].astype(acc_t)              # (q, n)
        cum = jnp.cumsum(ac)                            # (q,)
        # intra-chunk decay matrix — VMEM-only, never touches HBM
        diff = cum[:, None] - cum[None, :]
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        lmat = jnp.where(i >= j, jnp.exp(diff), 0.0)    # (q, q)
        g = cc @ bc.T                                   # (q, q) C_i . B_j
        y = (g * lmat) @ xc                             # (q, p) intra-chunk
        # carried-state contribution + state update
        state_decay = jnp.exp(cum)                      # (q,)
        y = y + state_decay[:, None] * (cc @ state.T)   # (q,n)@(n,p)->(q,p)
        decay_end = jnp.exp(cum[-1] - cum)              # (q,)
        new_contrib = (decay_end[:, None] * bc).T @ xc  # (n, q)@(q, p)->(n,p)
        state = state * jnp.exp(cum[-1]) + new_contrib.T  # (p, n)
        y_ref[0, 0, sl, :] = y.astype(y_ref.dtype)
    fs_ref[0, 0] = state.astype(fs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_fused(
    xd: jnp.ndarray,    # (b, l, h, p) — inputs pre-multiplied by dt
    ad: jnp.ndarray,    # (b, l, h)
    B: jnp.ndarray,     # (b, l, g, n)
    C: jnp.ndarray,     # (b, l, g, n)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused scan.  Returns (y (b,l,h,p), final_state (b,h,p,n))."""
    b, l, h, p = xd.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, "sequence must be chunk-padded"
    hg = h // g
    n_chunks = l // chunk
    # lay out per-(b, h) planes: (b, h, l, ...)
    xbh = xd.transpose(0, 2, 1, 3)                       # (b, h, l, p)
    abh = ad.transpose(0, 2, 1)                          # (b, h, l)
    bbh = jnp.repeat(B, hg, axis=2).transpose(0, 2, 1, 3)  # (b, h, l, n)
    cbh = jnp.repeat(C, hg, axis=2).transpose(0, 2, 1, 3)
    kernel = functools.partial(_ssd_fused_kernel, chunk=chunk, n_chunks=n_chunks)
    y, fs = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), xd.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.promote_types(xd.dtype, jnp.float32)),
        ],
        interpret=resolve_interpret(interpret),
    )(xbh, abh, bbh, cbh)
    return y.transpose(0, 2, 1, 3), fs
