"""Stockham radix-2 FFT Pallas kernel (paper §3.1, Vizcaino et al. [12]).

Long-vector FFT: every stage is a full-width butterfly over the n/2 pairs —
one "vector instruction" of VL = n/2 complex butterflies, with the twiddle
table pre-expanded per stage so the inner step is pure mul/add (no
bit-reversal: Stockham autosorts).  TPU has no complex VREGs, so the
planes are split re/im (two f32/f64 tiles).

The batch axis is the Pallas grid: one grid step transforms ``b_block``
signals.  On the device a length-n signal is held as (R, b_block, lanes)
chunks — element ``r * lanes + j`` of every signal in chunk r, lane j,
with ``lanes = min(128, n / 2)`` — so each chunk is a (b_block, lanes)
vreg tile.  A stage reads chunk pairs (q, q + R/2) in a loop and writes
its autosorted output into a VMEM ping-pong buffer: stages whose
butterfly span m is at least ``lanes`` move whole chunks, the first
``log2(lanes)`` stages interleave within a chunk with Mosaic's lane
gather.  No reshape touches the (b_block, lanes) minor dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import compiler_params, resolve_interpret
from repro.kernels.sell_core import LANES, take_lanes


def fft_lanes(n: int) -> int:
    """Lanes of one signal chunk: a vreg row, or n/2 for short signals so
    there are always two chunks to pair."""
    return max(min(LANES, n // 2), 1)


def _fft_kernel(re_ref, im_ref, wre_ref, wim_ref, or_ref, oi_ref,
                buf_re, buf_im):
    n_chunks, bb, lanes = re_ref.shape
    half = n_chunks // 2
    stages = wre_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, lanes), 1)
    src = (re_ref, im_ref)
    m = 1
    for s in range(stages):
        dst = (or_ref, oi_ref) if s == stages - 1 else \
            (buf_re.at[s % 2], buf_im.at[s % 2])

        def pair(q, carry, s=s, m=m, src=src, dst=dst):
            tr, ti = src[0][q], src[1][q]
            br, bi = src[0][q + half], src[1][q + half]
            wr = jnp.broadcast_to(wre_ref[s, pl.ds(q, 1), :], (bb, lanes))
            wi = jnp.broadcast_to(wim_ref[s, pl.ds(q, 1), :], (bb, lanes))
            topr, topi = tr + br, ti + bi
            dr, di = tr - br, ti - bi
            botr, boti = dr * wr - di * wi, dr * wi + di * wr
            if m >= lanes:                    # whole chunks move
                mc = m // lanes
                base = q + (q // mc) * mc
                dst[0][base], dst[1][base] = topr, topi
                dst[0][base + mc], dst[1][base + mc] = botr, boti
            else:                             # interleave inside a chunk
                src_lane = (lane // (2 * m)) * m + lane % m
                is_top = (lane // m) % 2 == 0
                for off, out in ((0, 2 * q), (lanes // 2, 2 * q + 1)):
                    idx = src_lane + off
                    dst[0][out] = jnp.where(is_top, take_lanes(topr, idx),
                                            take_lanes(botr, idx))
                    dst[1][out] = jnp.where(is_top, take_lanes(topi, idx),
                                            take_lanes(boti, idx))
            return carry

        jax.lax.fori_loop(0, half, pair, 0)
        src = dst
        m *= 2


def fft_twiddle_table(wre: jnp.ndarray, wim: jnp.ndarray, n: int):
    """(stages, n/2) twiddles -> (stages, R/2, lanes) chunk tables."""
    lanes = fft_lanes(n)
    shape = (wre.shape[0], (n // 2) // lanes, lanes)
    return wre.reshape(shape), wim.reshape(shape)


@functools.partial(jax.jit, static_argnames=("b_block", "interpret"))
def fft_stockham(
    re: jnp.ndarray,
    im: jnp.ndarray,
    wre: jnp.ndarray,
    wim: jnp.ndarray,
    *,
    b_block: int = 8,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched FFT of split-plane signals ``re``/``im`` of shape (batch, n).

    ``wre``/``wim`` come from :func:`repro.kernels.ref.fft_twiddles`.
    """
    batch, n = re.shape
    if batch % b_block:
        pad = b_block - batch % b_block
        re = jnp.pad(re, ((0, pad), (0, 0)))
        im = jnp.pad(im, ((0, pad), (0, 0)))
    padded = re.shape[0]
    lanes = fft_lanes(n)
    chunks = n // lanes
    wre, wim = fft_twiddle_table(wre, wim, n)

    def to_chunks(x):
        return x.reshape(padded, chunks, lanes).transpose(1, 0, 2)

    def from_chunks(x):
        return x.transpose(1, 0, 2).reshape(padded, n)[:batch]

    block = pl.BlockSpec((chunks, b_block, lanes), lambda i: (0, i, 0))
    table = pl.BlockSpec(wre.shape, lambda i: (0, 0, 0))
    out_r, out_i = pl.pallas_call(
        _fft_kernel,
        grid=(padded // b_block,),
        in_specs=[block, block, table, table],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((chunks, padded, lanes), re.dtype),
            jax.ShapeDtypeStruct((chunks, padded, lanes), im.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, chunks, b_block, lanes), re.dtype),
            pltpu.VMEM((2, chunks, b_block, lanes), im.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(to_chunks(re), to_chunks(im), wre.astype(re.dtype), wim.astype(re.dtype))
    return from_chunks(out_r), from_chunks(out_i)
