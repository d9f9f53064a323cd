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

NPB FT (:func:`ft_spectrum`, :func:`ft_step`, :func:`ft_field`) runs
3-D transforms as three batched passes of that kernel, one per axis, on
fields held in the same chunk layout: a field of logical shape
(K, A, B, C) is stored (C / lanes, K, A, B, lanes), so a pass along C is
one ``pallas_call`` over K·A·B signals, and one transpose between passes
moves C first and cuts B, the next axis, into chunks.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import compiler_params, resolve_interpret
from repro.kernels.ref import fft_twiddles
from repro.kernels.sell_core import LANES, take_lanes
from repro.obs import trace as obs_trace


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


def _stockham(re, im, wre, wim, b_block: int, interpret: bool | None):
    """The kernel over (chunks, batch, lanes) planes already in chunk
    layout, ``batch`` a multiple of ``b_block``; ``wre``/``wim`` are the
    (stages, R/2, lanes) chunk tables."""
    chunks, batch, lanes = re.shape
    block = pl.BlockSpec((chunks, b_block, lanes), lambda i: (0, i, 0))
    table = pl.BlockSpec(wre.shape, lambda i: (0, 0, 0))
    return pl.pallas_call(
        _fft_kernel,
        grid=(batch // b_block,),
        in_specs=[block, block, table, table],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct(re.shape, re.dtype),
            jax.ShapeDtypeStruct(im.shape, im.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, chunks, b_block, lanes), re.dtype),
            pltpu.VMEM((2, chunks, b_block, lanes), im.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=resolve_interpret(interpret),
    )(re, im, wre.astype(re.dtype), wim.astype(re.dtype))


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

    out_r, out_i = _stockham(to_chunks(re), to_chunks(im), wre, wim,
                             b_block, interpret)
    return from_chunks(out_r), from_chunks(out_i)


# ---------------------------------------------------------------------------
# NPB FT: 3-D transforms of a resident field
# ---------------------------------------------------------------------------

#: points NPB FT's checksum reads (``ft.f``, ``checksum``)
FT_SAMPLES = 1024
#: axes of the field: one batched pass each per transform
FT_AXES = 3


def ft_sample_points(shape) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(x, y, z) of the points NPB FT's checksum reads from a field of
    ``shape`` = (nx, ny, nz): (j mod nx, 3j mod ny, 5j mod nz), j = 1..1024."""
    nx, ny, nz = shape
    j = np.arange(1, FT_SAMPLES + 1)
    return j % nx, (3 * j) % ny, (5 * j) % nz


def ft_decay(alpha: float, steps) -> np.ndarray:
    """Per step t the exponent scale of NPB FT's evolve factor,
    -4 alpha pi^2 t, in float64 (the factor is exp(scale * kbar^2))."""
    return -4.0 * float(alpha) * np.pi ** 2 * np.asarray(steps, np.float64)


def _ft_to_chunks(x, lanes: int):
    """(K, A, B, C) -> (C / lanes, K, A, B, lanes): chunked along C."""
    k, a, b, c = x.shape
    return x.reshape(k, a, b, c // lanes, lanes).transpose(3, 0, 1, 2, 4)


def _ft_rotate(x, lanes: int):
    """Chunked (K, A, B, C) -> chunked (K, C, A, B): C moves first and B,
    now last, is cut into chunks of ``lanes``; one transpose."""
    cc, k, a, b, lc = x.shape
    bc = b // lanes
    x = x.reshape(cc, k, a, bc, lanes, lc).transpose(3, 1, 0, 5, 2, 4)
    return x.reshape(bc, k, cc * lc, a, lanes)


def _ft_pass(re, im, *, axis: int, inverse: bool, b_block: int,
             interpret: bool | None):
    """One batched 1-D FFT along the chunked axis of (R, K, A, B, lanes)
    planes: every line of the field is one signal of the kernel.  The
    inverse runs the conjugate twiddles, unnormalised."""
    r, k, a, b, lanes = re.shape
    n = r * lanes
    with obs_trace.scope("ft.pass", axis=axis):
        wre, wim = fft_twiddles(n, re.dtype)
        wre, wim = fft_twiddle_table(wre, -wim if inverse else wim, n)
        flat = (r, k * a * b, lanes)
        out_r, out_i = _stockham(re.reshape(flat), im.reshape(flat), wre, wim,
                                 b_block, interpret)
    return out_r.reshape(re.shape), out_i.reshape(im.shape)


@functools.partial(jax.jit, static_argnames=("b_blocks", "interpret"))
def ft_spectrum(re: jnp.ndarray, im: jnp.ndarray, *,
                b_blocks: tuple[int, int, int],
                interpret: bool | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The forward 3-D transform (e^{-2 pi i}) of the (nx, ny, nz) field
    ``re + i im``, in the layout :func:`ft_step` reads: (nx / lanes, ny, nz,
    lanes) planes, chunked along x.  ``b_blocks`` are the x, y, z passes'
    (:func:`repro.analysis.preflight.plan_ft`)."""
    nx, ny, nz = re.shape
    bx, by, bz = b_blocks
    lanes = fft_lanes(nz)
    re, im = _ft_to_chunks(re[None], lanes), _ft_to_chunks(im[None], lanes)
    kw = {"inverse": False, "interpret": interpret}
    re, im = _ft_pass(re, im, axis=2, b_block=bz, **kw)            # (x, y, z)
    re, im = _ft_rotate(re, fft_lanes(ny)), _ft_rotate(im, fft_lanes(ny))
    re, im = _ft_pass(re, im, axis=1, b_block=by, **kw)            # (z, x, y)
    re, im = _ft_rotate(re, fft_lanes(nx)), _ft_rotate(im, fft_lanes(nx))
    re, im = _ft_pass(re, im, axis=0, b_block=bx, **kw)            # (y, z, x)
    return re[:, 0], im[:, 0]


def _ft_evolved(sre, sim, decay, b_blocks, interpret):
    """u_t of every step in the group, from the resident spectrum: the
    evolve factor exp(decay_k kbar^2) from index iotas, then the inverse
    passes along x, z and y.  Returns (ny / lanes, K, nz, nx, lanes)
    planes: logical (K, z, x, y), chunked along y."""
    xc, ny, nz, lx = sre.shape
    nx = xc * lx
    bx, by, bz = b_blocks
    with obs_trace.scope("ft.evolve"):
        shape = (xc, 1, ny, nz, lx)

        def kbar2(n, *dims):
            k = sum(jax.lax.broadcasted_iota(jnp.int32, shape, d) * m
                    for d, m in dims)
            kb = (k + n // 2) % n - n // 2
            return kb * kb

        k2 = (kbar2(nx, (0, lx), (4, 1)) + kbar2(ny, (2, 1))
              + kbar2(nz, (3, 1))).astype(sre.dtype)
        factor = jnp.exp(decay.astype(sre.dtype)[None, :, None, None, None]
                         * k2)
        re, im = sre[:, None] * factor, sim[:, None] * factor
    kw = {"inverse": True, "interpret": interpret}
    re, im = _ft_pass(re, im, axis=0, b_block=bx, **kw)            # (y, z, x)
    re, im = _ft_rotate(re, fft_lanes(nz)), _ft_rotate(im, fft_lanes(nz))
    re, im = _ft_pass(re, im, axis=2, b_block=bz, **kw)            # (x, y, z)
    re, im = _ft_rotate(re, fft_lanes(ny)), _ft_rotate(im, fft_lanes(ny))
    return _ft_pass(re, im, axis=1, b_block=by, **kw)              # (z, x, y)


@functools.partial(jax.jit, static_argnames=("b_blocks", "interpret"))
def ft_step(sre: jnp.ndarray, sim: jnp.ndarray, decay: jnp.ndarray, *,
            b_blocks: tuple[int, int, int],
            interpret: bool | None = None) -> jnp.ndarray:
    """NPB FT time steps of the resident spectrum (``sre``/``sim`` from
    :func:`ft_spectrum`), one per entry of ``decay`` (:func:`ft_decay`),
    as one program: (K, 1025) complex, the 1024 checksum points of u_t
    (:func:`ft_sample_points`) and last their sum over nx ny nz, NPB's
    checksum."""
    re, im = _ft_evolved(sre, sim, decay, b_blocks, interpret)
    with obs_trace.scope("ft.sample"):
        yc, k, nz, nx, ly = re.shape
        x, y, z = ft_sample_points((nx, yc * ly, nz))
        pick = (y // ly, slice(None), z, x, y % ly)
        u = jax.lax.complex(re[pick], im[pick]).T             # (K, 1024)
        total = jnp.sum(u, axis=1, keepdims=True) / (nx * yc * ly * nz)
        return jnp.concatenate([u, total], axis=1)


@functools.partial(jax.jit, static_argnames=("b_blocks", "interpret"))
def ft_field(sre: jnp.ndarray, sim: jnp.ndarray, decay: jnp.ndarray, *,
             b_blocks: tuple[int, int, int],
             interpret: bool | None = None
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The whole fields u_t that :func:`ft_step` samples: (K, nx, ny, nz)
    re / im planes."""
    re, im = _ft_evolved(sre, sim, decay, b_blocks, interpret)
    yc, k, nz, nx, ly = re.shape

    def whole(p):
        return p.transpose(1, 3, 0, 4, 2).reshape(k, nx, yc * ly, nz)

    return whole(re), whole(im)
