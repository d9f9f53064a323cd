"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the semantic ground truth its kernel is tested against with
``np.testing.assert_allclose`` across shape/dtype sweeps.  They are also the
implementations the CPU examples run when Pallas is not worth interpreting.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

PAD = -1
INF = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# SpMV (ELLPACK slice-transposed layout)
# ---------------------------------------------------------------------------


def spmv_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """y = A @ x over the padded (n_slices, W, C) layout.

    Padding entries have ``cols == PAD`` and are masked out.
    """
    mask = cols != PAD
    safe = jnp.where(mask, cols, 0)
    gathered = x[safe]                                   # (S, W, C)
    y = jnp.sum(jnp.where(mask, vals * gathered, 0), axis=1)  # (S, C)
    return y.reshape(-1)[:n_rows]


# ---------------------------------------------------------------------------
# FFT (Stockham radix-2, split real/imag planes)
# ---------------------------------------------------------------------------


def fft_twiddles(n: int, dtype=jnp.float64) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-stage twiddle tables, pre-expanded to the (l, m) -> (n/2,) layout.

    Stage s (l = n >> (s+1), m = 1 << s) multiplies the "bottom" halves by
    w_j = exp(-2*pi*i * j / (2l)), j in [0, l), each repeated m times.
    Returns (wre, wim) of shape (stages, n // 2).
    """
    stages = int(np.log2(n))
    half = n // 2
    wre = np.empty((stages, half))
    wim = np.empty((stages, half))
    l, m = half, 1
    for s in range(stages):
        j = np.arange(l)
        w = np.exp(-2j * np.pi * j / (2 * l))
        wre[s] = np.repeat(w.real, m)
        wim[s] = np.repeat(w.imag, m)
        l //= 2
        m *= 2
    return jnp.asarray(wre, dtype), jnp.asarray(wim, dtype)


def fft_stockham_ref(
    re: jnp.ndarray, im: jnp.ndarray, wre: jnp.ndarray, wim: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched Stockham radix-2 DIT FFT on split planes.

    ``re``/``im``: (batch, n).  Returns (batch, n) spectra matching
    ``jnp.fft.fft`` up to fp error.  The stage loop is a python loop (n is
    static), mirroring the unrolled stages of the Pallas kernel.
    """
    b, n = re.shape
    stages = int(np.log2(n))
    half = n // 2
    l, m = half, 1
    xr, xi = re, im
    for s in range(stages):
        x0r = xr.reshape(b, 2, half)
        x0i = xi.reshape(b, 2, half)
        topr = x0r[:, 0] + x0r[:, 1]
        topi = x0i[:, 0] + x0i[:, 1]
        dr = x0r[:, 0] - x0r[:, 1]
        di = x0i[:, 0] - x0i[:, 1]
        botr = dr * wre[s] - di * wim[s]
        boti = dr * wim[s] + di * wre[s]
        # interleave (l, m) pairs: y[(j, h, k)] for h in {top, bot}
        yr = jnp.stack([topr.reshape(b, l, m), botr.reshape(b, l, m)], axis=2)
        yi = jnp.stack([topi.reshape(b, l, m), boti.reshape(b, l, m)], axis=2)
        xr = yr.reshape(b, n)
        xi = yi.reshape(b, n)
        l //= 2
        m *= 2
    return xr, xi


def fft_ref(re: jnp.ndarray, im: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ground-truth spectrum via jnp.fft (oracle for the oracle)."""
    spec = jnp.fft.fft(re + 1j * im)
    return jnp.real(spec), jnp.imag(spec)


# ---------------------------------------------------------------------------
# NPB FT (the 3-D heat equation, solved spectrally)
# ---------------------------------------------------------------------------


def ft_ref(re: jnp.ndarray, im: jnp.ndarray, t: int, alpha: float
           ) -> jnp.ndarray:
    """NPB FT's field at time step ``t`` (``ft.f``: ``compute_indexmap``,
    ``evolve``, ``fft``): u_t = IFFT3(FFT3(re + i im) * exp(-4 alpha pi^2
    t kbar^2)), kbar = ((k + n/2) mod n) - n/2 per axis, the forward
    transform e^{-2 pi i}, the inverse e^{+2 pi i} and unnormalised.  The
    evolve factor is raised to t at once, which NPB reaches by t
    multiplications."""
    with jax.default_matmul_precision("highest"):
        u0 = jnp.fft.fftn(re + 1j * im)
        k2 = 0
        for axis, n in enumerate(u0.shape):
            k = jnp.arange(n)
            kbar = (k + n // 2) % n - n // 2
            k2 = k2 + jnp.expand_dims(kbar * kbar, [a for a in range(3)
                                                    if a != axis])
        return jnp.fft.ifftn(u0 * jnp.exp(-4.0 * alpha * np.pi ** 2 * t * k2),
                             norm="forward")


def ft_checksum_ref(u: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """NPB FT's ``checksum`` of a field: the 1024 values u[j mod nx, 3j mod
    ny, 5j mod nz], j = 1..1024, and their sum over nx ny nz."""
    nx, ny, nz = u.shape
    j = np.arange(1, 1025)
    samples = u[j % nx, (3 * j) % ny, (5 * j) % nz]
    return samples, samples.sum() / u.size


# ---------------------------------------------------------------------------
# BFS (bottom-up / gather-only expansion step)
# ---------------------------------------------------------------------------


def bfs_step_ref(adj: jnp.ndarray, dist: jnp.ndarray, level: int) -> jnp.ndarray:
    """One level-synchronous bottom-up step.

    A node still at INF whose any in/out neighbor (``adj`` rows) sits at
    ``level - 1`` gets distance ``level``.  Gather-only: the long-vector
    formulation (scatter-free) of frontier expansion.
    """
    mask = adj != PAD
    safe = jnp.where(mask, adj, 0)
    nd = dist[safe]                                   # (n, width)
    in_frontier = jnp.where(mask, nd == level - 1, False)
    hit = jnp.any(in_frontier, axis=1)
    return jnp.where((dist == INF) & hit, level, dist)


# ---------------------------------------------------------------------------
# PageRank (pull-style power-iteration step)
# ---------------------------------------------------------------------------


def pagerank_step_ref(
    radj: jnp.ndarray,
    contrib: jnp.ndarray,
    damping: float,
    dangling_mass: jnp.ndarray,
    n_nodes: int,
) -> jnp.ndarray:
    """rank' = (1-d)/n + d * (sum_in contrib[u] + dangling/n).

    ``radj``: reverse (in-neighbor) ELLPACK adjacency (n, width).
    ``contrib``: (n,) = rank/out_degree (0 for dangling nodes).
    """
    mask = radj != PAD
    safe = jnp.where(mask, radj, 0)
    g = jnp.where(mask, contrib[safe], 0.0)
    pulled = g.sum(axis=1)
    return (1.0 - damping) / n_nodes + damping * (pulled + dangling_mass / n_nodes)
