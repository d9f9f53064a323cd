"""NAS Parallel Benchmarks FT: time steps of the 3-D heat equation, solved
spectrally.

The field u is (nx, ny, nz) complex; its spectrum is transformed once
(forward, e^{-2 pi i}), and time step t is

    u_t = IFFT3(FFT3(u) * exp(-4 alpha pi^2 t kbar^2)),

kbar = ((k + n/2) mod n) - n/2 on each axis and the inverse e^{+2 pi i},
unnormalised, as NPB's ``ft.f`` computes it (``compute_indexmap``,
``evolve``, ``fft``).  A request asks for one step t; its answer is u_t at
the 1024 points NPB's ``checksum`` reads, (j mod nx, 3j mod ny, 5j mod nz)
for j = 1..1024, and last their sum over nx ny nz, the checksum.

The initial field is drawn from ``--seed`` (re, im ~ U[0, 1) with numpy).
The reference is ``scipy.fft`` in complex128 on every host core, kept with
the benchmark, so a change to the program cannot move it.
"""
from __future__ import annotations

import numpy as np

#: points NPB FT's checksum reads
SAMPLES = 1024
#: steps of the run whose results are compared
CHECKED = 2


def sample_points(shape) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(x, y, z) of NPB FT's checksum points in a field of ``shape``."""
    nx, ny, nz = shape
    j = np.arange(1, SAMPLES + 1)
    return j % nx, (3 * j) % ny, (5 * j) % nz


def kbar2(n: int) -> np.ndarray:
    """kbar^2 along one axis of length ``n``."""
    k = np.arange(n)
    kbar = (k + n // 2) % n - n // 2
    return (kbar * kbar).astype(np.float64)


def ft_bytes(shape) -> int:
    """Bytes a step has to move at least: the resident spectrum's two
    float32 planes, read once."""
    return 2 * 4 * int(np.prod(shape))


def sample_err(got, want: np.ndarray) -> float:
    """Widest gap of the 1024 sampled values, over the largest of the
    reference's (a result of another shape, or not finite, is inf)."""
    got = np.asarray(got)
    if got.shape != (SAMPLES + 1,) or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got[:SAMPLES] - want).max() / np.abs(want).max())


class Workload:
    """The field from the seed, the steps requested, and the check."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.name = spec["name"]
        self.shape = (int(spec["nx"]), int(spec["ny"]), int(spec["nz"]))
        self.niter = int(spec["niter"])
        self.alpha = float(spec["alpha"])
        rng = np.random.default_rng(seed)
        self.re = rng.random(self.shape, dtype=np.float32)
        self.im = rng.random(self.shape, dtype=np.float32)
        self.checked = set((rng.choice(self.niter, CHECKED, replace=False)
                            + 1).tolist())
        self._spectrum = None
        self._want: dict[int, np.ndarray] = {}

    def step(self, i: int) -> int:
        return i % self.niter + 1

    def register(self, registry) -> None:
        registry.register_ft(self.name, self.re, self.im, self.alpha)

    def request(self, op: str, i: int) -> tuple:
        return self.step(i), {}

    def warmup(self, op: str, widths) -> list[list[tuple]]:
        """One group of each width the traffic can form."""
        return [[(self.step(j), {}) for j in range(w)] for w in widths]

    def keep(self, op: str, i: int) -> bool:
        return self.step(i) in self.checked

    def decay(self, t: int) -> np.ndarray:
        """exp(-4 alpha pi^2 t kbar^2) as its three separable factors."""
        ap = -4.0 * self.alpha * np.pi ** 2 * t
        ex, ey, ez = (np.exp(ap * kbar2(n)) for n in self.shape)
        return ex[:, None, None], ey[None, :, None], ez[None, None, :]

    def want(self, t: int) -> np.ndarray:
        """The reference's u_t at the checksum points, complex128."""
        import scipy.fft

        if t not in self._want:
            if self._spectrum is None:
                self._spectrum = scipy.fft.fftn(
                    self.re.astype(np.complex128) + 1j * self.im,
                    workers=-1)
            u = self._spectrum.copy()
            for f in self.decay(t):
                u *= f
            u = scipy.fft.ifftn(u, norm="forward", workers=-1,
                                overwrite_x=True)
            self._want[t] = u[sample_points(self.shape)]
        return self._want[t]

    def check(self, op: str, results: dict) -> list[dict]:
        """The kept requests against the float64 reference; a run that
        kept none has checked nothing and fails."""
        err = max((sample_err(got, self.want(self.step(i)))
                   for i, got in results.items()), default=float("inf"))
        return [{"name": "ft_sample_err", "value": err,
                 "limit": self.spec["limits"]["ft_sample_err"]}]

    def control(self, op: str, indices) -> dict:
        """The control: the reference in the program's place, computed in
        bfloat16 (the precision below the configuration's float32) on the
        default device: the field, the evolve factor and every pass's
        output rounded to bfloat16.  The rounding is ``reduce_precision``,
        which the compiler must keep: with ``astype`` round trips through
        bfloat16 in their place this control read 7e-6 on the v5e, within
        the limit, so some of them did not round there."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32

        def bf16(x):
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)

        def rounded(z):
            return jax.lax.complex(bf16(z.real), bf16(z.imag))

        @jax.jit
        def spectrum(re, im):
            z = rounded(jax.lax.complex(re, im))
            for axis in range(3):
                z = rounded(jnp.fft.fft(z, axis=axis))
            return z

        axes = [jnp.asarray(f, f32) for f in np.meshgrid(
            *(kbar2(n) for n in self.shape), indexing="ij", sparse=True)]
        points = sample_points(self.shape)

        @jax.jit
        def step(z, ap_t, kx, ky, kz):
            factor = bf16(jnp.exp(ap_t * (kx + ky + kz)))
            z = rounded(z * factor)
            for axis in range(3):
                z = rounded(jnp.fft.ifft(z, axis=axis, norm="forward"))
            u = z[points]
            return jnp.concatenate([u, u.sum(keepdims=True) / z.size])

        out = {}
        with jax.default_matmul_precision("highest"):
            z0 = spectrum(jnp.asarray(self.re), jnp.asarray(self.im))
            for t in {self.step(i) for i in indices}:
                ap_t = jnp.asarray(-4.0 * self.alpha * np.pi ** 2 * t, f32)
                out[t] = np.asarray(step(z0, ap_t, *axes))
        return {i: out[self.step(i)] for i in indices}

    def work(self, op: str, done: list[int], stats: dict) -> dict:
        """Algorithmic bytes of the completed steps."""
        return {"bytes": len(done) * ft_bytes(self.shape)}
