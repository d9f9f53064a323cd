"""NPB FT through the program: the 3-D step of ``kernels/fft.py`` against
the plain ``jnp.fft`` reference (``kernels/ref.ft_ref``), its plan, and the
service's ``ft`` op.

The program runs in float32, the chip's precision, on seeded random fields
(re, im ~ U[0, 1)) and is compared with the float64 reference, whole field
and the 1024 checksum points, at steps 0, 1 and 20.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis.launchplan import LaunchPlanError
from repro.analysis.preflight import HBM_BUDGET_BYTES, plan_fft_stockham, plan_ft
from repro.kernels import fft as fft_k
from repro.kernels import ops
from repro.kernels.ref import ft_checksum_ref, ft_ref
from repro.obs import trace as obs_trace
from repro.service import KernelRegistry, KernelService

ALPHA = 1e-6
SHAPES = [(16, 8, 8), (32, 16, 16), (8, 32, 64)]
STEPS = (0, 1, 20)


def tolerance(dtype, n_points: int) -> float:
    """u_t passes through 2 log2(N) radix-2 stages (the forward and the
    inverse transform), each rounding every value about once, so its error
    relative to the field's largest value stays below 2 log2(N) eps of the
    working precision: a linear bound, where independent roundings grow as
    its square root.  In float32 that is 2.4e-6 at 16 x 8 x 8 and 6.4e-6 at
    NPB class C's 512^3; in bfloat16 it is beyond 0.15."""
    return 2 * np.log2(n_points) * float(jnp.finfo(dtype).eps)


def field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random(shape), rng.random(shape)


def run_program(re, im, steps, dtype, samples=True):
    """The field's spectrum, then every step's whole field and (where
    ``samples``; complex results need float32 or wider) its samples."""
    plan = plan_ft(re.shape, k=len(steps), dtype=str(jnp.dtype(dtype)))
    sre, sim = fft_k.ft_spectrum(jnp.asarray(re, dtype), jnp.asarray(im, dtype),
                                 b_blocks=plan.b_blocks)
    decay = jnp.asarray(fft_k.ft_decay(ALPHA, steps), dtype)
    fr, fi = fft_k.ft_field(sre, sim, decay, b_blocks=plan.b_blocks)
    whole = np.asarray(fr, np.float64) + 1j * np.asarray(fi, np.float64)
    if not samples:
        return whole, None
    return whole, np.asarray(fft_k.ft_step(sre, sim, decay,
                                           b_blocks=plan.b_blocks))


def rel_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_ft_step_matches_the_reference_in_float32(shape):
    re, im = field(shape)
    whole, samples = run_program(re, im, STEPS, jnp.float32)
    tol = tolerance(jnp.float32, re.size)
    assert samples.shape == (len(STEPS), fft_k.FT_SAMPLES + 1)
    for i, t in enumerate(STEPS):
        want = np.asarray(ft_ref(re, im, t, ALPHA))
        points, checksum = ft_checksum_ref(want)
        assert rel_err(whole[i], want) < tol, t
        assert rel_err(samples[i, :-1], np.asarray(points)) < tol, t
        assert abs(samples[i, -1] - checksum) < tol * abs(checksum), t


def test_ft_step_in_bfloat16_fails_the_tolerance():
    re, im = field((16, 8, 8))
    whole, _ = run_program(re, im, (1,), jnp.bfloat16, samples=False)
    want = np.asarray(ft_ref(re, im, 1, ALPHA))
    tol = tolerance(jnp.float32, re.size)
    assert rel_err(whole[0], want) > 10 * tol
    x, y, z = fft_k.ft_sample_points(re.shape)
    assert rel_err(whole[0][x, y, z], np.asarray(ft_checksum_ref(want)[0])) \
        > tol


def test_ft_step_in_float64_matches_to_rounding():
    re, im = field((8, 32, 64), seed=4)
    whole, _ = run_program(re, im, (20,), jnp.float64)
    assert rel_err(whole[0], np.asarray(ft_ref(re, im, 20, ALPHA))) < \
        tolerance(jnp.float64, re.size)


def test_sample_points_are_npbs_checksum_points():
    x, y, z = fft_k.ft_sample_points((512, 512, 512))
    assert len(x) == fft_k.FT_SAMPLES == 1024
    # ft.f: q = mod(j, nx) + 1, r = mod(3*j, ny) + 1, s = mod(5*j, nz) + 1
    assert (x[0], y[0], z[0]) == (1, 3, 5)
    assert (x[-1], y[-1], z[-1]) == (0, 0, 0)


def test_ops_ft_is_the_registry_path_in_one_call():
    re, im = field((16, 8, 8), seed=2)
    got = np.asarray(ops.ft(re, im, [3, 7], ALPHA))
    for row, t in zip(got, (3, 7)):
        points, checksum = ft_checksum_ref(ft_ref(re, im, t, ALPHA))
        np.testing.assert_allclose(row[:-1], points, rtol=0, atol=1e-9)
        assert abs(row[-1] - checksum) < 1e-12


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


def test_plan_ft_composes_a_stockham_plan_per_axis():
    plan = plan_ft((8, 32, 64), k=2, dtype="float32")
    assert plan.ok and plan.n_launches == 3
    for axis, (n, bb, block) in enumerate(zip((8, 32, 64), plan.b_blocks,
                                              plan.blocks)):
        lines = 2 * 8 * 32 * 64 // n
        sub = plan_fft_stockham(n, lines, b_block=bb, dtype="float32")
        assert block.vmem_bytes == sub.blocks[0].vmem_bytes
        assert block.grid == (lines // bb,), axis


def test_plan_ft_picks_blocks_and_groups_for_class_c():
    plan = plan_ft((512, 512, 512), dtype="float32")
    assert plan.ok
    # the block is the plan's, not a fixed 8: as wide as VMEM and the
    # 262,144 lines of each axis allow, up to the cap
    assert plan.b_blocks == (256, 256, 256)
    field_bytes = 2 * 4 * 512 ** 3
    assert plan.hbm_bytes == 4 * field_bytes
    # 1 resident + k * 3 transient fields of 1.07 GB within 16 GB: k = 4
    assert plan.max_group == 4
    assert plan_ft((512, 512, 512), k=4).ok
    assert not plan_ft((512, 512, 512), k=8).ok


@pytest.mark.parametrize("shape", [(1024, 1024, 1024), (2048, 1024, 1024)],
                         ids=["1024^3", "class-D"])
def test_plan_ft_rejects_fields_beyond_hbm(shape):
    plan = plan_ft(shape, dtype="float32")
    assert not plan.ok and plan.max_group == 0
    assert any("HBM budget" in v for v in plan.violations)
    assert plan.hbm_bytes > HBM_BUDGET_BYTES
    with pytest.raises(LaunchPlanError):
        plan.raise_if_invalid()


def test_plan_ft_rejects_lengths_the_kernel_cannot_run():
    plan = plan_ft((16, 12, 8))
    assert not plan.ok
    assert any(v.startswith("axis 1:") for v in plan.violations)


# ---------------------------------------------------------------------------
# The service's ft op
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ft_world():
    re, im = field((16, 8, 8), seed=7)
    reg = KernelRegistry()
    op = reg.register_ft("ft", re, im, ALPHA)
    return re, im, reg, op


def want_row(re, im, t):
    points, checksum = ft_checksum_ref(ft_ref(re, im, t, ALPHA))
    return np.append(np.asarray(points), checksum)


def test_register_ft_keeps_the_spectrum_resident(ft_world):
    re, im, reg, op = ft_world
    assert op.kind == "ft" and op.n == re.size
    assert op.ft["shape"] == re.shape and op.ft["alpha"] == ALPHA
    assert op.plans["ft"].ok and op.ft["b_blocks"] == op.plans["ft"].b_blocks
    sre, sim = op.device_arrays["re"], op.device_arrays["im"]
    spectrum = np.fft.fftn(re + 1j * im)            # stored chunked along x
    xc, ny, nz, lx = sre.shape
    got = (np.asarray(sre) + 1j * np.asarray(sim)).transpose(
        0, 3, 1, 2).reshape(xc * lx, ny, nz)
    np.testing.assert_allclose(got, spectrum, rtol=0, atol=1e-10)


def test_one_ft_step_counts_one_step_and_three_passes(ft_world):
    re, im, reg, _ = ft_world
    svc = KernelService(reg, n_slots=1)
    rid = svc.submit("ft", "ft", 5)
    svc.drain()
    got = svc.poll(rid)
    assert got.shape == (fft_k.FT_SAMPLES + 1,)
    np.testing.assert_allclose(got, want_row(re, im, 5), rtol=0, atol=1e-9)
    assert svc.stats["ft_steps"] == 1 and svc.stats["ft_axis_passes"] == 3
    assert svc.stats["launches"] == 1 and svc.stats["graph_steps"] == 0


def test_an_ft_step_is_one_device_program(caplog):
    """In float32, as on the chip, a served step compiles (so runs) one
    program: no eager operation before or between the passes."""
    import logging

    import jax

    re, im = field((16, 8, 8), seed=8)
    with jax.enable_x64(False):
        reg = KernelRegistry()
        reg.register_ft("ft", re, im, ALPHA)
        svc = KernelService(reg, n_slots=1)
        jax.clear_caches()
        with jax.log_compiles(True), caplog.at_level(logging.WARNING):
            rid = svc.submit("ft", "ft", 3)
            svc.drain()
            svc.poll(rid)
    compiled = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("Compiling ")]
    assert len(compiled) == 1 and "ft_step" in compiled[0], compiled


def test_ft_group_stacks_steps_up_to_the_plans_width(ft_world, monkeypatch):
    re, im, reg, op = ft_world
    monkeypatch.setitem(op.ft, "max_group", 2)
    calls = []
    real = fft_k.ft_step

    def counting(sre, sim, decay, **kw):
        calls.append(decay.shape[0])
        return real(sre, sim, decay, **kw)

    monkeypatch.setattr(fft_k, "ft_step", counting)
    svc = KernelService(reg, n_slots=4)
    steps = [0, 1, 20]
    rids = [svc.submit("ft", "ft", t) for t in steps]
    svc.drain()
    assert calls == [2, 1]                  # 3 steps, at most 2 a launch
    for rid, t in zip(rids, steps):
        np.testing.assert_allclose(svc.poll(rid), want_row(re, im, t),
                                   rtol=0, atol=1e-9)
    assert svc.stats["ft_steps"] == 3 and svc.stats["ft_axis_passes"] == 6


@pytest.mark.parametrize("payload", [1.5, -1, True, None, np.array([1, 2])])
def test_a_bad_ft_payload_fails_alone(ft_world, payload):
    re, im, reg, _ = ft_world
    svc = KernelService(reg, n_slots=2)
    bad, good = svc.submit("ft", "ft", payload), svc.submit("ft", "ft", 2)
    svc.drain()
    with pytest.raises(RuntimeError, match="ft"):
        svc.poll(bad)
    np.testing.assert_allclose(svc.poll(good), want_row(re, im, 2),
                               rtol=0, atol=1e-9)
    assert svc.stats["ft_steps"] == 1


def test_ft_register_is_a_phase_and_the_step_scopes_its_parts(monkeypatch):
    import jax.profiler

    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    re, im = field((16, 8, 8))
    was = obs_trace.annotate(True)
    try:
        op = KernelRegistry().register_ft("ft", re, im, ALPHA)
    finally:
        obs_trace.annotate(was)
    assert seen == ["ft.register"]
    # the step is one program: its parts are scopes on its operations
    text = fft_k.ft_step.lower(
        op.device_arrays["re"], op.device_arrays["im"],
        jnp.zeros(1), b_blocks=op.ft["b_blocks"]).as_text(debug_info=True)
    for scope in ("ft.evolve", "ft.pass/axis=0", "ft.pass/axis=1",
                  "ft.pass/axis=2", "ft.sample"):
        assert scope in text, scope


def test_register_ft_refuses_a_field_that_is_not_3d():
    with pytest.raises(ValueError, match="3-D"):
        KernelRegistry().register_ft("ft", np.zeros((8, 8)), np.zeros((8, 8)),
                                     ALPHA)
    with pytest.raises(LaunchPlanError):
        KernelRegistry().register_ft("ft", np.zeros((8, 8, 6)),
                                     np.zeros((8, 8, 6)), ALPHA)
