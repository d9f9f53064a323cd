"""The NPB FT cell (``ft.npb-ft-c.seq``) on the CPU at a test size
(16^3): its control fails the limit, a run whose timed step is broken reads
``correct`` false, its programs-per-step reader, and its byte count.

The chip readings the limit was set from are in ``PERF.md``.  The
harness's look for a chip is skipped; everything else of a run is driven
as on the chip.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import trace_reduce  # noqa: E402

CELL = "ft.npb-ft-c.seq"
SEEDS = (1, 12345, 2**31 + 77)
SMALL = {"nx": 16, "ny": 16, "nz": 16}


def small_cell():
    c = harness.find_cell(CELL)
    c.config = {**c.config, **SMALL}
    return c


def test_cell_reads_every_metric_it_reports():
    c = harness.find_cell(CELL)
    assert c.op == "ft" and c.chips == 1
    assert c.traffic == {"op": "ft", "loop": "closed", "clients": 1,
                         "slots": 1}
    assert {m["name"] for m in c.end_to_end} == {"served_per_s", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "fft_roofline", "idle_share.ft", "programs_per_step.ft"}
    for m in c.per_layer:
        assert os.path.exists(harness.reader_path(m["name"]))


def test_requests_walk_whole_ft_jobs_and_check_two_steps():
    c = harness.find_cell(CELL)
    work = harness.workload(small_cell(), SEEDS[2])
    assert [work.request(c.op, i)[0] for i in range(41)] == \
        list(range(1, 21)) * 2 + [1]
    assert len(work.checked) == 2 and work.checked <= set(range(1, 21))
    kept = {work.step(i) for i in range(40) if work.keep(c.op, i)}
    assert kept == work.checked
    again = harness.workload(small_cell(), SEEDS[2])
    assert np.array_equal(work.re, again.re) and work.checked == again.checked


def test_control_fails_the_limit():
    c = small_cell()
    for seed in SEEDS:
        work = harness.workload(c, seed)
        idx = [i for i in range(1 << 12) if work.keep(c.op, i)][:16]
        (check,) = work.check(c.op, work.control(c.op, idx))
        assert not check["value"] <= check["limit"], seed


def _skip_a_pass(monkeypatch, svc):
    """The y pass of every step left out (and the broken step compiled
    before the window, as the harness's warm-up compiles the real one)."""
    import jax

    from repro.kernels import fft as fft_k

    orig = fft_k._ft_pass

    def skipped(re, im, *, axis, **kw):
        return (re, im) if axis == 1 else orig(re, im, axis=axis, **kw)

    monkeypatch.setattr(fft_k, "_ft_pass", skipped)
    jax.clear_caches()                  # the warmed-up step traces again
    rid = svc.submit("ft", svc.registry.names()[0], 1)
    svc.drain()
    svc.release(rid)


def _t_off_by_one(monkeypatch, svc):
    """Every request evolved one step too far."""
    from repro.kernels import fft as fft_k

    orig = fft_k.ft_decay
    monkeypatch.setattr(fft_k, "ft_decay", lambda alpha, steps: orig(
        alpha, np.asarray(steps) + 1))


FAULTS = {"pass_skipped": _skip_a_pass, "t_off_by_one": _t_off_by_one}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_a_broken_step_reads_not_correct(fault, monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    c = small_cell()
    monkeypatch.setattr(harness, "find_cell", lambda _name: c)

    def break_path(svc):
        if fault is not None:
            FAULTS[fault](monkeypatch, svc)

    line, notes = harness.run(CELL, 2**31 + 5, 1.0, False, t_start=0.0,
                              require_tpu=False, break_path=break_path)
    assert notes["stats"]["ft_steps"] >= 20          # a whole job at least
    assert line["correct"] is (fault is None), line["checks"]


def _run(stats, trace=None, window=(0.0, 0.0)):
    return harness.Run(setup_s=1.0, window_s=1.0, records=[], stats=stats,
                       work={}, peaks={}, trace=trace, trace_window=window)


def test_programs_per_step_reader_reads_the_trace_and_the_counter():
    reader = harness.load_module(harness.reader_path("programs_per_step.ft"))
    ops = [("jit_ft_step", 10.0 * i, 10.0 * i + 8.0) for i in range(6)]
    ops.append(("jit_convert_element_type", 15.0, 16.0))
    trace = trace_reduce.Trace(device_ops=[ops], spans=[])
    assert reader.read(_run({"ft_steps": 6}, trace, (0.0, 60.0))) == 7 / 6
    # outside the window nothing counts
    assert reader.read(_run({"ft_steps": 2}, trace, (0.0, 12.0))) == 1.0
    # no trace, no counter (the parent's program), no step: nothing
    assert reader.read(_run({"ft_steps": 6})) is None
    assert reader.read(_run({}, trace, (0.0, 60.0))) is None
    assert reader.read(_run({"ft_steps": 0}, trace, (0.0, 60.0))) is None


def test_work_bytes_do_not_depend_on_the_plans_block(monkeypatch):
    from repro.analysis import preflight
    from repro.service import KernelRegistry, KernelService

    c = small_cell()
    work = harness.workload(c, 3)
    counted = []
    for cap in (2, preflight.FT_B_BLOCK_MAX):
        monkeypatch.setattr(preflight, "FT_B_BLOCK_MAX", cap)
        reg = KernelRegistry()
        work.register(reg)
        assert max(reg.get(work.name).ft["b_blocks"]) == cap
        svc = KernelService(reg, n_slots=1)
        rids = [svc.submit(c.op, work.name, work.request(c.op, i)[0])
                for i in range(3)]
        svc.drain()
        assert all(svc.poll(r) is not None for r in rids)
        counted.append(work.work(c.op, list(range(3)), dict(svc.stats)))
    assert counted[0] == counted[1] == {"bytes": 3 * 2 * 4 * 16 ** 3}
