"""Pieces of the benchmark's harness on the CPU: configurations from the
seed, the traffic loops' clocks, the result readers, and the entry point's
refusal to run without a TPU."""
import contextlib
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

BIG_SEED = 2**31 + 977


def cell(name, **overrides):
    c = harness.find_cell(name)
    c.config = {**c.config, **overrides}
    return c


def test_rmat_workload_is_deterministic_in_the_seed():
    c = cell("bfs.graph500-s14.seq", scale=10)
    a, b = harness.workload(c, BIG_SEED), harness.workload(c, BIG_SEED)
    other = harness.workload(c, 7)
    assert np.array_equal(a.roots, b.roots)
    assert not np.array_equal(a.roots, other.roots)
    # the graph and its 64 search keys are the configuration's; the seed
    # draws the order they are searched in
    assert np.array_equal(a.src, other.src) and np.array_equal(a.dst, other.dst)
    assert sorted(a.roots) == sorted(other.roots)
    assert len(set(a.roots.tolist())) == 64 and (a.degree[a.roots] > 0).all()
    assert a.request("bfs", 5) == b.request("bfs", 5)
    assert a.request("bfs", 64 + 5) == a.request("bfs", 5)
    assert a.request("pagerank", 0) == (None, {"damping": 0.85, "iters": 20})


def test_graph500_s14_has_its_published_shape():
    w = harness.workload(cell("bfs.graph500-s14.seq"), 1)
    n = 1 << 14
    assert w.tuples.shape == (2, 16 * n)
    loops = int((w.tuples[0] == w.tuples[1]).sum())
    assert w.shape == (n, 2 * (16 * n - loops)) == (n, 523_602)
    # undirected: every stored entry has its reverse, and no self-loop
    fwd = np.sort(w.src * n + w.dst)
    assert np.array_equal(fwd, np.sort(w.dst * n + w.src))
    assert not (w.src == w.dst).any()
    assert w.degree.max() == 11_028 and 0.23 < (w.degree == 0).mean() < 0.24
    # labels permuted: the Kronecker hub is not vertex 0
    assert w.degree.argmax() != 0


def test_cage10_workload_is_deterministic_with_its_published_shape():
    c = cell("spmv.hpcg-24.clients8")
    a, b, other = (harness.workload(c, s) for s in (BIG_SEED, BIG_SEED, 3))
    assert a.shape == (13_824, 13_824, 70 ** 3)
    lengths = np.diff(a.indptr)
    assert lengths.max() == 27 and lengths.min() == 8
    assert np.array_equal(a.xs, b.xs) and np.array_equal(a.sample, b.sample)
    assert not np.array_equal(a.xs, other.xs)
    assert np.array_equal(a.data, other.data)
    # HPCG's matrix: symmetric, 26 on the diagonal, -1 for each neighbour,
    # so A @ 1 is 26 - (neighbours) and 0 on every interior row
    assert (a.a != a.a.T).nnz == 0
    assert set(a.a.diagonal()) == {26.0}
    ones = a.a @ np.ones(a.n)
    assert np.array_equal(ones, 27.0 - lengths)
    x, params = a.request("spmv", 3)
    assert params == {} and np.array_equal(x, b.request("spmv", 3)[0])


def test_group_widths_follow_the_loop():
    assert harness.group_widths({"loop": "closed", "clients": 8}, 8) == [8]
    assert harness.group_widths({"loop": "closed", "clients": 1}, 8) == [1]
    assert harness.group_widths({"loop": "poisson"}, 8) == [1, 2, 4, 8]


class FakeDriver:
    """A service on a fake clock that answers everything pending in one
    step of ``service_s`` seconds."""

    answer_wait_s = 60.0

    def __init__(self, service_s):
        self.t, self.service_s = 100.0, service_s
        self.records, self.pending = [], {}

    def clock(self):
        return self.t

    def advance(self, s):
        self.t += s

    def annotate(self, name):
        return contextlib.nullcontext()

    def submit(self, i, due):
        rec = harness.Record(index=i, due=due, submitted=self.t)
        self.records.append(rec)
        self.pending[i] = rec

    def step(self):
        self.t += self.service_s

    def collect(self):
        done = list(self.pending.values())
        for rec in done:
            rec.done = self.t
        self.pending.clear()
        return done


@pytest.mark.parametrize("clients", [1, 2])
def test_closed_loop_window_ends_at_the_last_completion(clients):
    loop = harness.load_module(os.path.join(harness.BENCH_DIR, "traffic",
                                            "closed.py"))
    drv = FakeDriver(0.3)
    t0 = loop.drive(drv, {"clients": clients}, 1.0, seed=1)
    assert t0 == 100.0
    # sends at 0, 0.3, 0.6 and 0.9 s; none after the window's 1 s
    assert len(drv.records) == 4 * clients
    assert all(r.submitted < t0 + 1.0 for r in drv.records)
    assert max(r.done for r in drv.records) - t0 == pytest.approx(1.2)
    assert not drv.pending


def test_closed_loop_gives_up_on_a_request_that_never_returns():
    loop = harness.load_module(os.path.join(harness.BENCH_DIR, "traffic",
                                            "closed.py"))
    drv = FakeDriver(0.5)
    drv.collect = lambda: []                 # the reply never comes
    t0 = loop.drive(drv, {"clients": 1}, 1.0, seed=1)
    assert len(drv.records) == 1 and drv.records[0].done is None
    assert drv.clock() >= t0 + 1.0 + drv.answer_wait_s


def test_poisson_due_times_come_from_the_seed():
    loop = harness.load_module(os.path.join(harness.BENCH_DIR, "traffic",
                                            "poisson.py"))
    a = loop.due_offsets(500.0, 2.0, BIG_SEED)
    assert np.array_equal(a, loop.due_offsets(500.0, 2.0, BIG_SEED))
    assert len(a) == len(loop.due_offsets(500.0, 2.0, 5)) == 1000
    assert not np.array_equal(a, loop.due_offsets(500.0, 2.0, 5))
    assert (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] < 2.0


def test_poisson_latency_is_timed_from_the_due_time(monkeypatch):
    loop = harness.load_module(os.path.join(harness.BENCH_DIR, "traffic",
                                            "poisson.py"))
    drv = FakeDriver(0.05)
    monkeypatch.setattr(loop.time, "sleep", drv.advance)
    t0 = loop.drive(drv, {"rate_per_s": 100.0}, 1.0, seed=3)
    due = t0 + loop.due_offsets(100.0, 1.0, 3)
    assert [r.due for r in drv.records] == pytest.approx(list(due))
    assert all(r.submitted >= r.due for r in drv.records)
    assert any(r.submitted > r.due + 1e-9 for r in drv.records)
    run = harness.Run(setup_s=0.0, window_s=1.0,
                      records=drv.records, stats={}, work={}, peaks={})
    reader = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                              "p95_ms.py"))
    p95 = reader.read(run)
    from_due = [(r.done - r.due) * 1e3 for r in drv.records]
    assert p95 == pytest.approx(np.percentile(from_due, 95, method="higher"))
    assert p95 > np.percentile([(r.done - r.submitted) * 1e3
                                for r in drv.records], 95, method="higher")
    assert len(drv.records) == 100
    for r in drv.records[:4]:             # 4 of 100 missing: below the p95
        r.error = "failed"
    assert math.isfinite(reader.read(run))
    drv.records[4].error = "failed"       # 5 of 100: the p95 is missing
    assert math.isinf(reader.read(run))


def _run_entry(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bfs.graph500-s14.seq",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_entry_point_refuses_to_run_without_a_tpu():
    out = _run_entry(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_entry_point_refuses_to_run_without_the_program(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _run_entry(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_driver_notes_slow_calls_with_their_cpu_time():
    import time

    class Sleepy:
        def step(self):
            time.sleep(0.1)

    drv = harness.Driver(Sleepy(), work=None, op="spmv")
    drv.step()
    time.sleep(0.2)
    drv.step()
    slow = drv.slow
    assert [s[0] for s in slow[:3]] == ["between", "step", "step"]
    first, second = sorted(slow[1:3], key=lambda s: s[1])
    assert all(wall >= 0.1 and cpu < 0.05 and proc < 0.05   # asleep
               for _, _, wall, cpu, proc in (first, second))
    assert first[1] < slow[0][1] < second[1]   # when each began
    for _ in range(2 * harness.SLOW_KEPT):
        drv.step()
    assert len(drv.slow) == harness.SLOW_KEPT
    assert drv.slow[0][0] == "between" and drv.slow[0][2] >= 0.2
