"""How the benchmark decides ``correct``: each configuration's control
fails its limits, and a run whose timed path is broken underneath reads
``correct`` false, for every fault its cell can have.

Both run on the CPU at sizes a test run holds (Graph500 scale 8–10,
HPCG's matrix on an 8^3 grid); the chip readings the limits were set from
are in ``PERF.md``.  The harness's look for a chip is skipped; everything
else of a run is driven as on the chip.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

SEEDS = (1, 12345, 2**31 + 77)
SMALL = {"graph500-s14": {"scale": 8},
         "hpcg-24": {"nx": 8, "ny": 8, "nz": 8}}


def small_cell(name, traffic=None, **overrides):
    """Cell ``name`` at test size; ``traffic`` drives it with another mix
    of ``bench/traffic/`` (the open loop, which no cell uses yet)."""
    c = harness.find_cell(name)
    c.config = {**c.config, **SMALL[c.config["name"]], **overrides}
    if traffic is not None:
        c.traffic = harness.read_json(os.path.join(
            harness.BENCH_DIR, "traffic", traffic + ".json"))
    return c


def failing(checks):
    return [c["name"] for c in checks
            if c["limit"] is None or not c["value"] <= c["limit"]]


@pytest.mark.parametrize("name", ["bfs.graph500-s14.seq",
                                  "pagerank.graph500-s14.seq",
                                  "spmv.hpcg-24.clients8"])
def test_control_fails_the_limits(name):
    c = small_cell(name, **({"scale": 10} if "graph500" in name else {}))
    for seed in SEEDS:
        work = harness.workload(c, seed)
        idx = [i for i in range(1 << 12) if work.keep(c.op, i)][:16]
        assert failing(work.check(c.op, work.control(c.op, idx))), seed


def _spmv(fault):
    from repro.kernels import sell_core

    orig = sell_core.spmm_sell

    def broken(cols, vals, rows, x, **kw):
        if fault == "unchanged":
            return x
        y = orig(cols, vals, rows, x, **kw)
        if fault == "altered":
            return y.at[0, :].add(1.0)
        half = max(1, y.shape[1] // 2)          # half the batch left out,
        mean = y[:, :half].mean(axis=1, keepdims=True)   # the mean in it
        return y.at[:, half:].set(np.broadcast_to(mean, y[:, half:].shape))
    return sell_core, "spmm_sell", broken


def _bfs(fault):
    from repro.kernels import bfs

    if fault == "unchanged":
        return bfs, "bfs_step_sell", lambda adj, nodes, dist, level, **kw: dist
    orig = bfs.bfs_sell

    def altered(*a, **kw):
        d = orig(*a, **kw)
        return d.at[0].set(d[0] ^ 1)
    return bfs, "bfs_sell", altered


def _pagerank(fault):
    from repro.kernels import pagerank

    if fault == "unchanged":
        return (pagerank, "pagerank_step_sell",
                lambda radj, nodes, contrib, consts, **kw: contrib)
    orig = pagerank.pagerank_sell

    def altered(*a, **kw):
        r = orig(*a, **kw)
        return r.at[0].multiply(1.01)
    return pagerank, "pagerank_sell", altered


BREAK = {"bfs": _bfs, "pagerank": _pagerank, "spmv": _spmv}
CASES = [
    ("bfs.graph500-s14.seq", None, ["unchanged", "altered"]),
    ("pagerank.graph500-s14.seq", None, ["unchanged", "altered"]),
    ("spmv.hpcg-24.clients8", "spmv.steady",
     ["unchanged", "half_batch", "altered"]),
    ("spmv.hpcg-24.clients8", None, ["unchanged", "half_batch", "altered"]),
]


@pytest.mark.parametrize("name,traffic,fault", [
    (n, t, f) for n, t, fs in CASES for f in [None] + fs])
def test_a_broken_timed_path_reads_not_correct(name, traffic, fault,
                                               monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    c = small_cell(name, traffic)
    if c.traffic["loop"] == "poisson":
        # enough load that the service groups requests, as on the chip
        c.traffic = {**c.traffic, "rate_per_s": 2000}

    def break_path(svc):
        if fault is not None:
            monkeypatch.setattr(*BREAK[c.op](fault))

    monkeypatch.setattr(harness, "find_cell", lambda _name: c)
    line, _ = harness.run(name, 2**31 + 5, 1.0, False, t_start=0.0,
                          require_tpu=False, break_path=break_path)
    assert line["attempted"] > 0
    assert line["correct"] is (fault is None), line["checks"]
