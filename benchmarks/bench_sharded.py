"""Sharded SELL execution benchmark: scaling curves over host device counts.

``XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
    python -m benchmarks.bench_sharded``

runs the sharded spmm / BFS / PageRank paths at mesh sizes {1, 2, 4} in ONE
process (the device-count flag must be exported before jax initializes; the
mesh for each row takes the first n of the forced host devices) and reports

* ``us_per_call`` per (op, device count) — interpret-mode wall times, NOT a
  hardware performance statement; the table exists so the sharded paths
  provably run end-to-end and their trends are diffable across PRs;
* ``mismatch`` — a zero-base counter gated by ``scripts/bench_compare.py``:
  1 when the sharded result drifts beyond 1e-10 from single-device
  execution, so a numerical regression fails CI even if timings look fine.

Results go to ``BENCH_sharded.json``; the committed baseline is
``benchmarks/BENCH_sharded_baseline.json`` (CI ``sharded-smoke`` job).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

TOL = 1e-10


def _build():
    from repro.graphs.gen import random_graph
    from repro.sparse import formats as F

    csr = F.random_csr(512, 512, 8.0, seed=0, skew=1.0)
    graph = random_graph(n_nodes=256, avg_degree=5, seed=1)
    rng = np.random.default_rng(2)
    xb = rng.standard_normal((512, 8))
    return csr, graph, xb


def _timed(fn, reps: int = 2):
    """(mean wall us, last result); one untimed warm-up call first so the
    row times execution, not tracing/compilation."""
    out = np.asarray(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = np.asarray(fn())
    return (time.perf_counter() - t0) / reps * 1e6, out


def collect(device_counts=(1, 2, 4)) -> dict:
    import jax

    from repro.kernels import ops
    from repro.kernels.execspec import ExecSpec

    csr, graph, xb = _build()
    have = jax.device_count()
    counts = [n for n in device_counts if n <= have]
    skipped = [n for n in device_counts if n > have]
    if skipped:
        print(f"# skipping device counts {skipped}: only {have} devices "
              "visible (export XLA_FLAGS=--xla_force_host_platform_"
              f"device_count={max(device_counts)})")

    refs: dict[str, np.ndarray] = {}
    table: dict[str, dict] = {}
    for n in counts:
        spec = ExecSpec(vl=16, placement=n)
        gspec = ExecSpec(vl=16, placement=n, layout="sell")
        rows = {
            "spmm": lambda: ops.spmm(csr, xb, spec=spec),
            "bfs": lambda: ops.bfs(graph, 0, spec=gspec),
            "pagerank": lambda: ops.pagerank(graph, iters=5, spec=gspec),
        }
        for op, fn in rows.items():
            us, out = _timed(fn)
            ref = refs.setdefault(op, out)       # d1 row is the reference
            err = float(np.abs(out.astype(np.float64)
                               - ref.astype(np.float64)).max())
            entry = {
                "us_per_call": round(us, 1),
                "n_devices": n,
                "mismatch": int(err > TOL),
                "max_abs_err": err,
            }
            base = table.get(f"{op}_sharded_d1")
            if base is not None:
                entry["speedup_vs_d1"] = round(
                    base["us_per_call"] / max(us, 1e-9), 2)
            table[f"{op}_sharded_d{n}"] = entry
    return table


def main(argv=None) -> None:
    import jax

    from repro.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_sharded.json",
                    help="machine-readable output path")
    args = ap.parse_args(argv)

    table = collect()
    print("# table: sharded execution (name,us_per_call,derived)")
    for name, entry in table.items():
        extras = ",".join(
            f"{k}={v}" for k, v in entry.items() if k != "us_per_call")
        print(f"{name},{entry['us_per_call']:.0f},{extras}")
    with open(args.json, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
