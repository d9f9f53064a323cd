#!/usr/bin/env python3
"""Find the knee of an open loop once, on the chip: the highest
offered rate whose backlog does not grow.

    python3 bench/sweep.py --workload spmv.hpcg-24.clients8 \
        --traffic spmv.steady --seed 1 --seconds 5 --rates 200,400,800

For each rate, in one process after one set-up, runs the Poisson loop of
the mix ``--traffic`` (default: the cell's own) on the cell's operand
for ``--seconds`` and prints one JSON line: offered and completed
requests, the backlog when the last request was due and at its half, the
p50/p95/p99 latency from the due time, and how late the generator ran.  The
benchmark's own runs never call this; ``PERF.md`` records what it found.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--traffic", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import harness
    from repro.service import KernelRegistry, KernelService

    cell = harness.find_cell(args.workload)
    if args.traffic is not None:
        cell.traffic = harness.read_json(os.path.join(
            harness.BENCH_DIR, "traffic", args.traffic + ".json"))
    harness.check_device(cell.chips, require_tpu=True)
    harness.enable_cache()
    work = harness.workload(cell, args.seed)
    registry = KernelRegistry()
    work.register(registry)
    svc = KernelService(registry, n_slots=int(cell.traffic["slots"]))
    harness.warmup(svc, work, cell)
    loop = harness.load_module(os.path.join(harness.BENCH_DIR, "traffic",
                                            "poisson.py"))
    for rate in [float(r) for r in args.rates.split(",")]:
        driver = harness.Driver(svc, work, cell.op)
        half, last = {}, {}
        step = driver.step

        def stepping():
            """Note the backlog when the window is half over and when its
            last request is due, then step."""
            t = driver.clock() - t0
            key = half if t < args.seconds else last
            if t >= args.seconds / 2 and not key:
                key["pending"] = len(driver.pending)
            step()

        driver.step = stepping
        t0 = driver.clock()
        loop.drive(driver, {**cell.traffic, "rate_per_s": rate},
                   args.seconds, args.seed)
        lat = np.array([(r.done - r.due) * 1e3 for r in driver.records
                        if r.ok])
        late = np.array([r.submitted - r.due for r in driver.records])
        print(json.dumps({
            "rate_per_s": rate, "offered": len(driver.records),
            "completed": int(len(lat)),
            "backlog_half": half.get("pending", 0),
            "backlog_end": last.get("pending", 0),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "late_max_ms": float(late.max() * 1e3),
            "drain_s": max(r.done for r in driver.records) - t0
            - args.seconds}), flush=True)
        time.sleep(0.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
