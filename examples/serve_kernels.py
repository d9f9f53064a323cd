"""Serve the paper's sparse kernels as a system (README "Serving the kernels").

    PYTHONPATH=src python examples/serve_kernels.py [--cache tune.json]

Registers the cage10-like matrix, a random graph and an FFT plan, optionally
warm-starts the tune cache from a stored campaign cube, serves a small mixed
request batch through the micro-batching KernelService, and prints the cache
and scheduler statistics — the registry -> tune -> submit lifecycle in one
file.  x64 stays off, so on a TPU the operands are served in float32.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.graphs.gen import random_graph
from repro.service import KernelRegistry, KernelService, TuneCache
from repro.sparse.formats import cage10_like


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cache", default="BENCH_tunecache.json",
                    help="persistent TuneCache path")
    ap.add_argument("--sweeps", default="BENCH_sweeps.json",
                    help="campaign store to warm-start from (if present)")
    ap.add_argument("--requests", type=int, default=24)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cache = TuneCache(args.cache)
    if os.path.exists(args.sweeps):
        seeded = cache.warm_from_sweeps(args.sweeps)
        print(f"warm-started {seeded} (kernel, machine) hints from {args.sweeps}")

    reg = KernelRegistry(cache=cache)
    t0 = time.perf_counter()
    mat = reg.register_matrix("cage10", cage10_like(seed=0))
    print(f"cage10 registered in {mat.register_us / 1e3:.1f} ms "
          f"(tune cached: {mat.tune_was_cached}; "
          f"C={mat.tuned.c}, sigma={mat.tuned.sigma}, "
          f"w_block={mat.tuned.w_block}, pad={mat.pad_factor:.3f})")
    reg.register_graph("g", random_graph(n_nodes=1024, avg_degree=8, seed=1))
    reg.register_fft("fft1024", 1024)
    print(f"registry ready in {time.perf_counter() - t0:.2f} s: {reg.names()}")

    svc = KernelService(reg, n_slots=8)
    rng = np.random.default_rng(0)
    rids = []
    for i in range(args.requests):
        if i % 3 == 0:
            rids.append(svc.submit("spmv", "cage10",
                                   rng.standard_normal(11_397)))
        elif i % 3 == 1:
            rids.append(svc.submit("fft", "fft1024",
                                   rng.standard_normal((1, 1024))))
        else:
            rids.append(svc.submit("pagerank", "g", iters=2))
    t0 = time.perf_counter()
    svc.drain()
    wall = time.perf_counter() - t0
    assert all(svc.poll(r) is not None for r in rids)
    print(f"served {len(rids)} requests in {wall:.2f} s "
          f"({len(rids) / wall:.0f} req/s)")
    print(f"scheduler: {svc.stats}")
    print(f"cache: {cache.stats}")
    cache.save()
    print(f"saved {args.cache} — the next process will tune nothing")


if __name__ == "__main__":
    main()
