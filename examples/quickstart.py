"""Quickstart: the paper in five minutes.

Runs the four kernels (SpMV/BFS/PageRank/FFT) against their oracles at
several vector lengths, then reproduces the paper's two headline numbers
through the SDV machine model:

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache
from repro.core import MachineParams, SDVMachine, VectorConfig
from repro.core.sweep import latency_sweep, slowdown_tables
from repro.core.traffic import TRACE_BUILDERS
from repro.graphs import gen as G
from repro.kernels import ops
from repro.sparse import formats as F


def kernels_demo():
    print("=== Pallas kernels (interpret mode) vs oracles ===")
    m = F.random_csr(1000, 1000, 8.0, seed=0)
    x = np.random.default_rng(0).standard_normal(1000)
    for vl in (8, 64, 256):
        y = ops.spmv(m, x, vl=vl)
        err = np.abs(np.asarray(y) - m.matvec(x)).max()
        print(f"  spmv  vl={vl:<4d} max|err| = {err:.2e}")

    sig = np.random.default_rng(1).standard_normal(2048)
    fr, fi = ops.fft(sig)
    want = np.fft.fft(sig)
    print(f"  fft   n=2048  max|err| = {np.abs(np.asarray(fr)[0]-want.real).max():.2e}")

    g = G.random_graph(n_nodes=1024, avg_degree=8, seed=2)
    d = ops.bfs(g, 0, vl=128)
    print(f"  bfs   match reference: {np.array_equal(d, G.bfs_reference(g, 0))}")

    pr = ops.pagerank(g, iters=15, vl=128)
    err = np.abs(pr - G.pagerank_reference(g, iters=15)).max()
    print(f"  pagerank  max|err| = {err:.2e}, sum = {pr.sum():.6f}")


def paper_numbers():
    print("\n=== Paper claims through the SDV machine model ===")
    tables = slowdown_tables(latency_sweep())
    spmv = tables["spmv"]
    print("  SpMV slowdown at +32 cycles:  scalar "
          f"{spmv[1][32]:.2f}x (paper 1.22x) | vl256 {spmv[256][32]:.2f}x (paper 1.05x)")
    print("  SpMV slowdown at +1024 cycles: scalar "
          f"{spmv[1][1024]:.2f}x (paper 8.78x) | vl256 {spmv[256][1024]:.2f}x (paper 3.39x)")

    machine = SDVMachine(MachineParams())
    print("\n  absolute cycles (SpMV, CAGE10-like):")
    for vl in (1, 8, 64, 256):
        run = machine.run(TRACE_BUILDERS["spmv"](VectorConfig(vl=vl)))
        label = "scalar" if vl == 1 else f"vl{vl}"
        print(f"    {label:>6}: {run.cycles:12.0f} cycles "
              f"({run.mem_instructions:.0f} mem instructions)")


if __name__ == "__main__":
    enable_compile_cache()
    kernels_demo()
    paper_numbers()
