"""Kernel wall-time microbenchmarks (CPU interpret mode vs jnp oracle).

Wall time in interpret mode is NOT a TPU performance statement (the roofline
section covers that); this table proves the kernels run and tracks the
oracle's cost as a sanity ratio.  CSV: name, us_per_call, derived.

``collect()`` returns the same rows as machine-readable dicts (including the
measured pad_factor where the row has one) for ``BENCH_kernels.json``.
"""
import time

import numpy as np

import jax

from benchmarks import bench_roofline
from repro.analysis.launchplan import LaunchPlanError
from repro.graphs import gen as G
from repro.kernels import ops, ref
from repro.sparse import formats as F

import jax.numpy as jnp


def _time(fn, *args, reps=3):
    fn(*args)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def rows():
    """Yield (name, us_per_call, meta_dict); meta is the derived column."""
    m = F.random_csr(2000, 2000, 10.0, seed=0)
    ell = F.csr_to_ellpack(m, c=128)
    x = np.random.default_rng(0).standard_normal(2000)
    cols, vals, xj = jnp.asarray(ell.cols), jnp.asarray(ell.vals), jnp.asarray(x)
    t_kernel = _time(lambda: ops.spmv(ell, x, vl=128))
    t_ref = _time(lambda: ref.spmv_ref(cols, vals, xj, m.n_rows))
    yield ("spmv_vl128_interpret", t_kernel,
           {"oracle_us": round(t_ref), "pad_factor": round(ell.pad_factor, 4)})

    # The SELL-C-sigma payoff: a skewed row-length distribution where the
    # uniform-width layout pays the global max per row and the bucketed
    # slabs pay only their sigma-window widths.
    skew = F.random_csr(2000, 2000, 8.0, seed=3, skew=1.2)
    ell_s = F.csr_to_ellpack(skew, c=128)
    slabs = F.csr_to_sell_slabs(skew, c=128, sigma=1024)
    xs = np.random.default_rng(1).standard_normal(2000)
    t_ell = _time(lambda: ops.spmv(ell_s, xs, vl=128))
    yield ("spmv_skew_ellpack_vl128", t_ell,
           {"pad_factor": round(ell_s.pad_factor, 4)})
    t_sell = _time(lambda: ops.spmv(slabs, xs, vl=128))
    yield ("spmv_skew_sell_slabs_vl128", t_sell,
           {"pad_factor": round(slabs.pad_factor, 4), "n_buckets": slabs.n_buckets})

    # Out-of-VMEM streaming SpMM: the same in-VMEM operand through both
    # schedules (the slowdown gates the double-buffered pipeline's overlap),
    # then a giant operand whose resident plan the preflight rejects —
    # streaming is the ONLY way it runs.  The rejection is set by the RHS
    # length (a million columns), so the row count is cut to 4096: interpret
    # mode emulates one lane-gather pass per 128 columns of every 8-row index
    # block, and at a million rows that took 13 minutes.  Single rep.
    sq = F.random_csr(4096, 4096, 8.0, seed=5)
    slabs_sq = F.csr_to_sell_slabs(sq, c=128, sigma=1024)
    xk = np.random.default_rng(2).standard_normal((4096, 8))
    t_res = _time(lambda: ops.spmm(slabs_sq, xk, vl=128, mode="resident"))
    yield ("spmm_4k_k8_resident", t_res,
           {"pad_factor": round(slabs_sq.pad_factor, 4)})
    t_str = _time(lambda: ops.spmm(slabs_sq, xk, vl=128, mode="stream"))
    yield ("spmm_4k_k8_stream", t_str,
           # streaming/resident throughput >= 0.7 <=> slowdown <= 1/0.7
           {"stream_slowdown": round(t_str / t_res, 3),
            "stream_vs_resident_throughput": round(t_res / t_str, 3)})

    giant = F.random_csr(4096, 1 << 20, 4.0, seed=9)
    slabs_g = F.csr_to_sell_slabs(giant, c=512, sigma=4096)
    xg = np.random.default_rng(3).standard_normal((1 << 20, 8))
    try:
        ops.spmm(slabs_g, xg, vl=512, mode="resident")
        accepted = 1                 # the honest-footprint model regressed
    except LaunchPlanError:
        accepted = 0                 # the operand streaming exists for
    t_g = _time(lambda: ops.spmm(slabs_g, xg, vl=512, mode="stream"), reps=1)
    model = bench_roofline.spmm_stream_terms(
        4096, 1 << 20, giant.nnz, 8, c=512,
        pad_factor=slabs_g.pad_factor)
    yield ("spmm_1m_cols_k8_stream", t_g,
           {"resident_plan_accepted": accepted,
            "pad_factor": round(slabs_g.pad_factor, 4),
            "modeled_overlap_speedup": round(model["overlap_speedup"], 3),
            "modeled_dominant": model["dominant"]})
    del giant, slabs_g, xg           # O(100 MB) of host arrays

    sig = np.random.default_rng(1).standard_normal((8, 2048))
    t_kernel = _time(lambda: ops.fft(sig))
    wre, wim = ref.fft_twiddles(2048)
    sr, si = jnp.asarray(sig), jnp.zeros_like(jnp.asarray(sig))
    t_ref = _time(lambda: ref.fft_stockham_ref(sr, si, wre, wim))
    yield ("fft2048_b8_interpret", t_kernel, {"oracle_us": round(t_ref)})

    g = G.random_graph(n_nodes=2048, avg_degree=8, seed=2)
    t_kernel = _time(lambda: ops.bfs(g, 0, vl=256), reps=1)
    yield ("bfs_2k_nodes_full_run", t_kernel, {"edges": g.n_edges})

    t_kernel = _time(lambda: ops.bfs(g, 0, vl=256, layout="sell"), reps=1)
    yield ("bfs_2k_nodes_sell", t_kernel, {"edges": g.n_edges})

    t_kernel = _time(lambda: ops.pagerank(g, iters=5, vl=256), reps=1)
    yield ("pagerank_2k_5iter", t_kernel, {"edges": g.n_edges})

    t_kernel = _time(lambda: ops.pagerank(g, iters=5, vl=256, layout="sell"), reps=1)
    yield ("pagerank_2k_5iter_sell", t_kernel, {"edges": g.n_edges})


def collect() -> dict:
    """name -> {us_per_call, ...meta} for machine-readable emission."""
    return {
        name: {"us_per_call": round(us, 1), **meta} for name, us, meta in rows()
    }


def campaign_records(table: dict | None = None) -> list[dict]:
    """The microbench table in the BENCH_sweeps.json record schema, so the
    measured wall times can be stored next to modeled campaign cycles (see
    ``repro.core.campaign.CampaignResult.records``)."""
    table = table if table is not None else collect()
    records = []
    for name, entry in table.items():
        kernel = next((k for k in ("pagerank", "spmv", "bfs", "fft")
                       if name.startswith(k)), name.split("_", 1)[0])
        vl = next((int(tok[2:]) for tok in name.split("_") if
                   tok.startswith("vl") and tok[2:].isdigit()), 256)
        rec = {
            "campaign": "bench-kernels",
            "machine": "pallas-interpret",
            "kernel": kernel,
            "vl": vl,
            "extra_latency": 0,
            "bw_limit": 0.0,
            "us_per_call": entry["us_per_call"],
            "problem": name,
            "source": "measured-interpret",
        }
        if "pad_factor" in entry:
            rec["pad_factor"] = entry["pad_factor"]
        records.append(rec)
    return records


def main(precomputed: dict | None = None):
    table = precomputed if precomputed is not None else collect()
    for name, entry in table.items():
        extras = ",".join(f"{k}={v}" for k, v in entry.items() if k != "us_per_call")
        print(f"{name},{entry['us_per_call']:.0f},{extras}")


if __name__ == "__main__":
    main()
