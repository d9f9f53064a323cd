#!/usr/bin/env python3
"""Bring-up run of the sparse-kernel service on a TPU.

    python3 chip_smoke.py             # one chip: every op through KernelService
    python3 chip_smoke.py --chips 4   # four chips: row-sharded SpMM, BFS and
                                      # PageRank, against the one-chip results

One process holds the chip for the whole run and drives the service the
way a user does: ``KernelRegistry.register_*`` -> ``KernelService.submit``
-> ``drain`` -> ``poll``, with every kernel compiled (``interpret`` off),
x64 off (the chip serves float32) and random operands made from fixed
seeds.  Every served result is compared with a plain reference —
``scipy.sparse`` products, ``repro.graphs.gen``'s BFS and PageRank,
``numpy.fft`` and a dense one-hot combine — at a float32 tolerance stated
next to it (BFS distances must match exactly).

The earlier lines report, per op, the compile seconds (trace + lower +
compile, from JAX's own monitoring events), the first-call and warm-call
seconds of the served request, and the error against the reference, then
the service's stats.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Without a TPU, or run from a directory without the repository, it exits
non-zero and prints no such line.  The persistent compile cache goes where
``repro.compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Operand sizes of one run (the chip run uses :data:`FULL`)."""

    stream_rows: int = 1 << 20          # spmm_1m_rows_k8_stream operand
    stream_cols: int = 1 << 20
    graph_scales: tuple = (18, 17)      # R-MAT scales, tried in order
    fft_sizes: tuple = (2048,)          # plus the largest the preflight takes
    fft_batch: int = 64
    moe_tokens: int = 512               # Mixtral-8x7B widths, one batch
    moe_d_model: int = 4096


FULL = Sizes()


class Compiles:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    monitoring events (one listener for the process)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


class Smoke:
    """One service, its registry and the per-op report lines."""

    def __init__(self, registry, compiles: Compiles):
        from repro.service import KernelService

        self.registry = registry
        self.compiles = compiles
        self.svc = KernelService(registry, n_slots=8)
        self.failures: list[str] = []
        if self.svc.interpret is not False:
            raise RuntimeError("the service resolved interpret mode on a TPU")

    def serve(self, requests):
        """Submit ``requests`` ((op, operand, payload, params) tuples) as one
        group, drain, and return (results, wall seconds, compile seconds)."""
        c0, t0 = self.compiles.seconds, time.perf_counter()
        rids = [self.svc.submit(op, name, payload, **params)
                for op, name, payload, params in requests]
        self.svc.drain()
        out = [self.svc.poll(r) for r in rids]
        for r in rids:
            self.svc.release(r)
        return out, time.perf_counter() - t0, self.compiles.seconds - c0

    def op(self, label, requests, check, *, warm: bool = True):
        """Serve ``requests`` cold (and once more warm), check the cold
        results, and print one report line."""
        out, first_s, compile_s = self.serve(requests)
        warm_s = self.serve(requests)[1] if warm else float("nan")
        err, tol, ok = check(out)
        print(f"[op] {label}: compile_s={compile_s:.3f} "
              f"first_call_s={first_s:.3f} warm_call_s={warm_s:.3f} "
              f"max_err={err:.3e} tol={tol:.1e} {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            self.failures.append(label)
        return out


# ---------------------------------------------------------------------------
# References and checks
# ---------------------------------------------------------------------------


def _scipy(csr):
    import scipy.sparse as sp

    return sp.csr_matrix(
        (csr.data.astype("float64"), csr.indices, csr.indptr),
        shape=(csr.n_rows, csr.n_cols))


def spmv_check(csr, xs):
    """Row-normalized error of each served y against A @ x in float64.

    Tolerance 1e-5: a float32 dot product of w terms is off by at most
    about w * 6e-8 of sum_j |a_ij x_j|, and the widest row here stores
    at most 32 entries (2e-6)."""
    import numpy as np

    a = _scipy(csr)
    x = np.stack(xs, axis=1).astype(np.float64)
    want = a @ x
    scale = abs(a) @ np.abs(x)

    def check(out):
        got = np.stack(out, axis=1)
        err = float((np.abs(got - want) / np.maximum(scale, 1e-30)).max())
        return err, 1e-5, bool(np.isfinite(got).all() and err <= 1e-5)
    return check


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def _register_graph(registry, sizes: Sizes, seed: int = 0):
    """The largest R-MAT scale (Graph500 A, B, C = 0.57, 0.19, 0.19, edge
    factor 16) whose launch plans the registry accepts."""
    from repro.analysis.launchplan import LaunchPlanError
    from repro.graphs import gen as G

    for scale in sizes.graph_scales:
        g = G.rmat_graph(1 << scale, avg_degree=16, seed=seed)
        try:
            rec = registry.register_graph(f"rmat{scale}", g)
        except LaunchPlanError as e:
            print(f"[graph] R-MAT scale {scale} ({g.n_edges} edges) refused "
                  f"by the preflight: {e}", flush=True)
            continue
        print(f"[graph] R-MAT scale {scale}: {g.n_nodes} nodes, "
              f"{g.n_edges} edges, C={rec.tuned.c}, "
              f"buckets={list(rec.slab_meta.widths)}, split={rec.split}",
              flush=True)
        return g, rec
    raise RuntimeError("no R-MAT scale fits the launch plans")


def _roots(g, k: int = 8, seed: int = 0):
    import numpy as np

    live = np.nonzero(g.out_degree > 0)[0]
    return [int(v) for v in
            np.random.default_rng(seed).choice(live, k, replace=False)]


def graph_phase(smoke: Smoke, g, name: str, tag: str = "",
                warm: bool = True):
    """BFS from 8 roots (one coalesced drive) and one PageRank."""
    import numpy as np

    from repro.graphs import gen as G

    roots = _roots(g)
    want = [G.bfs_reference(g, r) for r in roots]

    def bfs_check(out):
        bad = sum(int((np.asarray(o) != w).sum()) for o, w in zip(out, want))
        return float(bad), 0.0, bad == 0      # distances are exact

    bfs = smoke.op(f"bfs{tag} {name} 8 roots",
                   [("bfs", name, None, {"source": r}) for r in roots],
                   bfs_check, warm=warm)
    ref = G.pagerank_reference(g, damping=0.85, iters=20)

    def pr_check(out):
        """L1 distance to the float64 ranks (which sum to 1).  Tolerance
        1e-4: float32 pull sums over up to 2^15 in-neighbours, 20 steps;
        the expected error is about 1e-6."""
        err = float(np.abs(np.asarray(out[0], np.float64) - ref).sum())
        return err, 1e-4, bool(err <= 1e-4)

    pr = smoke.op(f"pagerank{tag} {name} d=0.85 iters=20",
                  [("pagerank", name, None, {"damping": 0.85, "iters": 20})],
                  pr_check, warm=warm)
    return bfs, pr


def one_chip(compiles: Compiles, sizes: Sizes) -> Smoke:
    import numpy as np

    from repro.analysis.preflight import plan_fft_stockham
    from repro.service import KernelRegistry
    from repro.sparse import formats as F

    registry = KernelRegistry()
    smoke = Smoke(registry, compiles)
    rng = np.random.default_rng(0)

    # SpMV on the paper's input: one request alone, then 8 coalesced (k=8)
    cage = F.cage10_like(seed=0, dtype=np.float32)
    rec = registry.register_matrix("cage10", cage)
    print(f"[spmv] cage10-like {cage.n_rows}x{cage.n_cols} nnz={cage.nnz} "
          f"C={rec.tuned.c} k_block={rec.tuned.k_block} mode={rec.mode}",
          flush=True)
    xs = [rng.standard_normal(cage.n_cols).astype(np.float32)
          for _ in range(8)]
    smoke.op("spmv cage10 k=1", [("spmv", "cage10", xs[0], {})],
             spmv_check(cage, xs[:1]))
    launches = smoke.svc.stats["launches"]
    smoke.op("spmv cage10 k=8", [("spmv", "cage10", x, {}) for x in xs],
             spmv_check(cage, xs), warm=False)
    if smoke.svc.stats["launches"] - launches != 1:
        smoke.failures.append("spmv k=8 did not coalesce into one launch")

    # Streamed SpMM: the bench's million-row operand, registered as any
    # operand is.  Its resident plan, priced at the 8-column RHS tile a
    # coalesced group runs, needs more VMEM than the budget, so the
    # registry puts it on the streaming schedule by itself.
    big = F.random_csr(sizes.stream_rows, sizes.stream_cols, 4.0, seed=9,
                       dtype=np.float32)
    rec = registry.register_matrix("stream1m", big)
    print(f"[stream] random {big.n_rows}x{big.n_cols} nnz={big.nnz} "
          f"C={rec.tuned.c} k_block={rec.tuned.k_block} mode={rec.mode} "
          f"col_tile={rec.tuned.col_tile} row_tile={rec.tuned.row_tile}",
          flush=True)
    if rec.mode != "stream":
        smoke.failures.append(f"stream operand registered as {rec.mode}")
    xs = [rng.standard_normal(big.n_cols).astype(np.float32)
          for _ in range(8)]
    smoke.op("spmm stream1m k=8", [("spmv", "stream1m", x, {}) for x in xs],
             spmv_check(big, xs), warm=False)
    if smoke.svc.stats["streamed_launches"] < 1:
        smoke.failures.append("no streamed launch")
    del big, xs

    # BFS and PageRank on the largest accepted R-MAT graph
    g, rec = _register_graph(registry, sizes)
    graph_phase(smoke, g, rec.name)

    # FFT: n=2048 x 64 signals, and the largest n the one-block plan takes
    largest = next(1 << e for e in range(24, 0, -1) if plan_fft_stockham(
        1 << e, batch=8, dtype="float32").ok)
    for n, batch in [(n, sizes.fft_batch) for n in sizes.fft_sizes] + [
            (largest, 8)]:
        registry.register_fft(f"fft{n}", n)
        sig = rng.standard_normal((batch, n)).astype(np.float32)
        want = np.fft.fft(sig.astype(np.float64))

        def fft_check(out, want=want):
            """Relative 2-norm error.  Tolerance 1e-5: a float32 radix-2
            FFT's error grows like 6e-8 * log2(n)."""
            got = out[0][0] + 1j * out[0][1]
            err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            return err, 1e-5, bool(err <= 1e-5)

        smoke.op(f"fft n={n} batch={batch}",
                 [("fft", f"fft{n}", sig, {})], fft_check)

    # MoE combine at Mixtral-8x7B widths: 8 experts, top-2, 512 tokens
    moe_phase(smoke, sizes, rng)
    return smoke


def moe_phase(smoke: Smoke, sizes: Sizes, rng):
    import numpy as np

    from repro.configs import get_config

    moe = get_config("mixtral-8x7b").moe
    e, top_k, t, d = moe.n_experts, moe.top_k, sizes.moe_tokens, \
        sizes.moe_d_model
    cap = -(-t * top_k // e) * 5 // 4            # capacity factor 1.25
    n_slots = e * cap
    logits = rng.standard_normal((t, e))
    top = np.argsort(-logits, axis=1)[:, :top_k]
    w = np.take_along_axis(logits, top, axis=1)
    w = np.exp(w - w.max(axis=1, keepdims=True))
    w /= w.sum(axis=1, keepdims=True)            # renormalized top-k weights
    fill = np.zeros(e, np.int64)
    rows, cols, vals = [], [], []
    for tok in range(t):
        for j in range(top_k):
            ex = int(top[tok, j])
            if fill[ex] < cap:                   # drop beyond capacity
                rows.append(tok)
                cols.append(ex * cap + fill[ex])
                vals.append(w[tok, j])
                fill[ex] += 1
    indptr = np.zeros(t + 1, np.int64)
    np.add.at(indptr, np.asarray(rows) + 1, 1)
    indptr = np.cumsum(indptr)
    x = rng.standard_normal((n_slots, d)).astype(np.float32)
    payload = {"indptr": indptr, "indices": np.asarray(cols, np.int32),
               "data": np.asarray(vals, np.float32), "x": x}
    smoke.registry.register_moe("mixtral_combine", n_tokens=t,
                                n_slots=n_slots, d_model=d, top_k=top_k,
                                c=128)
    onehot = np.zeros((t, n_slots))
    onehot[rows, cols] = np.asarray(vals, np.float32)
    want = np.einsum("ts,sd->td", onehot, x.astype(np.float64))
    scale = np.einsum("ts,sd->td", np.abs(onehot), np.abs(x))

    def check(out):
        """Error relative to sum_s |r_ts x_sd|.  Tolerance 1e-5: each
        output sums at most top_k = 2 float32 products."""
        err = float((np.abs(out[0] - want) / np.maximum(scale, 1e-30)).max())
        return err, 1e-5, bool(err <= 1e-5)

    print(f"[moe] mixtral-8x7b combine: {t} tokens, {e} experts top-{top_k}, "
          f"{n_slots} slots, d_model={d}", flush=True)
    smoke.op("moe_dispatch mixtral-8x7b",
             [("moe_dispatch", "mixtral_combine", payload, {})], check)


def four_chips(compiles: Compiles, sizes: Sizes) -> list[Smoke]:
    """Row-sharded SpMM, BFS (``pmin``) and PageRank (``psum``) on a
    four-device mesh, each compared with the same op on one chip."""
    import numpy as np

    from repro.service import KernelRegistry
    from repro.sparse import formats as F

    cage = F.cage10_like(seed=0, dtype=np.float32)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(cage.n_cols).astype(np.float32)
          for _ in range(8)]
    single = Smoke(KernelRegistry(), compiles)
    sharded = Smoke(KernelRegistry(mesh=4), compiles)
    results = {}
    for tag, smoke in (("1chip", single), ("4chips", sharded)):
        rec = smoke.registry.register_matrix("cage10", cage)
        print(f"[{tag}] cage10 mode={rec.mode} C={rec.tuned.c}", flush=True)
        if rec.sharded is not None:
            for b, arr in enumerate(rec.sharded.bucket_cols):
                where = sorted({s.device.id for s in arr.addressable_shards})
                print(f"[4chips] cage10 bucket {b} slabs on devices {where}",
                      flush=True)
                if len(where) != 4:
                    smoke.failures.append(f"bucket {b} on devices {where}")
        y = smoke.op(f"spmv{'/' + tag} cage10 k=8",
                     [("spmv", "cage10", x, {}) for x in xs],
                     spmv_check(cage, xs), warm=False)
        g, grec = _register_graph(smoke.registry, sizes)
        if grec.sharded is not None:
            where = sorted({s.device.id for s in
                            grec.sharded.bucket_adj[0].addressable_shards})
            print(f"[4chips] {grec.name} adjacency on devices {where}",
                  flush=True)
            if len(where) != 4:
                smoke.failures.append(f"graph slabs on devices {where}")
        # the one-chip side is the comparison: served once, not timed warm
        bfs, pr = graph_phase(smoke, g, grec.name, f"/{tag}",
                              warm=rec.sharded is not None)
        results[tag] = (y, bfs, pr)
    (y1, b1, p1), (y4, b4, p4) = results["1chip"], results["4chips"]
    # Limits: each side is already within its reference tolerance (SpMV
    # 1e-5 of sum_j |a_ij x_j|, PageRank L1 1e-4), so the two may differ
    # by at most twice that; BFS distances must be identical.
    scale = abs(_scipy(cage)) @ np.abs(np.stack(xs, axis=1))
    spmv_diff = float((np.abs(np.stack(y1, axis=1) - np.stack(y4, axis=1))
                       / np.maximum(scale, 1e-30)).max())
    bfs_same = all(np.array_equal(a, b) for a, b in zip(b1, b4))
    pr_diff = float(np.abs(np.asarray(p1[0], np.float64)
                           - np.asarray(p4[0], np.float64)).sum())
    print(f"[compare] 4 chips vs 1 chip: spmv max row-normalized "
          f"diff={spmv_diff:.3e} (limit 2e-5) bfs identical={bfs_same} "
          f"pagerank L1={pr_diff:.3e} (limit 2e-4)", flush=True)
    if not bfs_same:
        sharded.failures.append("sharded BFS differs from one chip")
    if not spmv_diff <= 2e-5:
        sharded.failures.append(f"sharded SpMV differs by {spmv_diff:.3e}")
    if not pr_diff <= 2e-4:
        sharded.failures.append(f"sharded PageRank differs by {pr_diff:.3e}")
    if sharded.svc.stats["sharded_launches"] < 3:
        sharded.failures.append("sharded launches missing")
    return [single, sharded]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    if jax.config.jax_enable_x64:
        print("chip_smoke: x64 must stay off on the chip", file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    compiles = Compiles(jax)
    t0 = time.perf_counter()
    smokes = ([one_chip(compiles, FULL)] if args.chips == 1
              else four_chips(compiles, FULL))
    failures = []
    for smoke in smokes:
        stats = dict(smoke.svc.stats)
        print(f"[stats] {json.dumps(stats, sort_keys=True)}", flush=True)
        if stats["failed"] or stats["rejected"]:
            failures.append(f"{stats['failed']} failed / "
                            f"{stats['rejected']} rejected requests")
        failures += smoke.failures
    print(f"[done] {time.perf_counter() - t0:.1f} s, "
          f"{len(failures)} failures {failures}", flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
