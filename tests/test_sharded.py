"""Multi-device sharded SELL execution tests.

Two layers of coverage.  The in-process tests exercise the serial fallback
(mesh=None: the same per-shard kernels and combiners, folded on one device)
plus the shard-layout invariants — uneven row splits, shards whose union
buckets are pure padding, boundary-column windows.  The subprocess tests
re-exec under ``XLA_FLAGS=--xla_force_host_platform_device_count={2,4}`` (the
flag must never leak into this process — see conftest) and assert the
sharded spmm/bfs/pagerank paths match single-device execution to 1e-10,
through both the ops/ExecSpec API and the registry+service stack.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.graphs import gen as G
from repro.kernels import ops, sell_shard
from repro.kernels.execspec import ExecSpec
from repro.sparse import formats as F

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

RNG = np.random.default_rng(11)


def _dense(csr: F.CSRMatrix) -> np.ndarray:
    out = np.zeros((csr.n_rows, csr.n_cols))
    for i in range(csr.n_rows):
        for j in range(csr.indptr[i], csr.indptr[i + 1]):
            out[i, csr.indices[j]] += csr.data[j]
    return out


# ---------------------------------------------------------------------------
# Shard layout invariants (in-process, single device)
# ---------------------------------------------------------------------------


def test_shard_row_ranges_covers_unevenly():
    lengths = np.array([40, 1, 1, 1, 1, 1, 1, 39], np.int64)
    ranges = F.shard_row_ranges(lengths, 3)
    # contiguous cover of [0, n)
    assert ranges[0][0] == 0 and ranges[-1][1] == len(lengths)
    for (a, b), (c, d) in zip(ranges, ranges[1:]):
        assert b == c and a <= b
    # nnz-weighted: the heavy head row does not drag half the matrix with it
    sums = [int(lengths[a:b].sum()) for a, b in ranges]
    assert max(sums) < lengths.sum()


@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_sharded_matvec_host_reference(n_shards):
    csr = F.random_csr(97, 97, 5.0, seed=3, skew=1.5)
    slabs = F.csr_to_sell_slabs(csr, c=16)
    sharded = F.shard_slabs(slabs, n_shards)
    assert sharded.n_shards == n_shards
    assert int(sharded.row_counts.sum()) >= csr.n_rows
    x = RNG.standard_normal(97)
    ref = _dense(csr) @ x
    np.testing.assert_allclose(sharded.matvec(x), ref, atol=1e-10)


def test_shard_handles_empty_device_buckets():
    """One dense row + a tail of near-empty rows: the union bucket set
    contains widths some shards never populate, so those shards carry
    PAD-only filler slabs — the kernels must treat them as no-ops."""
    n = 12
    indptr = [0]
    indices, data = [], []
    for i in range(n):
        deg = n if i == 0 else 1           # row 0 touches every column
        cols = np.arange(deg) if i == 0 else np.array([i])
        indices.extend(cols.tolist())
        data.extend((1.0 + 0.1 * i for _ in range(deg)))
        indptr.append(len(indices))
    csr = F.CSRMatrix(np.asarray(indptr, np.int64),
                      np.asarray(indices, np.int32),
                      np.asarray(data, np.float64), n)
    slabs = F.csr_to_sell_slabs(csr, c=4)
    sharded = F.shard_slabs(slabs, 4)
    x = RNG.standard_normal(n)
    ref = _dense(csr) @ x
    np.testing.assert_allclose(sharded.matvec(x), ref, atol=1e-10)
    y = np.asarray(sell_shard.spmm_sell_sharded(
        sharded, x[:, None], mesh=None, w_block=4, k_block=1))[:, 0]
    np.testing.assert_allclose(y, ref, atol=1e-10)


# ---------------------------------------------------------------------------
# Serial fallback == single-device kernels (in-process)
# ---------------------------------------------------------------------------


def test_spmm_sharded_serial_matches_unsharded():
    csr = F.random_csr(90, 90, 5.0, seed=5, skew=1.0)
    x = RNG.standard_normal((90, 4))
    ref = np.asarray(ops.spmm(csr, x, vl=16))
    slabs = F.csr_to_sell_slabs(csr, c=16)
    got = np.asarray(sell_shard.spmm_sell_sharded(
        F.shard_slabs(slabs, 3), x, mesh=None, w_block=8, k_block=4))
    np.testing.assert_allclose(got, ref, atol=1e-10)


def test_rhs_sharded_serial_matches_unsharded():
    csr = F.random_csr(64, 64, 4.0, seed=6)
    x = RNG.standard_normal((64, 32))
    ref = np.asarray(ops.spmm(csr, x, vl=16, k_block=4))
    slabs = F.csr_to_sell_slabs(csr, c=16)
    got = np.asarray(sell_shard.spmm_sell_rhs_sharded(
        slabs, x, mesh=None, w_block=8, k_block=4))
    np.testing.assert_allclose(got, ref, atol=1e-10)


def test_graph_sharded_serial_matches_unsharded():
    g = G.random_graph(n_nodes=72, avg_degree=4, seed=7)
    ref_bfs = np.asarray(ops.bfs(g, 0, vl=16))
    ref_pr = np.asarray(ops.pagerank(g, iters=12, vl=16))
    sg = G.shard_graph_slabs(g, c=16, n_shards=3, reverse=True)
    got_bfs = np.asarray(sell_shard.bfs_sell_sharded(sg, 0, mesh=None))
    got_pr = np.asarray(sell_shard.pagerank_sell_sharded(
        sg, np.asarray(g.out_degree, np.float64), iters=12, mesh=None))
    assert np.array_equal(got_bfs, ref_bfs)
    np.testing.assert_allclose(got_pr, ref_pr, atol=1e-10)


@pytest.mark.parametrize("n_shards", [2, 3])
def test_graph_sharded_serial_split_matches_single_device(n_shards):
    """Hub rows split into virtual rows inside their owner's shard: the
    serial fold (min for BFS, sum then damping for PageRank) equals the
    single-device split layout, and the host references."""
    import jax.numpy as jnp

    from repro.kernels import bfs as bfs_k
    from repro.kernels import pagerank as pr_k

    g = G.rmat_graph(n_nodes=128, avg_degree=5, seed=9)
    adj = np.concatenate([g.adj, np.full((128, 1), G.PAD, np.int32)], axis=1)
    adj[1:, -1] = 0                           # node 0: in-degree >= 127
    g = G.EllpackGraph(adj=adj, n_nodes=128)
    slabs = G.graph_to_sell_slabs(g, c=16, reverse=True)
    assert G.split_stats(slabs)["split_nodes"] >= 1
    dev = (tuple(jnp.asarray(a) for a in slabs.bucket_adj),
           tuple(jnp.asarray(m) for m in slabs.bucket_nodes))
    deg = jnp.asarray(g.out_degree, jnp.float64)
    sources = [0, 5, 77]
    ref_bfs = np.asarray(bfs_k.bfs_sell(*dev, g.n_nodes, sources))
    ref_pr = np.asarray(pr_k.pagerank_sell(
        *dev, deg, g.n_nodes, damping=[0.85, 0.7], iters=[10, 6]))
    sg = G.shard_graph_slabs(g, c=16, n_shards=n_shards, reverse=True)
    got_bfs = np.asarray(sell_shard.bfs_sell_sharded(sg, sources, mesh=None))
    got_pr = np.asarray(sell_shard.pagerank_sell_sharded(
        sg, deg, damping=[0.85, 0.7], iters=[10, 6], mesh=None))
    assert np.array_equal(got_bfs, ref_bfs)
    np.testing.assert_array_equal(
        ref_bfs, np.stack([G.bfs_reference(g, s) for s in sources], axis=1))
    np.testing.assert_allclose(got_pr, ref_pr, atol=1e-12)
    np.testing.assert_allclose(
        ref_pr[:, 1], G.pagerank_reference(g, damping=0.7, iters=6),
        rtol=1e-10)


def test_ops_placement_one_is_single_device():
    """placement=1 resolves to the empty mesh: the plain resident path."""
    csr = F.random_csr(50, 50, 4.0, seed=8)
    x = RNG.standard_normal(50)
    ref = np.asarray(ops.spmv(csr, x, vl=16))
    got = np.asarray(ops.spmv(csr, x, spec=ExecSpec(vl=16, placement=1)))
    np.testing.assert_allclose(got, ref, atol=1e-10)


def test_device_mesh_insufficient_devices_raises():
    import jax

    have = jax.device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        sell_shard.device_mesh(have + 1)


# ---------------------------------------------------------------------------
# Real meshes (subprocess re-exec at forced host device counts)
# ---------------------------------------------------------------------------


def _run_worker(code: str, n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    if payload.get("skip"):
        pytest.skip(payload["skip"])
    return payload


WORKER_COMMON = textwrap.dedent(
    """
    import json
    import numpy as np
    import jax
    jax.config.update("jax_enable_x64", True)
    N = {n}
    if jax.device_count() < N:
        print(json.dumps({{"skip": f"backend exposes {{jax.device_count()}} "
                                   f"devices, test needs {{N}}"}}))
        raise SystemExit(0)
    from repro.graphs import gen as G
    from repro.kernels import ops
    from repro.kernels.execspec import ExecSpec
    from repro.sparse import formats as F
    rng = np.random.default_rng(0)

    def with_hub(g, hub):
        # an edge from every other node to ``hub``: an in-degree above
        # the split width, so the hub's row is split into virtual rows
        n = g.n_nodes
        adj = np.concatenate([g.adj, np.full((n, 1), G.PAD, np.int32)], 1)
        adj[np.arange(n) != hub, -1] = hub
        return G.EllpackGraph(adj=adj, n_nodes=n)
    """
)


@pytest.mark.parametrize("n_devices", [2, 4])
def test_sharded_ops_match_single_device(n_devices):
    code = WORKER_COMMON.format(n=n_devices) + textwrap.dedent(
        """
        # uneven: skewed row lengths + a row count not divisible by N
        csr = F.random_csr(131, 131, 5.0, seed=1, skew=1.5)
        x = rng.standard_normal(131)
        xb = rng.standard_normal((131, 8))
        g = with_hub(G.random_graph(n_nodes=90, avg_degree=4, seed=2), 7)
        sg = ops._shard_graph_cached(g, 16, None, N, None)
        assert G.split_stats(sg)["split_nodes"] == 1
        spec = ExecSpec(vl=16, placement=N)
        gspec = ExecSpec(vl=16, placement=N, layout="sell")
        errs = {
            "spmv": float(np.abs(np.asarray(ops.spmv(csr, x, spec=spec))
                                 - np.asarray(ops.spmv(csr, x, vl=16))).max()),
            "spmm": float(np.abs(np.asarray(ops.spmm(csr, xb, spec=spec))
                                 - np.asarray(ops.spmm(csr, xb, vl=16))).max()),
            "pagerank": float(np.abs(
                np.asarray(ops.pagerank(g, iters=10, spec=gspec))
                - np.asarray(ops.pagerank(g, iters=10, vl=16))).max()),
            "bfs": float(np.abs(
                np.asarray(ops.bfs(g, 3, spec=gspec)).astype(np.int64)
                - np.asarray(ops.bfs(g, 3, vl=16)).astype(np.int64)).max()),
            "bfs_host": float(np.abs(
                np.asarray(ops.bfs(g, [3, 7], spec=gspec)).astype(np.int64)
                - np.stack([G.bfs_reference(g, s) for s in (3, 7)],
                           axis=1)).max()),
            "pagerank_host": float(np.abs(
                np.asarray(ops.pagerank(g, iters=10, spec=gspec))
                - G.pagerank_reference(g, iters=10)).max()),
        }
        # empty per-device buckets: 10 rows, one dense, over N devices
        small = F.random_csr(10, 10, 1.2, seed=3, skew=2.0)
        xs = rng.standard_normal(10)
        errs["empty_buckets"] = float(np.abs(
            np.asarray(ops.spmv(small, xs, spec=ExecSpec(vl=4, placement=N)))
            - np.asarray(ops.spmv(small, xs, vl=4))).max())
        # RHS sharding kicks in when k >> k_block
        wide = rng.standard_normal((131, 8 * N))
        errs["rhs_shard"] = float(np.abs(
            np.asarray(ops.spmm(csr, wide,
                                spec=ExecSpec(vl=16, k_block=4, placement=N)))
            - np.asarray(ops.spmm(csr, wide, vl=16, k_block=4))).max())
        print(json.dumps(errs))
        """
    )
    errs = _run_worker(code, n_devices)
    for name, err in errs.items():
        assert err <= 1e-10, f"{name}: {err} at {n_devices} devices"


@pytest.mark.parametrize("n_devices", [2, 4])
def test_sharded_service_matches_single_device(n_devices):
    code = WORKER_COMMON.format(n=n_devices) + textwrap.dedent(
        """
        from repro.service import (KernelRegistry, KernelService,
                                   SubmitRequest, TuneCache)
        csr = F.random_csr(101, 101, 5.0, seed=4, skew=1.0)
        g = with_hub(G.random_graph(n_nodes=80, avg_degree=4, seed=5), 2)
        xs = [rng.standard_normal(101) for _ in range(3)]

        def serve(mesh):
            reg = KernelRegistry(cache=TuneCache(), mesh=mesh)
            reg.register_matrix("mat", csr)
            reg.register_graph("graph", g)
            svc = KernelService(reg)
            rids = [svc.submit(SubmitRequest(op="spmv", operand="mat",
                                             payload=x)) for x in xs]
            rb = svc.submit("bfs", "graph", source=2)
            rp = svc.submit("pagerank", "graph", damping=0.9, iters=10)
            svc.drain()
            return ([np.asarray(svc.poll(r)) for r in rids],
                    np.asarray(svc.poll(rb)), np.asarray(svc.poll(rp)), svc)

        ys1, bfs1, pr1, _ = serve(None)
        ysN, bfsN, prN, svc = serve(N)
        assert svc.registry.get("mat").mode == "sharded"
        assert svc.registry.get("graph").mode == "sharded"
        assert svc.registry.get("graph").split["split_nodes"] == 1
        assert svc.stats["sharded_launches"] >= 2, svc.stats
        print(json.dumps({
            "spmv": max(float(np.abs(a - b).max())
                        for a, b in zip(ys1, ysN)),
            "bfs": float(np.abs(bfs1.astype(np.int64)
                                - bfsN.astype(np.int64)).max()),
            "pagerank": float(np.abs(pr1 - prN).max()),
        }))
        """
    )
    errs = _run_worker(code, n_devices)
    for name, err in errs.items():
        assert err <= 1e-10, f"{name}: {err} at {n_devices} devices"
