"""The main-path kernels compile for a TPU v5e, with no chip attached.

JAX describes a ``v5e:2x2`` topology and the TPU compiler, which is
installed with JAX, compiles each kernel for it: what Mosaic refuses
(gathers it cannot lower, blocks off the (8, 128) tiling, more VMEM than
the scoped limit) fails here, at no chip time.  Shapes are the ones
``chip_smoke.py`` and the benchmark serve: the CAGE10-like SpMV at k = 1 and k = 8, the
million-row streamed SpMM, BFS and PageRank steps on an R-MAT graph, the
Stockham FFT, NPB FT class C's 512^3 transforms, the MoE combine at
Mixtral-8x7B widths, and the row-sharded SpMM on a four-device mesh.
Nothing runs: each test only checks that the compiled program holds the
kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every
test-runner worker imports this file.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.graphs import gen as G
from repro.kernels import bfs as bfs_k
from repro.kernels import fft as fft_k
from repro.kernels import ops, sell_core, sell_shard
from repro.kernels import pagerank as pr_k
from repro.sparse import formats as F

#: R-MAT scale of the compiled graph steps: the largest chip_smoke serves
GRAPH_NODES = 1 << 18


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def cage10():
    slabs, tuned = ops.pack_tuned(F.cage10_like(seed=0))
    return slabs, tuned


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _slab_specs(slabs, sharding):
    return (
        tuple(_spec(a.shape, jnp.int32, sharding) for a in slabs.bucket_cols),
        tuple(_spec(a.shape, jnp.float32, sharding) for a in slabs.bucket_vals),
        tuple(_spec(a.shape, jnp.int32, sharding) for a in slabs.bucket_rows),
    )


def _assert_kernel(lowered):
    with jax.enable_x64(False):
        text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1, 8])
def test_spmm_sell_compiles(topo, one_chip, quiet_cache, cage10, k):
    slabs, tuned = cage10
    with jax.enable_x64(False):
        lowered = sell_core.spmm_sell.lower(
            *_slab_specs(slabs, one_chip),
            _spec((slabs.n_cols, k), jnp.float32, one_chip),
            n_rows=slabs.n_rows, w_block=tuned.w_block,
            k_block=tuned.k_block, interpret=False)
    _assert_kernel(lowered)


@pytest.fixture(scope="module")
def stream1m():
    """The million-row operand as the registry lays it out: its resident
    plan at the 8-column tile of a coalesced group exceeds VMEM, so it
    registers on the streaming schedule."""
    from repro.service import KernelRegistry

    csr = F.random_csr(1 << 20, 1 << 20, 4.0, seed=9, dtype=np.float32)
    rec = KernelRegistry().register_matrix("stream1m", csr)
    assert rec.mode == "stream"
    return rec


def test_spmm_sell_stream_compiles(topo, one_chip, quiet_cache, stream1m):
    slabs, tuned = stream1m.slabs, stream1m.tuned
    with jax.enable_x64(False):
        lowered = sell_core.spmm_sell_stream.lower(
            *_slab_specs(slabs, one_chip),
            _spec((slabs.n_cols, 8), jnp.float32, one_chip),
            n_rows=slabs.n_rows, w_block=tuned.w_block,
            k_block=tuned.k_block, col_tile=tuned.col_tile,
            row_tile=tuned.row_tile, interpret=False)
    _assert_kernel(lowered)


@pytest.fixture(scope="module")
def rmat_slabs():
    from repro.service import KernelRegistry

    g = G.rmat_graph(GRAPH_NODES, avg_degree=16, seed=0)
    return KernelRegistry().register_graph("g", g).slabs


@pytest.mark.parametrize("step", ["bfs", "pagerank"])
def test_graph_steps_compile(topo, one_chip, quiet_cache, rmat_slabs, step):
    slabs = rmat_slabs
    n = slabs.n_nodes
    adj = tuple(_spec(a.shape, jnp.int32, one_chip) for a in slabs.bucket_adj)
    nodes = tuple(_spec(a.shape, jnp.int32, one_chip)
                  for a in slabs.bucket_nodes)
    with jax.enable_x64(False):
        if step == "bfs":
            lowered = bfs_k.bfs_step_sell.lower(
                adj, nodes, _spec((n + 1, 8), jnp.int32, one_chip),
                _spec((1,), jnp.int32, one_chip), interpret=False)
        else:
            lowered = pr_k.pagerank_step_sell.lower(
                adj, nodes, _spec((n + 1,), jnp.float32, one_chip),
                _spec((3,), jnp.float32, one_chip), interpret=False)
    _assert_kernel(lowered)


def test_moe_dispatch_compiles(topo, one_chip, quiet_cache):
    """The MoE combine at Mixtral-8x7B widths: 512 token rows of top-2
    routing weights against 1280 expert slots of d_model = 4096, packed at
    C = 128 with the service's 64-column RHS tile."""
    from repro.service.service import _moe_k_block

    rng = np.random.default_rng(0)
    n_tok, n_slots, d = 512, 1280, 4096
    routing = F.CSRMatrix(
        indptr=np.arange(n_tok + 1, dtype=np.int64) * 2,
        indices=rng.integers(0, n_slots, 2 * n_tok).astype(np.int32),
        data=rng.random(2 * n_tok).astype(np.float32), n_cols=n_slots)
    slabs = F.csr_to_sell_slabs(routing, c=128)
    with jax.enable_x64(False):
        lowered = sell_core.spmm_sell.lower(
            *_slab_specs(slabs, one_chip),
            _spec((n_slots, d), jnp.float32, one_chip),
            n_rows=n_tok, w_block=8, k_block=_moe_k_block(d),
            interpret=False)
    _assert_kernel(lowered)


@pytest.mark.parametrize("n,batch", [(2048, 64), (1 << 16, 8)])
def test_fft_compiles(topo, one_chip, quiet_cache, n, batch):
    stages = int(np.log2(n))
    sig = _spec((batch, n), jnp.float32, one_chip)
    tw = _spec((stages, n // 2), jnp.float32, one_chip)
    with jax.enable_x64(False):
        lowered = fft_k.fft_stockham.lower(
            sig, sig, tw, tw, b_block=8, interpret=False)
    _assert_kernel(lowered)


@pytest.mark.parametrize("program", ["spectrum", "step"])
def test_npb_ft_class_c_compiles(topo, one_chip, quiet_cache, program):
    """NPB FT class C at its 512^3 grid, on the plan's blocks: the forward
    transform of registration and the served step, each of whose
    temporaries (XLA's count) fits the 16 GB chip beside the resident
    spectrum."""
    from repro.analysis.preflight import HBM_BUDGET_BYTES, plan_ft

    plan = plan_ft((512, 512, 512), dtype="float32")
    field = 2 * 4 * 512 ** 3
    with jax.enable_x64(False):
        if program == "spectrum":
            plane = _spec((512, 512, 512), jnp.float32, one_chip)
            lowered = fft_k.ft_spectrum.lower(
                plane, plane, b_blocks=plan.b_blocks, interpret=False)
        else:
            plane = _spec((4, 512, 512, 128), jnp.float32, one_chip)
            lowered = fft_k.ft_step.lower(
                plane, plane, _spec((1,), jnp.float32, one_chip),
                b_blocks=plan.b_blocks, interpret=False)
        compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert field + temp <= plan.hbm_bytes <= HBM_BUDGET_BYTES


def test_sharded_spmm_compiles_on_four_chips(topo, quiet_cache, cage10):
    slabs, tuned = cage10
    sharded = F.shard_slabs(slabs, 4)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("shard",))
    per_shard = NamedSharding(mesh, PartitionSpec("shard"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def run(cols, vals, rows, starts, x):
        placed = dataclasses.replace(
            sharded, bucket_cols=cols, bucket_vals=vals, bucket_rows=rows,
            col_starts=starts)
        return sell_shard.spmm_sell_sharded(
            placed, x, mesh=mesh, w_block=tuned.w_block,
            k_block=tuned.k_block, interpret=False)

    with jax.enable_x64(False):
        lowered = jax.jit(run).lower(
            *_slab_specs(sharded, per_shard),
            _spec((4,), jnp.int32, per_shard),
            _spec((slabs.n_cols, 8), jnp.float32, replicated))
    _assert_kernel(lowered)
