"""Multi-device sharded SELL execution: one SPMD program per kernel family.

The paper's thesis — longer effective vectors tolerate memory latency on
sparse workloads — scales out the same way it scales up: row-partitioning
the SELL slabs across devices puts more lanes in flight per launch, with
the cross-device combine playing the role the paper's long-vector gather
plays within one core.  This module is the device-parallel face of
:mod:`repro.kernels.sell_core`:

* :func:`spmm_sell_sharded` — row-sharded SpMM over a
  :class:`repro.sparse.formats.ShardedSlabs` partition: each device runs
  the resident bucket schedule on its own slab block against a
  ``window_cols``-wide slice of the replicated RHS (the boundary-column
  gather), and the per-device row blocks concatenate into Y — rows are
  disjoint, so no reduction collective is needed.
* :func:`spmm_sell_rhs_sharded` — the k ≫ k_block path: slabs replicate,
  the RHS *columns* shard, every device computes all rows for its column
  slice (no collectives at all).
* :func:`bfs_sell_sharded` / :func:`pagerank_sell_sharded` — graph drivers
  whose per-level step runs each device's bucketed node step on its owned
  node range against the replicated state, then combines: BFS unions
  frontiers with ``pmin`` (an update only ever lowers INF to a level),
  PageRank adds the pull-sums with ``psum`` (each node's virtual rows
  live on its owner, zeros elsewhere) and damps them after.

All mesh plumbing goes through :mod:`repro.compat` (``shard_map``,
``MeshContext``, ``make_mesh``); with no concrete multi-device mesh every
entry point degrades to a serial per-shard loop with the identical
combine, so the sharded structure is testable (and bit-identical) on one
device.  CPU CI builds an N-device mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.compat import MeshContext, concrete_mesh, jaxshim, make_mesh
from repro.compat.jaxshim import NamedSharding, P
from repro.graphs.gen import ShardedGraphSlabs
from repro.kernels import sell_core
from repro.kernels.backend import float_dtype, resolve_interpret
from repro.kernels.bfs import INF, _bfs_sell_step_kernel
from repro.kernels.pagerank import (
    _pr_sell_step_kernel,
    broadcast_configs,
    damped,
)
from repro.obs import trace as obs_trace
from repro.sparse.formats import SellSlabs, ShardedSlabs

#: the canonical 1-D mesh axis name for SELL sharding
SHARD_AXIS = "shard"

__all__ = [
    "SHARD_AXIS",
    "bfs_sell_sharded",
    "device_mesh",
    "pagerank_sell_sharded",
    "place",
    "spmm_sell_rhs_sharded",
    "spmm_sell_sharded",
]


def device_mesh(n_devices: int, devices=None) -> MeshContext:
    """A 1-D ``(n_devices,)`` mesh over the first visible devices.

    ``n_devices <= 1`` returns the null context (single-device execution,
    no mesh plumbing).  On CPU, more host devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — which must be
    exported before jax initializes, hence the subprocess re-exec in
    ``tests/test_sharded.py``.
    """
    n = int(n_devices)
    if n <= 1:
        return MeshContext(None)
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"placement asks for {n} devices but only {len(devs)} are "
            "visible; on CPU export XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before jax initializes")
    return MeshContext(make_mesh((n,), (SHARD_AXIS,), devices=devs[:n]))


def _shard_map(f, mesh, in_specs, out_specs):
    """compat ``shard_map`` with output-replication checking off: the
    graph combines produce replicated outputs *via collectives*, which the
    static checker cannot always prove."""
    return jaxshim.shard_map(f, mesh, in_specs, out_specs, check_vma=False)


def _as_mesh(mesh):
    """Concrete multi-device Mesh from a Mesh / MeshContext / None."""
    if isinstance(mesh, MeshContext):
        mesh = mesh.mesh
    return concrete_mesh(mesh)


def place(sharded, mesh):
    """Put each shard's slabs on its own device, once.

    ``sharded`` is a :class:`ShardedSlabs` or :class:`ShardedGraphSlabs`
    of host arrays stacked along the device axis; the returned copy holds
    every per-shard array as a ``NamedSharding`` over the mesh axis, so
    shard d's block lives on device d and a call stages nothing through
    one device.  Without a concrete multi-device mesh it is returned
    unchanged (the serial path reads host arrays).
    """
    m, axis = _mesh_axis(mesh, sharded.n_shards)
    if m is None:
        return sharded
    on_shards = NamedSharding(m, P(axis))

    def put(arrays):
        return tuple(jax.device_put(a, on_shards) for a in arrays)

    if isinstance(sharded, ShardedGraphSlabs):
        return dataclasses.replace(
            sharded, bucket_adj=put(sharded.bucket_adj),
            bucket_nodes=put(sharded.bucket_nodes))
    return dataclasses.replace(
        sharded, bucket_cols=put(sharded.bucket_cols),
        bucket_vals=put(sharded.bucket_vals),
        bucket_rows=put(sharded.bucket_rows),
        col_starts=jax.device_put(
            np.asarray(sharded.col_starts, np.int32), on_shards))


def _mesh_axis(mesh, n_shards: int):
    """(concrete mesh or None, axis name): validate a 1-D n_shards mesh."""
    m = _as_mesh(mesh)
    if m is None:
        return None, None
    shape = dict(m.shape)
    if len(shape) != 1:
        raise ValueError(
            f"sharded SELL execution expects a 1-D mesh, got axes {shape}")
    axis, size = next(iter(shape.items()))
    if int(size) != int(n_shards):
        raise ValueError(
            f"mesh axis {axis!r} has {size} devices but the operand is "
            f"partitioned into {n_shards} shards")
    return m, axis


# ---------------------------------------------------------------------------
# Row-sharded SpMM
# ---------------------------------------------------------------------------


def spmm_sell_sharded(
    sharded: ShardedSlabs,
    x: jnp.ndarray,
    *,
    mesh=None,
    w_block: int = 8,
    k_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Y = A @ X with A row-partitioned across a device mesh.

    Each shard runs the resident bucket schedule of
    :func:`repro.kernels.sell_core.spmm_sell` on its own slab block,
    gathering only its ``window_cols``-wide slice of the replicated X
    (``jax.lax.dynamic_slice`` at the per-device ``col_starts`` — the
    boundary-column gather).  Row ranges are disjoint, so the per-device
    outputs concatenate; no reduction collective runs.  Without a concrete
    multi-device mesh the same per-shard program runs serially, so results
    are identical at any device count.  Slabs already :func:`place`-d on
    the mesh are used where they live, and the program compiles once per
    operand layout and RHS shape.
    """
    m, axis = _mesh_axis(mesh, sharded.n_shards)
    return _row_sharded_spmm(
        tuple(jnp.asarray(b) for b in sharded.bucket_cols),
        tuple(jnp.asarray(b) for b in sharded.bucket_vals),
        tuple(jnp.asarray(b) for b in sharded.bucket_rows),
        jnp.asarray(sharded.col_starts, jnp.int32), jnp.asarray(x),
        mesh=m, axis=axis, row_counts=tuple(int(r) for r in
                                            sharded.row_counts),
        window=int(sharded.window_cols), w_block=w_block, k_block=k_block,
        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "row_counts", "window", "w_block", "k_block",
    "interpret"))
def _row_sharded_spmm(cols_t, vals_t, rows_t, starts, x, *, mesh, axis,
                      row_counts, window, w_block, k_block, interpret):
    k = int(x.shape[1])
    nsh = len(row_counts)
    kp = sell_core.k_tile_for(k, k_block)
    xk = sell_core.padded_k(k, k_block)
    if k != xk:
        x = jnp.pad(x, ((0, 0), (0, xk - k)))
    rows_max = max(row_counts)
    dtype = vals_t[0].dtype if vals_t else x.dtype
    lanes = sell_core.lane_width(cols_t[0].shape[-1]) if cols_t else 1

    def local(cols, vals, rows, start, xg):
        xw = jax.lax.dynamic_slice_in_dim(xg, start, window, axis=0)
        xt = sell_core.lane_table(xw.astype(dtype), lanes)
        y = jnp.zeros((rows_max + 1, xk), dtype)   # +1 local dump slot
        for cb, vb, rb in zip(cols, vals, rows):
            yb = sell_core.spmm_bucket(
                cb, vb, xt, w_block=w_block, k_tile=kp, interpret=interpret)
            y = y.at[rb.reshape(-1)].set(yb)
        return y

    if mesh is None:
        out = jnp.stack([
            local(tuple(b[d] for b in cols_t), tuple(b[d] for b in vals_t),
                  tuple(b[d] for b in rows_t), starts[d], x)
            for d in range(nsh)
        ])
    else:
        def body(cols, vals, rows, st, xg):
            return local(
                tuple(b[0] for b in cols), tuple(b[0] for b in vals),
                tuple(b[0] for b in rows), st[0], xg)[None]

        out = _shard_map(
            body, mesh,
            (P(axis), P(axis), P(axis), P(axis), P()),
            P(axis),
        )(cols_t, vals_t, rows_t, starts, x)

    pieces = [out[d, :row_counts[d]] for d in range(nsh)]
    return jnp.concatenate(pieces, axis=0)[:sum(row_counts), :k]


def spmm_sell_rhs_sharded(
    slabs: SellSlabs,
    x: jnp.ndarray,
    *,
    mesh=None,
    w_block: int = 8,
    k_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Y = A @ X with the RHS *columns* sharded: the k ≫ k_block path.

    The slabs replicate (every device holds the whole operand) and each
    device runs the full resident schedule on its slice of k columns —
    column blocks are independent, so there are no collectives at all.
    The k axis pads to ``n_devices * k_tile`` so every device receives
    whole RHS tiles.  Degrades to plain :func:`sell_core.spmm_sell`
    without a concrete multi-device mesh.
    """
    interpret = resolve_interpret(interpret)
    m = _as_mesh(mesh)
    args = (
        tuple(jnp.asarray(b) for b in slabs.bucket_cols),
        tuple(jnp.asarray(b) for b in slabs.bucket_vals),
        tuple(jnp.asarray(b) for b in slabs.bucket_rows),
        jnp.asarray(x),
    )
    if m is None:
        return sell_core.spmm_sell(
            *args, n_rows=slabs.n_rows, w_block=w_block,
            k_block=k_block, interpret=interpret)
    shape = dict(m.shape)
    if len(shape) != 1:
        raise ValueError(
            f"sharded SELL execution expects a 1-D mesh, got axes {shape}")
    return _rhs_sharded_spmm(
        *args, mesh=m, axis=next(iter(shape)), n_rows=slabs.n_rows,
        w_block=w_block, k_block=k_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "n_rows", "w_block", "k_block", "interpret"))
def _rhs_sharded_spmm(cols_t, vals_t, rows_t, x, *, mesh, axis, n_rows,
                      w_block, k_block, interpret):
    k = int(x.shape[1])
    n = int(mesh.shape[axis])
    kp = sell_core.k_tile_for(k, k_block)
    xk = n * kp * (-(-k // (n * kp)))          # whole k tiles per device
    if k != xk:
        x = jnp.pad(x, ((0, 0), (0, xk - k)))
    dtype = vals_t[0].dtype if vals_t else x.dtype
    lanes = sell_core.lane_width(cols_t[0].shape[-1]) if cols_t else 1

    def body(cols, vals, rows, xb):
        y = jnp.zeros((n_rows + 1, xb.shape[1]), dtype)
        xt = sell_core.lane_table(xb.astype(dtype), lanes)
        for cb, vb, rb in zip(cols, vals, rows):
            yb = sell_core.spmm_bucket(
                cb, vb, xt, w_block=w_block, k_tile=kp, interpret=interpret)
            y = y.at[rb.reshape(-1)].set(yb)
        return y

    out = _shard_map(
        body, mesh, (P(), P(), P(), P(None, axis)), P(None, axis),
    )(cols_t, vals_t, rows_t, x)
    return out[:n_rows, :k]


# ---------------------------------------------------------------------------
# Graph drivers: per-device node step + collective combine
# ---------------------------------------------------------------------------


def _graph_step_fn(sg: ShardedGraphSlabs, mesh, kernel, combine: str,
                   interpret: bool | None, finish=None):
    """Build ``step(state, scalars, out_init, consts=None) -> combined
    state``.

    The per-device program is :func:`sell_core.bucketed_node_step` over the
    shard's buckets (``scalars`` to the kernel's SMEM, None for none) —
    identical to the single-device drivers, with the scatter that matches
    the collective (``min`` for ``"pmin"``, ``add`` for ``"psum"``) —
    followed by the cross-device combine and, when given,
    ``finish(combined, consts)`` in the same program.  Serially (no
    concrete mesh) the matching element-wise op folds over shards, so both
    paths compute the same values.  The step compiles once per layout and
    state shape, not once per level.
    """
    m, axis = _mesh_axis(mesh, sg.n_shards)
    adj_t = tuple(jnp.asarray(b) for b in sg.bucket_adj)
    nodes_t = tuple(jnp.asarray(b) for b in sg.bucket_nodes)
    interpret = resolve_interpret(interpret)

    def step(state, scalars, out_init, consts=None):
        return _node_step_program(
            adj_t, nodes_t, state, scalars, out_init, consts, kernel=kernel,
            mesh=m, axis=axis, combine=combine, finish=finish,
            interpret=interpret)

    return step


#: collective -> (node scatter, element-wise op of the serial fold)
_COMBINES = {"pmin": ("min", jnp.minimum), "psum": ("add", jnp.add)}


@functools.partial(jax.jit, static_argnames=(
    "kernel", "mesh", "axis", "combine", "finish", "interpret"))
def _node_step_program(adj_t, nodes_t, state, scalars, out_init, consts, *,
                       kernel, mesh, axis, combine, finish, interpret):
    scatter, fold = _COMBINES[combine]
    if mesh is None:
        acc = out_init
        for d in range(adj_t[0].shape[0] if adj_t else 0):
            part = sell_core.bucketed_node_step(
                kernel, tuple(b[d] for b in adj_t),
                tuple(b[d] for b in nodes_t), state, scalars, out_init,
                combine=scatter, interpret=interpret)
            acc = part if d == 0 else fold(acc, part)
    else:
        # scalars ride into the shard_map only when the kernel takes them
        scal = () if scalars is None else (scalars,)

        def body(adjs, nodeses, state, out_init, *scal):
            part = sell_core.bucketed_node_step(
                kernel, tuple(b[0] for b in adjs),
                tuple(b[0] for b in nodeses), state,
                scal[0] if scal else None, out_init, combine=scatter,
                interpret=interpret)
            return getattr(jax.lax, combine)(part, axis)

        acc = _shard_map(
            body, mesh, (P(axis), P(axis), P(), P()) + (P(),) * len(scal),
            P(),
        )(adj_t, nodes_t, state, out_init, *scal)
    return acc if finish is None else finish(acc, consts)


def bfs_sell_sharded(
    sg: ShardedGraphSlabs,
    source,
    *,
    mesh=None,
    max_levels: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """BFS over node-partitioned SELL adjacency: frontier union by ``pmin``.

    Each device advances its owned nodes against the replicated distance
    state; a device's output keeps the old distance for nodes it does not
    own, and an update only ever lowers INF to the current level, so the
    element-wise minimum across devices IS the frontier union.  Same
    contract as :func:`repro.kernels.bfs.bfs_sell` (scalar source ->
    (n,), k sources -> (n, k)).
    """
    n = sg.n_nodes
    sources = np.atleast_1d(np.asarray(source, np.int64))
    k = len(sources)
    dist = jnp.full((n + 1, k), INF, jnp.int32)
    dist = dist.at[jnp.asarray(sources), jnp.arange(k)].set(0)
    step = _graph_step_fn(sg, mesh, _bfs_sell_step_kernel, "pmin", interpret)
    for level in range(1, (max_levels or n) + 1):
        with obs_trace.phase("graph.level"):
            new = step(dist, jnp.array([level], jnp.int32), dist)
            new = new.at[-1].set(INF)          # keep the dump slot inert
            with obs_trace.phase("graph.converge"):
                settled = bool(jnp.all(new == dist))
        if settled:
            break
        dist = new
    return dist[:n, 0] if np.ndim(source) == 0 else dist[:n]


def pagerank_sell_sharded(
    sg: ShardedGraphSlabs,
    out_degree: jnp.ndarray,
    *,
    mesh=None,
    damping=0.85,
    iters=20,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """PageRank over node-partitioned reverse adjacency: rank exchange by
    ``psum``.

    Each device adds the pull-sums of its owned nodes' virtual rows into
    zeros; every node is owned by one device, so the cross-device sum
    assembles the full pull-sums — the rank-exchange collective — which
    the same program damps into the replicated iterate.  Same contract as
    :func:`repro.kernels.pagerank.pagerank_sell` (scalar config -> (n,),
    broadcast (damping, iters) columns -> (n, k)).
    """
    n = sg.n_nodes
    scalar = np.ndim(damping) == 0 and np.ndim(iters) == 0
    dtype = float_dtype()
    step = _graph_step_fn(sg, mesh, _pr_sell_step_kernel, "psum", interpret,
                          finish=damped)
    dampings, iters_arr = broadcast_configs(damping, iters)
    k = len(dampings)
    rank = jnp.full((n, k), 1.0 / n, dtype)
    deg = jnp.asarray(out_degree).astype(dtype)[:, None]
    d = jnp.asarray(dampings, dtype)
    zero_row = jnp.zeros((1, k), dtype)
    for t in range(1, int(iters_arr.max()) + 1):
        with obs_trace.phase("graph.level"):
            contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
            dangling = jnp.sum(jnp.where(deg == 0, rank, 0.0), axis=0)
            consts = jnp.stack([(1.0 - d) / n, d, dangling / n]).astype(dtype)
            state = jnp.concatenate([contrib, zero_row])
            new = step(state, None, jnp.zeros_like(state), consts)[:n]
            active = jnp.asarray(t <= iters_arr)
            rank = jnp.where(active[None, :], new, rank)
    return rank[:, 0] if scalar else rank
