"""BFS frontier-expansion Pallas kernel (paper §3.1, Vizcaino [13]).

Gather-only ("bottom-up") level-synchronous step: one grid step examines a
block of ``vl`` nodes, DMAs their padded adjacency rows into VMEM, gathers
the distances of all neighbors in one indexed access, and flags nodes whose
any neighbor sits on the current frontier.  Scatter-free by construction —
the long-vector formulation of frontier expansion (the paper's top-down
variant needs vector scatter; bottom-up keeps the same traffic class with
TPU-friendly semantics).

The SELL variants are thin drivers over the batched execution core
(:mod:`repro.kernels.sell_core`): the frontier state is a stacked
(n + 1, k) column matrix — one column per BFS source — read through the
core's in-VMEM lane gather, and only the combine op (``any neighbor on the
previous level``) lives here.  The per-bucket launch + scatter loop is
:func:`sell_core.bucketed_node_step`, shared with PageRank.

Grid: (n_nodes / vl,).  The dist array stays VMEM-resident (2^15 nodes =
128 KiB of i32), adjacency streams through.  Node counts that do not divide
``vl`` are padded internally (and the pad trimmed from the result).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import sell_core
from repro.kernels.backend import resolve_interpret
from repro.obs import trace as obs_trace
from repro.sparse.formats import SUBLANES

PAD = -1
INF = np.iinfo(np.int32).max


def _bfs_step_kernel(adj_ref, dist_ref, level_ref, out_ref, *, vl: int):
    i = pl.program_id(0)
    level = level_ref[0]
    adj = adj_ref[...]                        # (vl, width)
    mask = adj != PAD
    safe = jnp.where(mask, adj, 0)
    nd = dist_ref[safe]                       # gather neighbor distances
    hit = jnp.any(jnp.where(mask, nd == level - 1, False), axis=1)
    mine = jax.lax.dynamic_slice(dist_ref[...], (i * vl,), (vl,))
    out_ref[...] = jnp.where((mine == INF) & hit, level, mine)


@functools.partial(jax.jit, static_argnames=("vl", "interpret"))
def bfs_step(
    adj: jnp.ndarray,
    dist: jnp.ndarray,
    level: jnp.ndarray,
    *,
    vl: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One bottom-up BFS level over ELLPACK adjacency (n, width).

    ``level`` is a (1,) int32 array; returns the updated (n,) distances.
    ``n`` need not divide ``vl``: the node block is padded with PAD rows
    (distance INF, never hit) and the pad is trimmed from the result.
    """
    n, width = adj.shape
    if n % vl:
        pad = vl - n % vl
        adj = jnp.pad(adj, ((0, pad), (0, 0)), constant_values=PAD)
        dist = jnp.pad(dist, (0, pad), constant_values=INF)
    n_pad = adj.shape[0]
    grid = (n_pad // vl,)
    kernel = functools.partial(_bfs_step_kernel, vl=vl)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((vl, width), lambda i: (i, 0)),
            pl.BlockSpec(dist.shape, lambda i: (0,)),       # resident
            pl.BlockSpec(level.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((vl,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), dist.dtype),
        interpret=resolve_interpret(interpret),
    )(adj, dist, level)
    return out[:n]


def _bfs_sell_step_kernel(adj_ref, nodes_ref, dist_ref, level_ref, out_ref):
    """The BFS combine op: any in-neighbor on the previous level.

    One output row per stacked source column kk of the (k, R, lanes)
    distance table: a node still at INF joins the frontier at ``level``
    when any of its in-neighbors sits at ``level - 1``.  Each virtual row
    of a split node yields either its owner's distance or ``level``, so
    the min-combining scatter of :func:`bfs_step_sell` is exact.
    """
    level = level_ref[0]
    width, c = adj_ref.shape[1:]
    rows = min(width, SUBLANES)

    def column(kk, carry):
        def block(b, hit):
            adj = adj_ref[0, pl.ds(b * rows, rows), :]
            mask = adj != PAD
            nd = sell_core.gather(dist_ref, kk, jnp.where(mask, adj, 0))
            on_frontier = (mask & (nd == level - 1)).astype(jnp.int32)
            return jnp.maximum(hit, jnp.max(on_frontier, axis=0,
                                            keepdims=True))

        hit = jax.lax.fori_loop(0, width // rows, block,
                                jnp.zeros((1, c), jnp.int32))
        mine = sell_core.gather(dist_ref, kk, nodes_ref[0])  # via sigma-sort
        out_ref[0, pl.ds(kk, 1), :] = jnp.where(
            (mine == INF) & (hit > 0), level, mine)
        return carry

    jax.lax.fori_loop(0, dist_ref.shape[0], column, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bfs_step_sell(
    bucket_adj: tuple[jnp.ndarray, ...],
    bucket_nodes: tuple[jnp.ndarray, ...],
    dist: jnp.ndarray,
    level: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One bottom-up level over width-bucketed, degree-sorted adjacency.

    ``dist`` is (n + 1,) for a single source or (n + 1, k) for k stacked
    sources (the dump slot stays INF); returns the updated copy with the
    same shape.  One launch set advances every column; the virtual rows
    of a split node merge by minimum.
    """
    cols = dist if dist.ndim == 2 else dist[:, None]
    out = sell_core.bucketed_node_step(
        _bfs_sell_step_kernel, bucket_adj, bucket_nodes,
        cols, level, cols, combine="min", interpret=interpret,
    )
    out = out.at[-1].set(INF)                 # keep the dump slot inert
    return out if dist.ndim == 2 else out[:, 0]


def bfs_sell(
    bucket_adj: tuple[jnp.ndarray, ...],
    bucket_nodes: tuple[jnp.ndarray, ...],
    n_nodes: int,
    source,
    *,
    max_levels: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Full BFS over bucketed SELL adjacency, batched over sources.

    ``source`` may be one node id or a sequence of k ids: the frontiers
    become RHS columns and every level is one launch set for the whole
    batch.  Returns (n_nodes,) distances for a scalar source, (n_nodes, k)
    — one column per source — for a sequence.  Columns that converge early
    stay fixed while the rest keep expanding.  Each level is a
    ``graph.level`` phase; its blocking read of whether anything changed
    is ``graph.converge``.
    """
    sources = np.atleast_1d(np.asarray(source, np.int64))
    k = len(sources)
    dist = jnp.full((n_nodes + 1, k), INF, jnp.int32)
    dist = dist.at[jnp.asarray(sources), jnp.arange(k)].set(0)
    max_levels = max_levels or n_nodes
    for level in range(1, max_levels + 1):
        with obs_trace.phase("graph.level"):
            new = bfs_step_sell(
                bucket_adj, bucket_nodes, dist,
                jnp.array([level], jnp.int32), interpret=interpret,
            )
            with obs_trace.phase("graph.converge"):
                settled = bool(jnp.all(new == dist))
        if settled:
            break
        dist = new
    return dist[:n_nodes, 0] if np.ndim(source) == 0 else dist[:n_nodes]


def levels_run(dist) -> int:
    """Level steps :func:`bfs_sell` ran to reach the host distances
    ``dist``: one per level that reached a vertex, plus the last, which
    found nothing new."""
    dist = np.asarray(dist)
    return int(np.max(dist, where=dist < INF, initial=0)) + 1


def bfs(
    adj: jnp.ndarray,
    source: int,
    *,
    vl: int = 256,
    max_levels: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Full BFS: fixed-point iteration of :func:`bfs_step`.

    Runs level-synchronous steps until no distance changes (checked on host,
    as the FPGA driver does) or ``max_levels`` is hit.
    """
    n = adj.shape[0]
    # pad once here, not once per level inside bfs_step (which would copy
    # the whole adjacency every iteration of the fixed point)
    if n % vl:
        adj = jnp.pad(adj, ((0, vl - n % vl), (0, 0)), constant_values=PAD)
    dist = jnp.full((adj.shape[0],), INF, jnp.int32).at[source].set(0)
    max_levels = max_levels or n
    for level in range(1, max_levels + 1):
        new = bfs_step(adj, dist, jnp.array([level], jnp.int32), vl=vl, interpret=interpret)
        if bool(jnp.all(new == dist)):
            break
        dist = new
    return dist[:n]
