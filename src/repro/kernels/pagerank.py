"""PageRank Pallas kernel (paper §3.1): pull-style gather-MAC power step.

Structurally the SpMV schedule on the reverse graph: one grid step pulls the
contributions of all in-neighbors of a ``vl``-node block with one indexed
gather per adjacency column tile and reduces them.  The contribution vector
(rank / out_degree) stays VMEM-resident; adjacency streams.

The SELL variants are thin drivers over the batched execution core
(:mod:`repro.kernels.sell_core`): the power iterate is a stacked (n + 1, k)
column matrix — one column per (damping, iters) configuration — read
through the core's in-VMEM lane gather, and only the combine op (the
pull-sum, added up over a split node's virtual rows) and the damping
epilogue live here.  The per-bucket launch + scatter loop is
:func:`sell_core.bucketed_node_step`, shared with BFS.

Grid: (n_nodes / vl,).  VL is the node-block width, exactly the paper's
knob.  Node counts that do not divide ``vl`` are padded internally (and the
pad trimmed from the result).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import sell_core
from repro.kernels.backend import float_dtype, resolve_interpret
from repro.obs import trace as obs_trace
from repro.sparse.formats import SUBLANES

PAD = -1


def _pr_step_kernel(radj_ref, contrib_ref, consts_ref, out_ref):
    radj = radj_ref[...]                      # (vl, width)
    mask = radj != PAD
    safe = jnp.where(mask, radj, 0)
    g = jnp.where(mask, contrib_ref[safe], 0.0)
    pulled = jnp.sum(g, axis=1)
    base, damping, dangling_term = consts_ref[0], consts_ref[1], consts_ref[2]
    out_ref[...] = base + damping * (pulled + dangling_term)


@functools.partial(jax.jit, static_argnames=("vl", "interpret"))
def pagerank_step(
    radj: jnp.ndarray,
    contrib: jnp.ndarray,
    consts: jnp.ndarray,
    *,
    vl: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One power-iteration step.

    ``consts`` = [(1-d)/n, d, dangling_mass/n] as a (3,) array of the rank
    dtype (kept in SMEM-like resident block).  ``n`` need not divide ``vl``:
    the node block is padded with PAD rows (zero contribution) and the pad
    is trimmed from the result.
    """
    n, width = radj.shape
    if n % vl:
        pad = vl - n % vl
        radj = jnp.pad(radj, ((0, pad), (0, 0)), constant_values=PAD)
        contrib = jnp.pad(contrib, (0, pad))
    n_pad = radj.shape[0]
    grid = (n_pad // vl,)
    out = pl.pallas_call(
        _pr_step_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((vl, width), lambda i: (i, 0)),
            pl.BlockSpec(contrib.shape, lambda i: (0,)),
            pl.BlockSpec(consts.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((vl,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), contrib.dtype),
        interpret=resolve_interpret(interpret),
    )(radj, contrib, consts)
    return out[:n]


def _pr_sell_step_kernel(radj_ref, nodes_ref, contrib_ref, out_ref):
    """The PageRank combine op: the pull-sum of one virtual row.

    One output row per stacked (damping, iters) column kk of the
    (k, R, lanes) contribution table.  A split node's rows are summed by
    the scatter, and the damping applied after it (:func:`damped`).
    """
    del nodes_ref                             # pull-only: no own-state gather
    width, c = radj_ref.shape[1:]
    rows = min(width, SUBLANES)

    def column(kk, carry):
        def block(b, acc):
            radj = radj_ref[0, pl.ds(b * rows, rows), :]
            mask = radj != PAD
            got = sell_core.gather(contrib_ref, kk, jnp.where(mask, radj, 0))
            return acc + jnp.sum(jnp.where(mask, got, 0.0), axis=0,
                                 keepdims=True)

        out_ref[0, pl.ds(kk, 1), :] = jax.lax.fori_loop(
            0, width // rows, block, jnp.zeros((1, c), out_ref.dtype))
        return carry

    jax.lax.fori_loop(0, contrib_ref.shape[0], column, 0)


def damped(pulled: jnp.ndarray, consts: jnp.ndarray) -> jnp.ndarray:
    """The power step's epilogue over the (n + 1, k) pull-sums: ``(1-d)/n
    + d * (pulled + dangling/n)`` per column from the (3, k) ``consts``,
    the dump slot kept at 0."""
    out = consts[0] + consts[1] * (pulled + consts[2])
    return out.at[-1].set(0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pagerank_step_sell(
    bucket_radj: tuple[jnp.ndarray, ...],
    bucket_nodes: tuple[jnp.ndarray, ...],
    contrib: jnp.ndarray,
    consts: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One power step over width-bucketed, in-degree-sorted adjacency.

    ``contrib`` is (n + 1,) for a single configuration or (n + 1, k) for k
    stacked ones (dump slot = 0); ``consts`` is (3,) or (3, k) to match.
    The per-bucket pull-sums are added into original node order through
    ``bucket_nodes`` and damped in the same program; returns the new rank
    matrix, same shape as ``contrib``.
    """
    cols = contrib if contrib.ndim == 2 else contrib[:, None]
    consts = consts.reshape(3, cols.shape[1])
    pulled = sell_core.bucketed_node_step(
        _pr_sell_step_kernel, bucket_radj, bucket_nodes,
        cols, None, jnp.zeros_like(cols), combine="add",
        interpret=interpret,
    )
    out = damped(pulled, consts)
    return out if contrib.ndim == 2 else out[:, 0]


def broadcast_configs(damping, iters) -> tuple[np.ndarray, np.ndarray]:
    """Broadcast scalar-or-sequence ``damping`` / ``iters`` against each
    other into equal-length config columns — the one definition of the
    batched-PageRank request shape (shared with :func:`repro.kernels.ops
    .pagerank`'s per-column ELLPACK fallback)."""
    dampings = np.atleast_1d(np.asarray(damping, np.float64))
    iters_arr = np.atleast_1d(np.asarray(iters, np.int64))
    k = max(len(dampings), len(iters_arr))
    try:
        return (np.broadcast_to(dampings, (k,)),
                np.broadcast_to(iters_arr, (k,)))
    except ValueError:
        raise ValueError(
            f"damping ({len(dampings)}) and iters ({len(iters_arr)}) must "
            "be scalars or equal-length sequences") from None


def pagerank_sell(
    bucket_radj: tuple[jnp.ndarray, ...],
    bucket_nodes: tuple[jnp.ndarray, ...],
    out_degree: jnp.ndarray,
    n_nodes: int,
    *,
    damping=0.85,
    iters=20,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Full PageRank over bucketed SELL reverse adjacency, batched configs.

    ``damping`` / ``iters`` may be scalars or sequences: configurations are
    broadcast against each other and become RHS columns, so k requests run
    as one launch set per power step.  A column whose ``iters`` budget is
    exhausted freezes while longer ones keep iterating.  ``out_degree`` is
    the (n_nodes,) degree vector in *original* node order; returns
    (n_nodes,) ranks for scalar inputs, (n_nodes, k) otherwise.  Each
    power step is a ``graph.level`` phase.
    """
    scalar = np.ndim(damping) == 0 and np.ndim(iters) == 0
    n = n_nodes
    dtype = float_dtype()
    if scalar:
        rank = jnp.full((n,), 1.0 / n, dtype)
        deg = out_degree.astype(dtype)
        zero = jnp.zeros((1,), dtype)
        for _ in range(int(iters)):
            with obs_trace.phase("graph.level"):
                contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
                dangling = jnp.sum(jnp.where(deg == 0, rank, 0.0))
                consts = jnp.stack(
                    [(1.0 - damping) / n, damping, dangling / n]).astype(dtype)
                new = pagerank_step_sell(
                    bucket_radj, bucket_nodes,
                    jnp.concatenate([contrib, zero]),   # dump slot: 0
                    consts, interpret=interpret,
                )
                rank = new[:n]
        return rank
    dampings, iters_arr = broadcast_configs(damping, iters)
    k = len(dampings)
    rank = jnp.full((n, k), 1.0 / n, dtype)
    deg = out_degree.astype(dtype)[:, None]   # (n, 1) broadcasts over columns
    d = jnp.asarray(dampings, dtype)          # (k,)
    zero_row = jnp.zeros((1, k), dtype)
    for t in range(1, int(iters_arr.max()) + 1):
        with obs_trace.phase("graph.level"):
            contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
            dangling = jnp.sum(jnp.where(deg == 0, rank, 0.0), axis=0)
            consts = jnp.stack([(1.0 - d) / n, d, dangling / n]).astype(dtype)
            new = pagerank_step_sell(
                bucket_radj, bucket_nodes,
                jnp.concatenate([contrib, zero_row]),   # dump slot: 0
                consts, interpret=interpret,
            )
            active = jnp.asarray(t <= iters_arr)    # freeze finished columns
            rank = jnp.where(active[None, :], new[:n], rank)
    return rank


def pagerank(
    radj: jnp.ndarray,
    out_degree: jnp.ndarray,
    *,
    damping: float = 0.85,
    iters: int = 20,
    vl: int = 256,
    n_real: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Full PageRank: ``iters`` power steps over the reverse adjacency.

    ``n_real`` excludes VL-padding nodes from the rank mass and dangling sum
    (padded rows produce garbage entries that callers trim); node counts
    that do not divide ``vl`` are padded here once — not once per power
    step — and the pad trimmed from the result.
    """
    n0 = radj.shape[0]
    n = n_real if n_real is not None else n0
    if n0 % vl:
        pad = vl - n0 % vl
        radj = jnp.pad(radj, ((0, pad), (0, 0)), constant_values=PAD)
        out_degree = jnp.pad(out_degree, (0, pad))
    n_pad = radj.shape[0]
    dtype = float_dtype()
    real = jnp.arange(n_pad) < n
    rank = jnp.where(real, 1.0 / n, 0.0).astype(dtype)
    deg = out_degree.astype(dtype)
    for _ in range(iters):
        contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
        dangling = jnp.sum(jnp.where(real & (deg == 0), rank, 0.0))
        consts = jnp.stack([(1.0 - damping) / n, damping, dangling / n]).astype(dtype)
        rank = pagerank_step(radj, contrib, consts, vl=vl, interpret=interpret)
    return rank[:n0]
