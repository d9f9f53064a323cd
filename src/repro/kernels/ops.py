"""Public jit'd wrappers over the Pallas kernels.

These are the APIs the examples/benchmarks call: they take the host-side
substrate objects (:class:`repro.sparse.EllpackMatrix`,
:class:`repro.sparse.SellSlabs`, :class:`repro.graphs.EllpackGraph`), move
them to device, pad to the chosen VL, dispatch the kernel matching the
format, and trim the result.  ``interpret`` defaults to "not on TPU"
(:func:`repro.kernels.backend.default_interpret`) so the same call sites
run interpreted on CPU and compiled on real hardware.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.preflight import (
    SlabMeta,
    plan_bfs_sell,
    plan_fft_stockham,
    plan_ft,
    plan_moe_dispatch,
    plan_pagerank_sell,
    plan_spmm_sell,
    plan_spmm_sell_sharded,
    plan_spmm_sell_stream,
)
from repro.core.autotune import (
    SellTuneResult,
    pick_stream_tiles,
    tune_sell_layout,
)
from repro.graphs.gen import EllpackGraph, graph_to_sell_slabs, shard_graph_slabs
from repro.kernels import bfs as bfs_k
from repro.kernels import fft as fft_k
from repro.kernels import pagerank as pr_k
from repro.kernels import sell_core, sell_shard
from repro.kernels import spmv as spmv_k
from repro.kernels.backend import float_dtype, resolve_interpret
from repro.kernels.execspec import _UNSET, ExecSpec
from repro.kernels.ref import fft_twiddles
from repro.obs import Stopwatch
from repro.obs import profile as obs_profile
from repro.sparse.formats import (
    CSRMatrix,
    EllpackMatrix,
    SellCSigmaMatrix,
    SellSlabs,
    csr_to_sell_slabs,
    sell_to_slabs,
    shard_slabs,
    to_csr,
)

PAD = -1
INF = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# SpMV
# ---------------------------------------------------------------------------


_DEFAULT_CACHE = None


def default_tune_cache():
    """Process-wide in-memory TuneCache backing the repack-on-mismatch path.

    Serving stacks construct their own persistent cache and pass it
    explicitly; this default exists so ad-hoc ``spmv`` calls still stop
    paying for the same repack twice.  Its packed-slab memo is kept small
    (8 entries, LRU) because slabs are O(nnz) and callers never opted into
    retention; :func:`reset_default_tune_cache` releases everything.
    Imported lazily: the service layer sits above kernels, so the
    dependency must not bind at module import.
    """
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None:
        from repro.service.tunecache import TuneCache

        _DEFAULT_CACHE = TuneCache(max_packed=8)
    return _DEFAULT_CACHE


def reset_default_tune_cache() -> None:
    """Drop the process-wide repack memo (frees the retained slabs)."""
    global _DEFAULT_CACHE
    _DEFAULT_CACHE = None


def _repack_cached(matrix, vl: int, sigma: int | None, cache) -> SellSlabs:
    """Repack a matrix whose slice width disagrees with the requested vl.

    The repacked slabs are memoized in the TuneCache (keyed by content
    signature + target layout) and the event is recorded in the cache's
    persisted repack ledger — the second call with the same operand reuses
    the layout instead of warning and redoing the work.
    """
    from repro.service.tunecache import operand_signature

    cache = cache if cache is not None else default_tune_cache()
    sig = operand_signature(matrix)
    sigma = int(sigma or 8 * vl)
    key = ("repack", sig.key, vl, sigma)
    slabs = cache.packed_get(key)
    if slabs is None:
        slabs = csr_to_sell_slabs(to_csr(matrix), c=vl, sigma=sigma)
        cache.packed_put(key, slabs)
        cache.note_repack(f"repack|{sig.key}|c{vl}|sigma{sigma}")
    return slabs


def _shard_cached(slabs: SellSlabs, n_shards: int, cache):
    """Row-partition slabs for a device mesh, memoized like repacks.

    Sharding is O(nnz) (CSR round trip + per-shard repack), so the result
    is memoized in the TuneCache's packed-layout LRU keyed by content
    signature + shard count — the same pay-once protocol as
    :func:`_repack_cached`.
    """
    from repro.service.tunecache import operand_signature

    cache = cache if cache is not None else default_tune_cache()
    sig = operand_signature(slabs)
    key = ("shard", sig.key, slabs.c, int(slabs.sigma or 0), int(n_shards))
    sharded = cache.packed_get(key)
    if sharded is None:
        sharded = shard_slabs(slabs, n_shards)
        cache.packed_put(key, sharded)
    return sharded


def _shard_graph_cached(graph: EllpackGraph, vl: int, sigma: int | None,
                        n_shards: int, cache):
    """Node-partitioned reverse-adjacency slabs for a device mesh, memoized
    (see :func:`_shard_cached`)."""
    from repro.service.tunecache import operand_signature

    cache = cache if cache is not None else default_tune_cache()
    sig = operand_signature(graph)
    key = ("shard-graph-rev", sig.key, int(vl), int(sigma or 0),
           int(n_shards))
    sg = cache.packed_get(key)
    if sg is None:
        sg = shard_graph_slabs(graph, c=vl, n_shards=n_shards, sigma=sigma,
                               reverse=True)
        cache.packed_put(key, sg)
    return sg


def _sharded_graph_meta(sg, check_bounds: bool = False) -> SlabMeta:
    """Per-device :class:`SlabMeta` of sharded graph slabs: every device
    executes ``slices_per_shard`` slices of each union bucket against the
    full replicated state, which is exactly what the single-device
    ``plan_bfs_sell``/``plan_pagerank_sell`` price.  ``check_bounds``
    scans the stored ids, as :meth:`SlabMeta.from_slabs` does."""
    scan = check_bounds and any(a.size for a in sg.bucket_adj)
    return SlabMeta(
        kind="graph", c=sg.c, widths=sg.widths,
        n_slices=sg.slices_per_shard, n_rows=sg.n_nodes, n_cols=sg.n_nodes,
        val_dtype=None, idx_dtype=str(sg.bucket_adj[0].dtype)
        if sg.bucket_adj else "int32",
        idx_min=min(int(a.min()) for a in sg.bucket_adj if a.size)
        if scan else None,
        idx_max=max(int(a.max()) for a in sg.bucket_adj if a.size)
        if scan else None,
    )


#: ops-level execution modes for the SELL SpMM core
_SPMM_MODES = ("auto", "resident", "stream")


def _run_profiled(op: str, plan, thunk):
    """Run a core-call thunk under the optional launch profiler.

    When a :class:`repro.obs.LaunchProfiler` is installed
    (:func:`repro.obs.profile.install` / :func:`~repro.obs.profiled`), the
    call is forced to completion (``block_until_ready`` — measured wall
    time must cover the device work, not the async dispatch) and the
    (static preflight plan, measured wall) pair is recorded.  With no
    profiler installed the cost is one global read and the result stays
    lazy, exactly as before.
    """
    prof = obs_profile.active()
    if prof is None:
        return thunk()
    sw = Stopwatch().start()
    y = jax.block_until_ready(thunk())
    sw.stop()
    prof.record(op=op, operand=plan.operand, wall_us=sw.elapsed_us, plan=plan)
    return y


def _spmm_slabs(
    slabs: SellSlabs,
    x,
    *,
    w_block: int,
    k_block: int,
    interpret: bool,
    mode: str = "auto",
    col_tile: int | None = None,
    row_tile: int | None = None,
) -> jnp.ndarray:
    """Dispatch a slab SpMM to the resident or streaming schedule.

    ``mode="auto"`` picks by footprint: resident when the static
    :func:`plan_spmm_sell` fits :data:`repro.core.autotune.VMEM_BUDGET_BYTES`,
    streaming otherwise.  Either schedule is preflighted (VMEM budget, pow2
    tiles, dtype flow) with a structured error before XLA sees the launch.

    Single k-padding policy (asserted here, at the ops boundary): only the
    core pads the k axis, via :func:`repro.kernels.sell_core.padded_k`, and
    a power-of-two k is its fixpoint — so an RHS the service already
    pow2-padded (``service._pow2_pad``) is never padded a second time.
    """
    if mode not in _SPMM_MODES:
        raise ValueError(f"unknown mode {mode!r}: expected one of {_SPMM_MODES}")
    meta = SlabMeta.from_slabs(slabs)
    k = int(x.shape[1])
    # the padding-policy fixpoint: pow2 k in => identical k out of the core
    assert sell_core.padded_k(sell_core.pow2_ceil(max(k, 1)), k_block) \
        == sell_core.pow2_ceil(max(k, 1)), "k-padding policy drifted"
    resident_plan = plan_spmm_sell(
        meta, k=k, x_dtype=str(x.dtype), w_block=w_block, k_block=k_block)
    if mode == "auto":
        mode = "resident" if resident_plan.ok else "stream"
    args = (
        tuple(jnp.asarray(c) for c in slabs.bucket_cols),
        tuple(jnp.asarray(v) for v in slabs.bucket_vals),
        tuple(jnp.asarray(r) for r in slabs.bucket_rows),
        jnp.asarray(x),
    )
    if mode == "resident":
        resident_plan.raise_if_invalid()
        return _run_profiled("spmm", resident_plan, lambda: sell_core.spmm_sell(
            *args, n_rows=slabs.n_rows, w_block=w_block, k_block=k_block,
            interpret=interpret,
        ))
    if col_tile is None or row_tile is None:
        ct, rt = pick_stream_tiles(meta.c, w_block, k_block)
        col_tile = ct if col_tile is None else col_tile
        row_tile = rt if row_tile is None else row_tile
    stream_plan = plan_spmm_sell_stream(
        meta, k=k, x_dtype=str(x.dtype), w_block=w_block, k_block=k_block,
        col_tile=col_tile, row_tile=row_tile,
    ).raise_if_invalid()
    return _run_profiled("spmm", stream_plan, lambda: sell_core.spmm_sell_stream(
        *args, n_rows=slabs.n_rows, w_block=w_block, k_block=k_block,
        col_tile=int(col_tile), row_tile=int(row_tile), interpret=interpret,
    ))


def _spmm_sharded(
    slabs: SellSlabs,
    x: jnp.ndarray,
    spec: ExecSpec,
    *,
    k_block: int,
    interpret: bool,
) -> jnp.ndarray:
    """Dispatch a slab SpMM across the spec's device mesh.

    Two shard axes, picked by the RHS width: when the padded k covers at
    least one full k tile *per device* (k >> k_block), the RHS columns
    shard and the operand replicates (:func:`sell_shard.spmm_sell_rhs_sharded`
    — no collectives); otherwise the rows shard
    (:func:`sell_shard.spmm_sell_sharded` — boundary-column gather, disjoint
    output concatenation).  Both paths preflight their per-device plan.
    """
    if spec.mode == "stream":
        raise ValueError(
            "mode='stream' and a multi-device placement cannot combine: "
            "the streaming schedule is a single-device out-of-VMEM "
            "pipeline; drop the placement or use mode='auto'")
    ndev = spec.n_devices()
    mesh = spec.resolved_placement()
    k = int(x.shape[1])
    kp = sell_core.k_tile_for(k, k_block)
    meta = SlabMeta.from_slabs(slabs)
    if sell_core.padded_k(k, k_block) >= ndev * kp:
        # every device gets >= 1 whole RHS tile: shard k, replicate A
        plan_spmm_sell(
            meta, k=max(1, -(-k // ndev)), x_dtype=str(x.dtype),
            w_block=spec.w_block, k_block=k_block,
        ).raise_if_invalid()
        return sell_shard.spmm_sell_rhs_sharded(
            slabs, x, mesh=mesh, w_block=spec.w_block, k_block=k_block,
            interpret=interpret)
    sharded = _shard_cached(slabs, ndev, spec.cache)
    plan_spmm_sell_sharded(
        meta, k=k, x_dtype=str(x.dtype), n_devices=ndev,
        w_block=spec.w_block, k_block=k_block,
        window_cols=sharded.window_cols,
    ).raise_if_invalid()
    return sell_shard.spmm_sell_sharded(
        sharded, x, mesh=mesh, w_block=spec.w_block, k_block=k_block,
        interpret=interpret)


def _normalize_matrix(matrix, spec: ExecSpec):
    """Normalize any supported matrix format toward SELL slabs at the
    spec's (vl, sigma) — repack-on-mismatch memoized through the cache."""
    if not isinstance(matrix, CSRMatrix) and matrix.c != spec.vl:
        matrix = _repack_cached(matrix, spec.vl, spec.sigma, spec.cache)
    if isinstance(matrix, CSRMatrix):
        matrix = csr_to_sell_slabs(matrix, c=spec.vl, sigma=spec.sigma)
    if isinstance(matrix, SellCSigmaMatrix):
        matrix = sell_to_slabs(matrix)
    return matrix


def spmm(
    matrix: CSRMatrix | EllpackMatrix | SellCSigmaMatrix | SellSlabs,
    x: np.ndarray | jnp.ndarray,
    *,
    spec: ExecSpec | None = None,
    vl=_UNSET,
    sigma=_UNSET,
    w_block=_UNSET,
    k_block=_UNSET,
    interpret=_UNSET,
    cache=_UNSET,
    mode=_UNSET,
    col_tile=_UNSET,
    row_tile=_UNSET,
) -> jnp.ndarray:
    """Y = A @ X for stacked right-hand sides X of shape (n_cols, k).

    The batched core of :func:`spmv`: every supported format is normalized
    to width-bucketed SELL slabs and the whole RHS stack runs as one
    launch set through :func:`repro.kernels.sell_core.spmm_sell` (or, for
    operands whose resident footprint exceeds the VMEM budget, the
    out-of-VMEM :func:`repro.kernels.sell_core.spmm_sell_stream`).
    Returns Y of shape (n_rows, k).

    Configuration arrives as one :class:`~repro.kernels.execspec.ExecSpec`
    (``spec=``).  ``spec.k_block`` defaults to the power of two covering
    k, capped at 8 — pass the co-tuned :attr:`SellTuneResult.k_block` for
    the VMEM-fitted value.  ``spec.mode`` forces the schedule (``"auto"`` /
    ``"resident"`` / ``"stream"``); ``spec.col_tile``/``row_tile`` override
    the streaming tiles.  A multi-device ``spec.placement`` runs the
    sharded executors (RHS-sharded when k >> k_block, row-sharded
    otherwise).  The bare keywords are deprecated aliases for the matching
    spec fields (one ``DeprecationWarning``, identical results).
    """
    spec = ExecSpec.resolve(
        spec, _caller="ops.spmm", vl=vl, sigma=sigma, w_block=w_block,
        k_block=k_block, interpret=interpret, cache=cache, mode=mode,
        col_tile=col_tile, row_tile=row_tile)
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"spmm expects X of shape (n_cols, k), got {x.shape}")
    if spec.mode not in _SPMM_MODES:
        raise ValueError(
            f"unknown mode {spec.mode!r}: expected one of {_SPMM_MODES}")
    kb = spec.k_block if spec.k_block is not None \
        else min(8, sell_core.pow2_ceil(x.shape[1]))
    interp = resolve_interpret(spec.interpret)
    matrix = _normalize_matrix(matrix, spec)
    if isinstance(matrix, SellSlabs):
        if spec.n_devices() > 1:
            return _spmm_sharded(matrix, x, spec, k_block=kb,
                                 interpret=interp)
        return _spmm_slabs(
            matrix, x, w_block=spec.w_block, k_block=kb, interpret=interp,
            mode=spec.mode, col_tile=spec.col_tile, row_tile=spec.row_tile,
        )
    if spec.n_devices() > 1:
        raise ValueError(
            "multi-device placement requires a SELL slab layout; ELLPACK "
            "operands only run the single-device uniform-width kernel")
    if spec.mode == "stream":
        raise ValueError(
            "mode='stream' requires a SELL slab layout; ELLPACK operands "
            "only run the resident uniform-width kernel")
    # uniform-width ELLPACK: run the stack column-by-column through the
    # paper-baseline kernel (the SELL slab path above is the batched one)
    cols = jnp.asarray(matrix.cols)
    vals = jnp.asarray(matrix.vals)
    ys = [
        spmv_k.spmv_ell(
            cols, vals, x[:, i],
            w_block=min(spec.w_block, matrix.width), interpret=interp,
        )[: matrix.n_rows]
        for i in range(x.shape[1])
    ]
    return jnp.stack(ys, axis=1)


def spmv(
    matrix: CSRMatrix | EllpackMatrix | SellCSigmaMatrix | SellSlabs,
    x: np.ndarray | jnp.ndarray,
    *,
    spec: ExecSpec | None = None,
    vl=_UNSET,
    sigma=_UNSET,
    w_block=_UNSET,
    interpret=_UNSET,
    cache=_UNSET,
    mode=_UNSET,
    col_tile=_UNSET,
    row_tile=_UNSET,
) -> jnp.ndarray:
    """y = A @ x, dispatching the kernel that matches the matrix format.

    * :class:`CSRMatrix` — packed to width-bucketed SELL slabs at slice
      width ``spec.vl`` (sigma defaults to 8*vl) and run bucket-by-bucket;
    * :class:`SellSlabs` / :class:`SellCSigmaMatrix` — bucketed kernel;
    * :class:`EllpackMatrix` — the uniform-width kernel.

    ``x`` may be a single (n_cols,) vector or a stacked (n_cols, k) RHS
    matrix; the latter dispatches to :func:`spmm` and returns (n_rows, k).

    A pre-packed matrix whose C disagrees with ``spec.vl`` is repacked once
    and the layout is memoized in the TuneCache (``spec.cache``, defaulting
    to the process-wide :func:`default_tune_cache`): repeated calls with
    the same operand reuse the repacked slabs instead of discarding the
    work.

    All launch knobs ride on ``spec=`` (one
    :class:`~repro.kernels.execspec.ExecSpec`): ``mode``/``col_tile``/
    ``row_tile`` select and shape the resident vs streaming schedule
    exactly as in :func:`spmm`, and a multi-device ``placement`` runs the
    row-sharded executor.  The bare keywords are deprecated aliases
    (warning emitted, identical results).
    """
    spec = ExecSpec.resolve(
        spec, _caller="ops.spmv", vl=vl, sigma=sigma, w_block=w_block,
        interpret=interpret, cache=cache, mode=mode, col_tile=col_tile,
        row_tile=row_tile)
    x = jnp.asarray(x)
    if x.ndim == 2:
        return spmm(matrix, x, spec=spec)
    if spec.mode not in _SPMM_MODES:
        raise ValueError(
            f"unknown mode {spec.mode!r}: expected one of {_SPMM_MODES}")
    interp = resolve_interpret(spec.interpret)
    matrix = _normalize_matrix(matrix, spec)
    if isinstance(matrix, SellSlabs):
        if spec.n_devices() > 1:
            return _spmm_sharded(
                matrix, x[:, None], spec, k_block=1, interpret=interp)[:, 0]
        return _spmm_slabs(
            matrix, x[:, None], w_block=spec.w_block, k_block=1,
            interpret=interp, mode=spec.mode, col_tile=spec.col_tile,
            row_tile=spec.row_tile,
        )[:, 0]
    if spec.n_devices() > 1:
        raise ValueError(
            "multi-device placement requires a SELL slab layout; ELLPACK "
            "operands only run the single-device uniform-width kernel")
    if spec.mode == "stream":
        raise ValueError(
            "mode='stream' requires a SELL slab layout; ELLPACK operands "
            "only run the resident uniform-width kernel")
    y = spmv_k.spmv_ell(
        jnp.asarray(matrix.cols),
        jnp.asarray(matrix.vals),
        x,
        w_block=min(spec.w_block, matrix.width),
        interpret=interp,
    )
    return y[: matrix.n_rows]


def pack_tuned(
    matrix: CSRMatrix, machine=None, cache=None, device: str | None = None,
    candidates_c=None, signature=None, n_devices: int = 1,
) -> tuple[SellSlabs, SellTuneResult]:
    """Autotune (C, sigma, w_block) for this matrix and pack it.

    The co-design loop as an API: measure the pad_factor every candidate
    layout would produce on the actual row-length distribution, score
    SDV-modeled cycles, and return the packed winner plus the tune table.
    Feed the result straight to :func:`spmv`:

        slabs, tuned = pack_tuned(csr)
        y = spmv(slabs, x, vl=tuned.c, w_block=tuned.w_block)

    Passing a ``cache`` (:class:`repro.service.tunecache.TuneCache`) makes
    the tune a pay-once cost per operand signature: a warm cache answers
    without measuring a single pad factor, and the packed slabs themselves
    are memoized by (signature, C, sigma).
    """
    base_key = None
    if cache is not None:
        from repro.core.sdv import tpu_v5e_machine

        if device is None:
            device = jax.default_backend()
        # the key must name the machine the tune scores against, so resolve
        # the tuner's default before keying; callers that already
        # fingerprinted the operand pass ``signature`` to skip re-hashing
        machine = machine if machine is not None else tpu_v5e_machine()
        base_key = cache.sell_key(
            "spmv", signature if signature is not None else matrix,
            device=device, dtype=str(matrix.data.dtype), machine=machine,
            n_devices=n_devices)
    return tune_and_pack(
        matrix.row_lengths,
        lambda t: csr_to_sell_slabs(matrix, c=t.c, sigma=t.sigma),
        n_cols=matrix.n_cols, machine=machine,
        candidates_c=candidates_c, cache=cache, base_key=base_key,
        n_devices=n_devices,
    )


def cached_tune_sell(
    row_lengths, n_cols=None, machine=None, candidates_c=None,
    cache=None, base_key: str | None = None, n_devices: int = 1,
) -> SellTuneResult:
    """The one cached-tune protocol (shared by :func:`pack_tuned` and the
    service registry's graph path).

    A narrowed candidate sweep is a different experiment than the full
    grid, so hinted results live under a ``|cands...``-suffixed key and can
    never masquerade as a full-sweep tune.  On a hinted miss the full-grid
    entry is consulted first — an operand the cache has already seen is
    never re-measured just because hints appeared (or disappeared) since.
    """
    key = base_key
    if candidates_c is not None and base_key is not None:
        key = base_key + "|cands" + "-".join(map(str, sorted(candidates_c)))
        if cache is not None:
            full = cache.get_sell(base_key)
            if full is not None:
                return full
    return tune_sell_layout(
        row_lengths, n_cols=n_cols, machine=machine,
        candidates_c=candidates_c, cache=cache, cache_key=key,
        n_devices=n_devices,
    )


def tune_and_pack(
    row_lengths, pack_fn, n_cols=None, machine=None, candidates_c=None,
    cache=None, base_key: str | None = None, n_devices: int = 1,
):
    """Cached tune + memoized pack — the full serving protocol, shared by
    :func:`pack_tuned` (matrices) and the registry's graph path.

    ``pack_fn(tuned)`` builds the layout for the winning (C, sigma); the
    result is memoized under ``(base_key, C, sigma)`` — the layout depends
    only on content and the chosen shape, so hinted and full-sweep tunes
    share packed slabs.
    """
    tuned = cached_tune_sell(
        row_lengths, n_cols=n_cols, machine=machine,
        candidates_c=candidates_c, cache=cache, base_key=base_key,
        n_devices=n_devices,
    )
    if cache is not None and base_key is not None:
        packed_key = (base_key, tuned.c, tuned.sigma)
        layout = cache.packed_get(packed_key)
        if layout is None:
            layout = pack_fn(tuned)
            cache.packed_put(packed_key, layout)
        return layout, tuned
    return pack_fn(tuned), tuned


# ---------------------------------------------------------------------------
# FFT
# ---------------------------------------------------------------------------


def fft(
    signal_re: np.ndarray | jnp.ndarray,
    signal_im: np.ndarray | jnp.ndarray | None = None,
    *,
    spec: ExecSpec | None = None,
    b_block=_UNSET,
    interpret=_UNSET,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched FFT of (batch, n) split-plane signals (n power of two).

    Configuration rides on ``spec=`` (``b_block``, ``interpret``); the bare
    keywords are deprecated aliases.  FFT has no sharded execution path —
    a multi-device ``spec.placement`` is rejected rather than silently run
    on one device.
    """
    spec = ExecSpec.resolve(
        spec, _caller="ops.fft", b_block=b_block, interpret=interpret)
    if spec.n_devices() > 1:
        raise ValueError(
            "fft has no sharded execution path; use a single-device "
            "placement")
    re = jnp.atleast_2d(jnp.asarray(signal_re))
    im = (
        jnp.zeros_like(re)
        if signal_im is None
        else jnp.atleast_2d(jnp.asarray(signal_im))
    )
    n = re.shape[-1]
    if n & (n - 1):
        raise ValueError(f"n must be a power of two, got {n}")
    interp = resolve_interpret(spec.interpret)
    wre, wim = fft_twiddles(n, re.dtype)
    bb = min(spec.b_block, re.shape[0])
    plan_fft_stockham(
        int(n), batch=int(re.shape[0]), b_block=int(bb),
        dtype=str(re.dtype),
    ).raise_if_invalid()
    return fft_k.fft_stockham(re, im, wre, wim, b_block=bb, interpret=interp)


def ft(
    re: np.ndarray | jnp.ndarray,
    im: np.ndarray | jnp.ndarray,
    steps,
    alpha: float,
    *,
    spec: ExecSpec | None = None,
) -> jnp.ndarray:
    """NPB FT time steps ``steps`` of the (nx, ny, nz) field ``re + i im``
    with diffusion constant ``alpha``: (len(steps), 1025) complex, u_t at
    the 1024 checksum points and last the checksum
    (:func:`repro.kernels.fft.ft_step`).  Runs the forward transform on
    every call; :meth:`repro.service.KernelRegistry.register_ft` keeps the
    spectrum resident instead.  Configuration rides on ``spec=``
    (``interpret``); the plan (:func:`plan_ft`) picks the passes' blocks."""
    spec = ExecSpec.resolve(spec, _caller="ops.ft")
    if spec.n_devices() > 1:
        raise ValueError(
            "ft has no sharded execution path; use a single-device "
            "placement")
    re, im = jnp.asarray(re), jnp.asarray(im)
    steps = np.atleast_1d(np.asarray(steps, np.int64))
    plan = plan_ft(re.shape, k=len(steps),
                   dtype=str(re.dtype)).raise_if_invalid()
    interp = resolve_interpret(spec.interpret)
    sre, sim = fft_k.ft_spectrum(re, im, b_blocks=plan.b_blocks,
                                 interpret=interp)
    decay = jnp.asarray(fft_k.ft_decay(alpha, steps).astype(re.dtype))
    return fft_k.ft_step(sre, sim, decay, b_blocks=plan.b_blocks,
                         interpret=interp)


# ---------------------------------------------------------------------------
# BFS
# ---------------------------------------------------------------------------


def bfs(
    graph: EllpackGraph,
    source=0,
    *,
    spec: ExecSpec | None = None,
    vl=_UNSET,
    sigma=_UNSET,
    layout=_UNSET,
    interpret=_UNSET,
) -> np.ndarray:
    """BFS distances from ``source`` (INF = unreachable).

    ``spec.layout = "sell"`` runs the width-bucketed kernel over
    in-degree-sorted adjacency slabs: skewed-degree graphs stop paying the
    global max in-degree per node, and a hub's row is split into virtual
    rows no longer than :data:`repro.sparse.formats.SPLIT_WIDTH`.

    ``source`` may be one node id or a sequence of k ids.  A sequence
    returns stacked (n_nodes, k) distances, one column per source; on the
    SELL layout the whole stack advances through one launch set per level
    (the multi-RHS batched core), on ELLPACK the sources run one by one.

    A multi-device ``spec.placement`` (SELL layout only) node-partitions
    the reverse adjacency and unions per-device frontiers with ``pmin``
    every level — results are identical to the single-device drive at any
    device count.  The bare keywords are deprecated aliases for the
    matching spec fields.
    """
    spec = ExecSpec.resolve(
        spec, _caller="ops.bfs", vl=vl, sigma=sigma, layout=layout,
        interpret=interpret)
    if spec.layout not in ("ell", "sell"):
        raise ValueError(
            f"unknown layout {spec.layout!r}: expected 'ell' or 'sell'")
    interp = resolve_interpret(spec.interpret)
    n = graph.n_nodes
    # Bottom-up expansion needs *in*-neighbors: a node joins the frontier if
    # one of the nodes that point AT it was reached last level.
    if spec.n_devices() > 1:
        if spec.layout != "sell":
            raise ValueError(
                "multi-device placement requires layout='sell' (the "
                "ELLPACK drive has no sharded path)")
        sg = _shard_graph_cached(
            graph, spec.vl, spec.sigma, spec.n_devices(),
            spec.cache)
        plan_bfs_sell(
            _sharded_graph_meta(sg), k=int(np.size(source)),
        ).raise_if_invalid()
        dist = sell_shard.bfs_sell_sharded(
            sg, source, mesh=spec.resolved_placement(), interpret=interp)
        return np.asarray(dist)
    if spec.layout == "sell":
        slabs = graph_to_sell_slabs(graph, c=spec.vl, sigma=spec.sigma,
                                    reverse=True)
        plan_bfs_sell(
            SlabMeta.from_slabs(slabs), k=int(np.size(source)),
        ).raise_if_invalid()
        dist = bfs_k.bfs_sell(
            tuple(jnp.asarray(a) for a in slabs.bucket_adj),
            tuple(jnp.asarray(m) for m in slabs.bucket_nodes),
            n, source, interpret=interp,
        )
        return np.asarray(dist)
    radj = jnp.asarray(graph.transpose().adj)  # bfs_step auto-pads to vl
    if np.ndim(source) == 0:
        return np.asarray(
            bfs_k.bfs(radj, source, vl=spec.vl, interpret=interp))
    return np.stack(
        [np.asarray(bfs_k.bfs(radj, int(s), vl=spec.vl, interpret=interp))
         for s in np.asarray(source)], axis=1)


# ---------------------------------------------------------------------------
# PageRank
# ---------------------------------------------------------------------------


def pagerank(
    graph: EllpackGraph,
    *,
    damping=0.85,
    iters=20,
    spec: ExecSpec | None = None,
    vl=_UNSET,
    sigma=_UNSET,
    layout=_UNSET,
    interpret=_UNSET,
) -> np.ndarray:
    """PageRank scores via the pull-style kernel on the reverse graph.

    ``spec.layout = "sell"`` uses in-degree-sorted, width-bucketed reverse
    adjacency (see :func:`bfs`).

    ``damping`` / ``iters`` may be scalars or sequences (broadcast against
    each other): sequences return stacked (n_nodes, k) ranks, one column
    per configuration; on the SELL layout every power step is one launch
    set for all k columns, on ELLPACK the configurations run one by one.

    A multi-device ``spec.placement`` (SELL layout only) node-partitions
    the reverse adjacency; every power step each device adds up the
    pull-sums of its owned nodes, and the cross-device ``psum`` assembles
    them for the damping that gives the replicated iterate — the rank
    exchange.  Bare layout keywords are
    deprecated aliases for the matching spec fields.
    """
    spec = ExecSpec.resolve(
        spec, _caller="ops.pagerank", vl=vl, sigma=sigma, layout=layout,
        interpret=interpret)
    if spec.layout not in ("ell", "sell"):
        raise ValueError(
            f"unknown layout {spec.layout!r}: expected 'ell' or 'sell'")
    interp = resolve_interpret(spec.interpret)
    n = graph.n_nodes
    if spec.n_devices() > 1:
        if spec.layout != "sell":
            raise ValueError(
                "multi-device placement requires layout='sell' (the "
                "ELLPACK drive has no sharded path)")
        sg = _shard_graph_cached(
            graph, spec.vl, spec.sigma, spec.n_devices(),
            spec.cache)
        plan_pagerank_sell(
            _sharded_graph_meta(sg),
            k=max(int(np.size(damping)), int(np.size(iters))),
        ).raise_if_invalid()
        rank = sell_shard.pagerank_sell_sharded(
            sg, jnp.asarray(graph.out_degree, float_dtype()),
            mesh=spec.resolved_placement(), damping=damping, iters=iters,
            interpret=interp,
        )
        return np.asarray(rank)
    if spec.layout == "sell":
        slabs = graph_to_sell_slabs(graph, c=spec.vl, sigma=spec.sigma,
                                    reverse=True)
        plan_pagerank_sell(
            SlabMeta.from_slabs(slabs),
            k=max(int(np.size(damping)), int(np.size(iters))),
        ).raise_if_invalid()
        rank = pr_k.pagerank_sell(
            tuple(jnp.asarray(a) for a in slabs.bucket_adj),
            tuple(jnp.asarray(m) for m in slabs.bucket_nodes),
            jnp.asarray(graph.out_degree, float_dtype()),
            n, damping=damping, iters=iters, interpret=interp,
        )
        return np.asarray(rank)
    radj = jnp.asarray(graph.transpose().adj)  # pagerank_step auto-pads
    deg = jnp.asarray(graph.out_degree, float_dtype())
    if np.ndim(damping) == 0 and np.ndim(iters) == 0:
        rank = pr_k.pagerank(
            radj, deg, damping=damping, iters=iters, vl=spec.vl,
            interpret=interp,
        )
        return np.asarray(rank[:n])
    dampings, iters_arr = pr_k.broadcast_configs(damping, iters)
    cols = [
        np.asarray(pr_k.pagerank(
            radj, deg, damping=float(d), iters=int(it), vl=spec.vl,
            interpret=interp,
        )[:n])
        for d, it in zip(dampings, iters_arr)
    ]
    return np.stack(cols, axis=1)

#: ops-level MoE dispatch paths (ExecSpec.dispatch)
_MOE_DISPATCH_MODES = ("auto", "sell", "dense")


def _routing_dense(routing: CSRMatrix) -> np.ndarray:
    """Materialize the routing matrix densely — the counterfactual the
    ``dispatch="dense"`` path executes (one XLA matmul over the same
    operand, exactly what the masked one-hot einsum reduces to)."""
    dense = np.zeros((routing.n_rows, routing.n_cols), routing.data.dtype)
    rows = np.repeat(np.arange(routing.n_rows), np.diff(routing.indptr))
    dense[rows, routing.indices] = routing.data
    return dense


def moe_dispatch(
    routing: CSRMatrix | SellSlabs,
    x: np.ndarray | jnp.ndarray,
    *,
    spec: ExecSpec | None = None,
    top_k: int,
) -> jnp.ndarray:
    """Y = R @ X for the MoE token<->slot routing matrix R.

    The expert-dispatch step of :func:`repro.models.moe.moe_forward` as a
    first-class kernel entry point: ``routing`` is the per-step combine
    matrix (one row per token, at most ``top_k`` stored entries — the
    renormalized router weights — whose columns are expert capacity slots)
    and ``x`` the ``(n_slots, d_model)`` expert-output stack.  Returns the
    ``(n_tokens, d_model)`` combined activations.

    ``spec.dispatch`` selects the path: ``"sell"``/``"auto"`` pack R into
    width-bucketed SELL slabs at ``spec.vl`` and run the batched multi-RHS
    :func:`repro.kernels.sell_core.spmm_sell` core (the whole activation
    stack in one launch set); ``"dense"`` materializes R and runs one dense
    matmul — the in-process counterfactual the serving bench measures the
    SELL path against.  Every SELL launch is preflighted with
    :func:`repro.analysis.preflight.plan_moe_dispatch` (the spmm contracts
    plus the routing-shape contract: no bucket wider than
    ``pow2_ceil(top_k)``).
    """
    spec = ExecSpec.resolve(spec, _caller="ops.moe_dispatch")
    if spec.dispatch not in _MOE_DISPATCH_MODES:
        raise ValueError(
            f"unknown dispatch {spec.dispatch!r}: expected one of "
            f"{_MOE_DISPATCH_MODES}")
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(
            f"moe_dispatch expects X of shape (n_slots, d), got {x.shape}")
    if spec.dispatch == "dense":
        if not isinstance(routing, CSRMatrix):
            raise TypeError(
                "dispatch='dense' materializes the routing matrix and needs "
                f"CSR input, got {type(routing).__name__}")
        return jnp.asarray(_routing_dense(routing)) @ x
    slabs = routing if isinstance(routing, SellSlabs) \
        else csr_to_sell_slabs(routing, c=spec.vl, sigma=spec.sigma)
    if not isinstance(slabs, SellSlabs):
        raise TypeError(
            f"routing must be a CSRMatrix or SellSlabs, got "
            f"{type(routing).__name__}")
    kb = spec.k_block if spec.k_block is not None \
        else min(8, sell_core.pow2_ceil(x.shape[1]))
    interp = resolve_interpret(spec.interpret)
    meta = SlabMeta.from_slabs(slabs)
    plan_moe_dispatch(
        meta, k=int(x.shape[1]), x_dtype=str(x.dtype), top_k=top_k,
        w_block=spec.w_block, k_block=kb,
    ).raise_if_invalid()
    return _spmm_slabs(
        slabs, x, w_block=spec.w_block, k_block=kb, interpret=interp,
        mode=spec.mode, col_tile=spec.col_tile, row_tile=spec.row_tile,
    )
