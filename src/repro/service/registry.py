"""Operand registry: register once, pack once, tune once, serve forever.

The serving subsystem's contract is that the expensive per-operand work —
signature fingerprinting, (C, sigma, w_block) tuning, SELL packing and the
host->device transfer of the slabs — happens at *registration*, so request
execution touches only prebuilt device arrays.  The tune step goes through
the persistent :class:`repro.service.tunecache.TuneCache`: registering an
operand whose signature the cache has seen (this process or any earlier one)
performs **zero** pad-factor measurements.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

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
from repro.core.autotune import SellTuneResult
from repro.core.sdv import MachineParams, tpu_v5e_machine
from repro.obs import MetricsRegistry, Stopwatch
from repro.obs import trace as obs_trace
from repro.graphs.gen import (
    EllpackGraph,
    graph_to_sell_slabs,
    in_degree,
    shard_graph_slabs,
    split_rows,
    split_stats,
)
from repro.kernels.backend import float_dtype
from repro.service.tunecache import OperandSignature, TuneCache, operand_signature
from repro.sparse.formats import (
    CSRMatrix,
    SellSlabs,
    pow2_ceil,
    to_csr,
    widest_k_tile,
)


@dataclasses.dataclass
class RegisteredOperand:
    """One served operand: host container + tuned device-ready arrays.

    The tuned result carries the co-selected ``k_block`` — the RHS tile of
    the batched SpMM core — so the service can collapse a whole coalesced
    request group into one ``spmm_sell`` launch against these arrays.
    ``launches`` counts those batched core launches (the launch-counter
    hook: one per coalesced group, not one per request).
    """

    name: str
    kind: str                               # matrix | graph | fft | ft | moe
    signature: OperandSignature | None
    tuned: SellTuneResult | None = None
    slabs: Any = None                       # SellSlabs | SellGraphSlabs
    device_arrays: dict = dataclasses.field(default_factory=dict)
    n: int = 0                  # n_rows / n_nodes / fft length / ft points
    n_cols: int = 0                         # RHS length for matrix operands
    register_us: float = 0.0                # wall time spent registering
    tune_was_cached: bool = False
    launches: int = 0                       # batched core launches served
    slab_meta: Any = None                   # SlabMeta (bounds-scanned) | None
    plans: dict = dataclasses.field(default_factory=dict)  # op -> LaunchPlan
    #: execution schedule the operand registered on: "resident" when its
    #: footprint fits the VMEM budget, "stream" (the out-of-VMEM
    #: double-buffered pipeline) when the resident plan honestly rejects it,
    #: "sharded" when the registry carries a multi-device mesh
    mode: str = "resident"
    #: the device-partitioned layout (ShardedSlabs / ShardedGraphSlabs)
    #: when the registry carries a multi-device mesh, else None
    sharded: Any = None
    #: MoE dispatch envelope (kind == "moe"): the per-step routing operands
    #: an LM engine submits are transient, so what registers is the SHAPE
    #: CONTRACT — ``{"c", "top_k", "d_model", "dtype"}`` — that every
    #: submitted routing matrix is preflighted against
    moe: dict | None = None
    #: NPB FT field (kind == "ft"): ``{"shape", "alpha", "b_blocks",
    #: "max_group"}``; the spectrum's planes are ``device_arrays["re"]`` /
    #: ``["im"]``
    ft: dict | None = None
    #: graph layout engagement (kind == "graph"): the split width (None
    #: when no node was split), the nodes split, the virtual rows and the
    #: padded slots a node step sweeps (:func:`repro.graphs.gen.split_stats`)
    split: dict | None = None

    @property
    def pad_factor(self) -> float:
        return float(self.slabs.pad_factor) if self.slabs is not None else 1.0


class KernelRegistry:
    """Named operands, packed and tuned once through a shared TuneCache."""

    def __init__(self, cache: TuneCache | None = None,
                 machine: MachineParams | None = None,
                 device: str | None = None,
                 mesh=None,
                 metrics: MetricsRegistry | None = None):
        if device is None:
            import jax

            device = jax.default_backend()
        self.cache = cache if cache is not None else TuneCache()
        # resolve the tuner's default machine eagerly: the cache key must
        # name the machine the tune actually scored against
        self.machine = machine if machine is not None else tpu_v5e_machine()
        self.device = device
        # mesh placement: None (single device), an int device count, or a
        # Mesh / MeshContext — resolved once through the same ExecSpec
        # machinery the ops layer uses, so registry and ops agree on what a
        # placement means.  Every operand registered while the mesh is
        # multi-device is packed into its sharded layout at registration
        # (mode "sharded"), and the tune scores the busiest shard under a
        # device-count-qualified cache key.
        from repro.kernels.execspec import ExecSpec

        _placement = ExecSpec(placement=mesh)
        self.mesh = _placement.resolved_placement()
        self.n_devices = _placement.n_devices()
        self._operands: dict[str, RegisteredOperand] = {}
        # registration-path observability: register_us was recorded on each
        # operand since PR 4 but never surfaced — every admission now also
        # lands in this registry (share the service's instance to get one
        # unified snapshot)
        self.metrics = metrics if metrics is not None else MetricsRegistry()

    # -- lookup ------------------------------------------------------------
    def names(self) -> list[str]:
        return sorted(self._operands)

    def get(self, name: str) -> RegisteredOperand:
        try:
            return self._operands[name]
        except KeyError:
            raise KeyError(
                f"operand {name!r} not registered; have {self.names()}"
            ) from None

    def __contains__(self, name: str) -> bool:
        return name in self._operands

    def _admit(self, op: RegisteredOperand, sw: Stopwatch) -> RegisteredOperand:
        op.register_us = sw.stop().elapsed_us
        self._operands[op.name] = op
        self.metrics.histogram(
            "register_us", "wall time of operand registration "
            "(pack + tune + upload)").observe(op.register_us)
        self.metrics.counter(f"registered_{op.kind}").inc()
        if op.tune_was_cached:
            self.metrics.counter(
                "register_tune_cached",
                "registrations whose tune came from the TuneCache").inc()
        return op

    def summary(self) -> dict:
        """Registration-path observability snapshot.

        Per-operand: kind, execution mode, registration wall time
        (``register_us`` — recorded since the registry existed, surfaced
        here), whether the tune was a cache hit, batched launches served,
        and the pack's pad factor.  ``cache`` carries the TuneCache's own
        stats including per-key repack counts (``note_repack`` events that
        previously died inside the cache file).
        """
        return {
            "operands": {
                name: {
                    "kind": op.kind,
                    "mode": op.mode,
                    "register_us": round(op.register_us, 1),
                    "tune_was_cached": op.tune_was_cached,
                    "launches": op.launches,
                    "pad_factor": round(op.pad_factor, 4),
                }
                for name, op in sorted(self._operands.items())
            },
            "cache": dict(self.cache.stats),
            "repacks": dict(self.cache.repacks),
        }

    # -- registration ------------------------------------------------------
    def register_matrix(self, name: str, matrix) -> RegisteredOperand:
        """Pack + tune a sparse matrix for SpMV serving.

        Any supported format is accepted and normalized to CSR for tuning.
        The TuneCache is consulted before any measurement, and the packed
        slabs are memoized by (signature, C, sigma) so re-registering the
        same content under another name reuses the layout outright.
        """
        from repro.kernels.ops import pack_tuned

        sw = Stopwatch().start()
        csr = to_csr(matrix) if not isinstance(matrix, CSRMatrix) else matrix
        # with x64 off (the TPU has no float64) wider values pack as
        # float32: pack, key and plan the values the kernels will read
        dtype = np.dtype(float_dtype())
        if csr.data.dtype.itemsize > dtype.itemsize:
            csr = dataclasses.replace(csr, data=csr.data.astype(dtype))
        sig = operand_signature(csr)
        before = self.cache.hits
        # pack_tuned owns the cached tune-and-pack sequence (key build,
        # cache-consulted tune, packed-slab memo) — the registry only adds
        # the campaign-hint narrowing and the device upload
        slabs, tuned = pack_tuned(
            csr, machine=self.machine, cache=self.cache, device=self.device,
            candidates_c=self.cache.candidate_vls_for(
                "spmv", self.machine.name),
            signature=sig,                 # skip the second content hash
            n_devices=self.n_devices,
        )
        op = RegisteredOperand(
            name=name, kind="matrix", signature=sig, tuned=tuned,
            slabs=slabs, n=csr.n_rows, n_cols=csr.n_cols,
            tune_was_cached=self.cache.hits > before,
        )
        # registration-time preflight: one bounds scan over the stored
        # indices plus the static launch plan for the tuned tiles — a
        # corrupt pack or a stale/poisoned cached tune is rejected here
        # with a structured LaunchPlanError, never served
        op.slab_meta = SlabMeta.from_slabs(slabs, check_bounds=True)
        # plans are priced at the widest RHS tile any coalesced group runs
        k = widest_k_tile(tuned.k_block)
        if self.n_devices > 1:
            from repro.kernels.sell_shard import place
            from repro.sparse.formats import shard_slabs

            # each shard's slabs go to its own device once, here
            op.sharded = place(shard_slabs(slabs, self.n_devices), self.mesh)
            op.mode = "sharded"
            op.plans = {"spmv": plan_spmm_sell_sharded(
                op.slab_meta, k=k,
                x_dtype=str(csr.data.dtype),
                n_devices=self.n_devices,
                w_block=tuned.w_block, k_block=tuned.k_block,
                window_cols=op.sharded.window_cols,
            ).raise_if_invalid()}
            return self._admit(op, sw)
        resident = plan_spmm_sell(
            op.slab_meta, k=k,
            x_dtype=str(csr.data.dtype),
            w_block=tuned.w_block, k_block=tuned.k_block,
        )
        if resident.ok:
            op.plans = {"spmv": resident}
        else:
            # A giant operand the resident plan honestly rejects registers
            # on the streaming schedule instead — no resident copy is ever
            # materialized.  The streaming plan still enforces every other
            # contract (pow2 tiles, dtype flow, scratch budget), so a
            # poisoned/stale cached tune is rejected here exactly as before.
            op.mode = "stream"
            op.plans = {"spmv": plan_spmm_sell_stream(
                op.slab_meta, k=k,
                x_dtype=str(csr.data.dtype),
                w_block=tuned.w_block, k_block=tuned.k_block,
                col_tile=tuned.col_tile, row_tile=tuned.row_tile,
            ).raise_if_invalid()}
        op.device_arrays = _matrix_device_arrays(slabs)
        return self._admit(op, sw)

    def register_graph(self, name: str, graph: EllpackGraph) -> RegisteredOperand:
        """Pack + tune a graph for BFS/PageRank serving.

        Both pull-style kernels consume the *reverse* adjacency, so the
        registry packs the in-neighbours into SELL slabs straight from the
        edge list (:func:`graph_to_sell_slabs` with ``reverse=True``),
        tuned on the in-degree distribution (the row-length law of the pull
        traffic).  Rows longer than the split width are packed as virtual
        rows (:func:`repro.graphs.gen.split_rows`) and tuned as such, so a
        hub sets no slice's width; a graph without hubs keeps one row per
        node.  Under a multi-device mesh the same packer builds the
        node-partitioned layout instead — one pack either way.  The tuned
        layout is preflighted from its metadata before anything is packed,
        so a graph whose launch plans fail is refused without building its
        slabs.  The cache key records the float dtype the PageRank state is
        held in on the device, and that the rows are split.
        """
        dtype = str(np.dtype(float_dtype()))
        from repro.kernels.ops import _sharded_graph_meta, tune_and_pack

        sw = Stopwatch().start()
        sig = operand_signature(graph)
        # the device count keys the packed-layout memo: one graph packs to
        # different slabs on one device and on a mesh
        key = self.cache.sell_key("graph-split", sig, device=self.device,
                                  dtype=dtype, machine=self.machine,
                                  n_devices=self.n_devices)
        before = self.cache.hits
        in_deg = in_degree(graph)

        def pack(tuned):
            meta = _graph_layout_meta(in_deg, tuned.c, tuned.sigma)
            plan_bfs_sell(meta).raise_if_invalid()
            plan_pagerank_sell(meta, dtype=dtype).raise_if_invalid()
            if self.n_devices > 1:
                return shard_graph_slabs(
                    graph, c=tuned.c, n_shards=self.n_devices,
                    sigma=tuned.sigma, reverse=True)
            return graph_to_sell_slabs(graph, c=tuned.c, sigma=tuned.sigma,
                                       reverse=True)

        # both pull-style kernels share the layout; a pagerank (or bfs)
        # campaign hint narrows the sweep for either — tune_and_pack owns
        # the hinted-vs-full-grid key protocol and the packed-slab memo
        hinted = (self.cache.candidate_vls_for("pagerank", self.machine.name)
                  or self.cache.candidate_vls_for("bfs", self.machine.name))
        slabs, tuned = tune_and_pack(
            split_rows(in_deg)[1], pack, n_cols=graph.n_nodes,
            machine=self.machine, candidates_c=hinted, cache=self.cache,
            base_key=key,
        )
        op = RegisteredOperand(
            name=name, kind="graph", signature=sig, tuned=tuned,
            slabs=slabs, n=graph.n_nodes,
            tune_was_cached=self.cache.hits > before,
            split=split_stats(slabs),
        )
        if self.n_devices > 1:
            from repro.kernels.sell_shard import place

            op.sharded = place(slabs, self.mesh)
            op.mode = "sharded"
            # per-device plan: each device runs slices_per_shard slices of
            # every union bucket against the full replicated state
            op.slab_meta = _sharded_graph_meta(slabs, check_bounds=True)
        else:
            op.slab_meta = SlabMeta.from_slabs(slabs, check_bounds=True)
        op.plans = {
            "bfs": plan_bfs_sell(op.slab_meta).raise_if_invalid(),
            "pagerank": plan_pagerank_sell(
                op.slab_meta, dtype=dtype).raise_if_invalid(),
        }
        op.device_arrays = _graph_device_arrays(
            None if op.sharded is not None else slabs, graph)
        return self._admit(op, sw)

    def register_fft(self, name: str, n: int) -> RegisteredOperand:
        """Precompute the twiddle plan for length-``n`` batched FFTs."""
        import jax.numpy as jnp

        from repro.kernels.ref import fft_twiddles

        sw = Stopwatch().start()
        if n & (n - 1) or n < 2:
            raise ValueError(f"fft length must be a power of two >= 2, got {n}")
        dtype = float_dtype()
        wre, wim = fft_twiddles(n, dtype)
        op = RegisteredOperand(name=name, kind="fft", signature=None, n=n)
        op.plans = {"fft": plan_fft_stockham(
            n, batch=8, dtype=str(np.dtype(dtype))).raise_if_invalid()}
        op.device_arrays = {"wre": jnp.asarray(wre), "wim": jnp.asarray(wim)}
        return self._admit(op, sw)

    def register_ft(self, name: str, re, im, alpha: float
                    ) -> RegisteredOperand:
        """Admit an NPB FT field u = ``re`` + i ``im`` of shape (nx, ny, nz)
        with diffusion constant ``alpha``: the forward 3-D transform runs
        on the device once, here, and its spectrum stays resident; each
        ``ft`` request evolves and inverts it for one time step.  The plan
        (:func:`plan_ft`) fixes each pass's signal block and the widest
        group of steps one launch may stack in HBM."""
        import jax.numpy as jnp

        from repro.kernels import fft as fft_k

        sw = Stopwatch().start()
        if self.n_devices > 1:
            raise ValueError("ft has no sharded execution path; register "
                             "it on a single-device registry")
        dtype = np.dtype(float_dtype())
        with obs_trace.phase("ft.register"):
            re, im = np.asarray(re, dtype), np.asarray(im, dtype)
            if re.ndim != 3 or re.shape != im.shape:
                raise ValueError(f"ft field planes must be 3-D and of one "
                                 f"shape, got {re.shape} and {im.shape}")
            plan = plan_ft(re.shape, dtype=str(dtype)).raise_if_invalid()
            op = RegisteredOperand(name=name, kind="ft", signature=None,
                                   n=int(re.size))
            op.ft = {"shape": re.shape, "alpha": float(alpha),
                     "b_blocks": plan.b_blocks, "max_group": plan.max_group}
            op.plans = {"ft": plan}
            sre, sim = fft_k.ft_spectrum(jnp.asarray(re), jnp.asarray(im),
                                         b_blocks=plan.b_blocks)
            op.device_arrays = {"re": sre.block_until_ready(),
                                "im": sim.block_until_ready()}
        return self._admit(op, sw)

    def register_moe(self, name: str, *, n_tokens: int, n_slots: int,
                     d_model: int, top_k: int, c: int = 32,
                     dtype: str | None = None) -> RegisteredOperand:
        """Admit an LM engine's MoE dispatch traffic class.

        Unlike matrices and graphs, the operand itself is transient — the
        token→slot routing matrix changes every decode step — so what
        registers is the *envelope*: up to ``n_tokens`` routing rows of at
        most ``top_k`` stored entries against an ``(n_slots, d_model)``
        expert-output stack, packed at slice height ``c``.  The envelope's
        worst-case :class:`SlabMeta` is preflighted with
        :func:`plan_moe_dispatch` at registration (and re-derived live at
        every submit, like the other kinds), so an engine whose dispatch
        shape cannot launch is refused before any token is decoded.
        """
        sw = Stopwatch().start()
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        dtype = dtype or str(np.dtype(float_dtype()))
        w = pow2_ceil(max(int(top_k), 1))
        meta = SlabMeta(
            kind="matrix", c=int(c), widths=(w,),
            n_slices=(-(-int(n_tokens) // int(c)),),
            n_rows=int(n_tokens), n_cols=int(n_slots),
            val_dtype=dtype, idx_dtype="int32",
        )
        op = RegisteredOperand(name=name, kind="moe", signature=None,
                               n=int(n_tokens), n_cols=int(n_slots))
        op.slab_meta = meta
        op.moe = {"c": int(c), "top_k": int(top_k),
                  "d_model": int(d_model), "dtype": dtype}
        kb = min(64, pow2_ceil(int(d_model)))
        op.plans = {"moe_dispatch": plan_moe_dispatch(
            meta, k=int(d_model), x_dtype=dtype, top_k=int(top_k),
            k_block=kb).raise_if_invalid()}
        return self._admit(op, sw)


def _matrix_device_arrays(slabs: SellSlabs) -> dict:
    import jax.numpy as jnp

    return {
        "cols": tuple(jnp.asarray(c) for c in slabs.bucket_cols),
        "vals": tuple(jnp.asarray(v) for v in slabs.bucket_vals),
        "rows": tuple(jnp.asarray(r) for r in slabs.bucket_rows),
    }


def _graph_layout_meta(in_deg: np.ndarray, c: int, sigma: int) -> SlabMeta:
    """Launch metadata of the reverse-graph slabs ``graph_to_sell_slabs``
    would pack at (c, sigma), split rows included, from the in-degrees
    alone."""
    from repro.sparse.formats import next_pow2, sigma_sort_order, slice_widths

    lengths = split_rows(in_deg)[1]
    widths = next_pow2(slice_widths(lengths, sigma_sort_order(lengths, sigma),
                                    c))
    uniq, counts = np.unique(widths, return_counts=True)
    n = len(in_deg)
    return SlabMeta(
        kind="graph", c=int(c), widths=tuple(int(w) for w in uniq),
        n_slices=tuple(int(k) for k in counts), n_rows=n, n_cols=n,
        val_dtype=None, idx_dtype="int32")


def _graph_device_arrays(slabs, graph: EllpackGraph) -> dict:
    """Out-degrees for PageRank, plus the single-device adjacency slabs
    (``slabs=None`` for a sharded operand, whose slabs live on the mesh)."""
    import jax.numpy as jnp

    arrays = {"out_degree": jnp.asarray(graph.out_degree, float_dtype())}
    if slabs is not None:
        arrays["adj"] = tuple(jnp.asarray(a) for a in slabs.bucket_adj)
        arrays["nodes"] = tuple(jnp.asarray(m) for m in slabs.bucket_nodes)
    return arrays
