"""Request-driven execution engine for the paper's sparse kernels.

:class:`KernelService` turns SpMV / BFS / PageRank / FFT into a serving
surface with the async submit/poll shape of :mod:`repro.serve.engine`:
``submit`` enqueues and returns a request id immediately, ``poll`` reports a
result when one exists, and ``step``/``run``/``drain`` advance the scheduler.

Scheduling is the same slot-based admission loop the LM batcher runs
(:class:`repro.serve.slots.SlotLoop` — one batching core, two engines).  The
service's ``execute`` hook is where kernel-specific coalescing happens: all
active requests against the same registered operand form one group per
scheduling round, and every group collapses into a single launch of the
batched execution core:

* SpMV requests stack their x vectors as RHS columns of ONE
  ``sell_core.spmm_sell`` call (the multi-RHS SpMM kernel, k_block
  co-tuned at registration);
* BFS requests stack their sources, PageRank requests their
  (damping, iters) configurations, as columns of one batched
  ``bfs_sell`` / ``pagerank_sell`` drive;
* FFT requests of equal length stack into a single batched
  ``fft_stockham`` call;
* NPB FT requests (a time step t of a registered field) stack as the
  group axis of one ``ft_step`` program over the resident spectrum, as
  many a launch as the plan lets HBM hold.

``max_queue`` bounds the admission queue: a full queue rejects the submit
with :class:`QueueFull` (counted in ``stats["rejected"]``) instead of
buffering unboundedly — the backpressure signal a fronting load balancer
needs.  Per-request submit/finish timestamps feed
:meth:`latency_percentiles`.

Observability (:mod:`repro.obs`) threads through every stage: ``stats``
is a live view over the service's :class:`~repro.obs.MetricsRegistry`
counters, per-class latency / group-size / launch-wall histograms and
queue-depth / in-flight gauges accumulate alongside, and an optional
:class:`~repro.obs.Tracer` records one span tree per request — including
rejected and failed ones — with batched launch spans fanning in their
group members via links.  Phase spans (:func:`repro.obs.trace.phase`)
mark where each step spends its host time: ``svc.schedule`` (the slot
loop), ``svc.preflight`` (each submit), and per launch ``svc.prepare``
(validate, cast, stack, pad, device put), ``svc.launch`` (the kernel
call; the graph level loop inside it), ``svc.fetch`` (the device wait
and copy back) and ``svc.split`` (results to the group's requests).
The NPB FT step is one program: its parts, ``ft.evolve``, ``ft.pass``
(per ``axis``) and ``ft.sample``, are scopes of that program
(:func:`repro.obs.trace.scope`), on its device operations.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Sequence

import numpy as np

from repro.analysis.launchplan import LaunchPlan, LaunchPlanError
from repro.obs import (
    CounterDict,
    LaunchProfiler,
    MetricsRegistry,
    Span,
    Stopwatch,
    Tracer,
    timer,
)
from repro.obs import trace as obs_trace
from repro.analysis.preflight import (
    plan_bfs_sell,
    plan_fft_stockham,
    plan_ft,
    plan_moe_dispatch,
    plan_pagerank_sell,
    plan_spmm_sell,
    plan_spmm_sell_sharded,
    plan_spmm_sell_stream,
)
from repro.kernels.execspec import ExecSpec
from repro.service.registry import KernelRegistry, RegisteredOperand
from repro.serve.slots import SlotLoop
from repro.sparse.formats import pow2_ceil, widest_k_tile

OPS = ("spmv", "bfs", "pagerank", "fft", "moe_dispatch", "ft")

#: request class of each op for the per-class latency histograms:
#: ``moe_dispatch`` is LM dispatch traffic, everything else is plain kernel
#: traffic (the LM engine's own per-token class, ``lm_token``, is observed
#: by :class:`repro.serve.engine.ServeEngine` into the same registry)
OP_CLASS = {op: ("moe_dispatch" if op == "moe_dispatch" else "kernel")
            for op in OPS}

#: FROZEN contract: the exact key set of ``KernelService.stats``.  These
#: names are observability API — dashboards and the bench gate
#: (``scripts/bench_compare.py`` zero-base counters) key on them, so
#: renaming or removing one is a breaking change; additions append here.
#: The SOURCE OF TRUTH is the service's metrics registry: each key is a
#: live :class:`repro.obs.Counter` under the same name, and ``stats`` is
#: the :class:`repro.obs.CounterDict` view over them — the dict spelling
#: and ``registry.snapshot()`` agree by construction.
STATS_KEYS = (
    "submitted",            # requests admitted (post-preflight)
    "served",               # requests retired with a result
    "failed",               # requests retired with an error
    "rejected",             # submits refused by QueueFull backpressure
    "steps",                # scheduler rounds executed
    "groups",               # coalesced (op, operand, spec) groups formed
    "coalesced",            # requests that shared a group with >= 1 other
    "max_group",            # largest group size seen
    "launches",             # batched core launches (one per group)
    "preflight_rejected",   # submits refused by a LaunchPlan violation
    "streamed_launches",    # launches on the out-of-VMEM streaming path
    "sharded_launches",     # launches on the multi-device sharded path
    "moe_dispatch_launches",  # batched MoE combine launches (LM serving)
    "graph_steps",          # BFS levels / PageRank power steps launched
    "graph_slots",          # padded slots x state columns those steps swept
    "ft_steps",             # NPB FT time steps served
    "ft_axis_passes",       # batched 1-D FFT passes of those launches
)


def _moe_k_block(d_model: int) -> int:
    """RHS tile of the MoE combine SpMM.  Unlike SpMV traffic (few stacked
    vectors), the combine's RHS is the full d_model-wide activation stack,
    so the tile tracks the model width: wider k tiles mean fewer grid
    cells, which is where the SELL path's win over the dense counterfactual
    comes from.  Capped at 64 lanes; the launch plan still preflights the
    resulting VMEM footprint."""
    from repro.kernels.sell_core import pow2_ceil as _p2

    return min(64, _p2(max(1, d_model)))


class QueueFull(RuntimeError):
    """The service's admission queue is at ``max_queue``; retry after a
    ``step`` (or shed the request upstream)."""


def _pow2_pad(items: list) -> list:
    """Pad a request-column list to the next power of two by repeating the
    last element.  The padding columns compute throwaway results; what they
    buy is a bounded set of compiled batch shapes (k in {1, 2, 4, ...})
    across arbitrary coalesced group sizes.

    Single k-padding policy: this is the ONLY padding the service applies,
    and a power-of-two k is a fixpoint of the core's
    :func:`repro.kernels.sell_core.padded_k` — so the group's columns are
    never padded a second time inside ``spmm_sell``/``spmm_sell_stream``
    (asserted at the ops boundary, ``ops._spmm_slabs``)."""
    from repro.kernels.sell_core import pow2_ceil

    return items + [items[-1]] * (pow2_ceil(len(items)) - len(items))


def _block_diagonal(payloads: list, dtype: str) -> tuple:
    """Stack MoE combine requests block-diagonally: request i's tokens
    occupy rows [row_off_i, row_off_i + n_tok_i), its slots the matching
    column band — one operand, one launch.  Returns the CSR operand, the
    stacked expert outputs and each request's row span."""
    from repro.sparse.formats import CSRMatrix

    indptrs, indices_all, data_all, xs, spans = [np.zeros(1, np.int64)], \
        [], [], [], []
    row_off = col_off = nnz_off = 0
    for indptr, indices, data, x in payloads:
        spans.append((row_off, row_off + indptr.shape[0] - 1))
        indptrs.append(indptr[1:] + nnz_off)
        indices_all.append(indices + col_off)
        data_all.append(data)
        xs.append(x)
        row_off += indptr.shape[0] - 1
        col_off += x.shape[0]
        nnz_off += int(indptr[-1])
    csr = CSRMatrix(
        indptr=np.concatenate(indptrs),
        indices=np.concatenate(indices_all).astype(np.int32)
        if indices_all else np.zeros(0, np.int32),
        data=np.concatenate(data_all)
        if data_all else np.zeros(0, np.dtype(dtype)),
        n_cols=col_off,
    )
    return csr, np.vstack(xs), spans


@dataclasses.dataclass
class SubmitRequest:
    """Typed submission: the one structure admission reads end to end.

    ``KernelService.submit`` accepts this in place of the positional
    ``(op, operand, payload, **params)`` spelling; the attached
    :class:`~repro.kernels.execspec.ExecSpec` feeds preflight-at-admission,
    the coalescing key (requests only coalesce when their specs agree),
    and the mesh placement — one structure instead of loose strings.
    """

    op: str                     # one of OPS
    operand: str                # registry name
    payload: Any = None         # x vector / (b, n) signal / None
    params: dict = dataclasses.field(default_factory=dict)
    spec: ExecSpec | None = None


@dataclasses.dataclass
class KernelRequest:
    rid: int
    op: str                     # one of OPS
    operand: str                # registry name
    payload: Any = None         # x vector / (b, n) signal / None
    params: dict = dataclasses.field(default_factory=dict)
    spec: ExecSpec | None = None
    result: Any = None
    error: str | None = None
    submit_t: float = 0.0       # obs timer.now_s() at submit
    done_t: float = 0.0         # obs timer.now_s() when the result landed
    # trace spans (None when the service runs without a tracer): the
    # request root, its queued-stage child, its execute-stage child
    span: Span | None = None
    queued_span: Span | None = None
    exec_span: Span | None = None

    @property
    def done(self) -> bool:
        return self.result is not None or self.error is not None

    @property
    def group_key(self) -> tuple:
        """Coalescing identity: requests collapse into one launch only when
        op, operand AND execution spec agree (a spec-less request uses the
        default-spec key, so legacy submits coalesce exactly as before)."""
        spec = self.spec if self.spec is not None else _DEFAULT_SPEC
        return (self.op, self.operand, spec.coalesce_key())


_DEFAULT_SPEC = ExecSpec()


class KernelService(SlotLoop[KernelRequest]):
    """Micro-batching scheduler over a :class:`KernelRegistry`."""

    def __init__(self, registry: KernelRegistry, n_slots: int = 8,
                 interpret: bool | None = None,
                 max_queue: int | None = None,
                 metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        super().__init__(n_slots)
        from repro.kernels.backend import resolve_interpret

        if max_queue is not None and max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 (or None for unbounded), got "
                f"{max_queue}: a zero-capacity queue rejects every submit "
                "and the reject-then-step retry pattern would spin forever")
        self.registry = registry
        self.interpret = resolve_interpret(interpret)
        self.max_queue = max_queue
        self._next_rid = 0
        self._by_rid: dict[int, KernelRequest] = {}
        # bounded window: a long-running server must not grow one float per
        # request served forever; percentiles describe recent traffic
        self._latencies_us: deque[float] = deque(maxlen=8192)
        # observability: the metrics registry is the source of truth for
        # every counter; ``stats`` is the frozen-contract dict view over it
        # (built from the frozen tuple so the live dict can never drift
        # from the documented key set).  ``tracer=None`` disables span
        # recording entirely — the hot path pays one None check.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = CounterDict(self.metrics, STATS_KEYS)
        self.tracer = tracer
        self.profiler = LaunchProfiler()
        self._g_queue = self.metrics.gauge(
            "queue_depth", "admission queue length after slot fill")
        self._g_inflight = self.metrics.gauge(
            "in_flight", "occupied slots this scheduling round")
        self._g_vmem = self.metrics.gauge(
            "planned_vmem_bytes", "peak VMEM of the last preflighted plan")

    # -- async API ---------------------------------------------------------
    def submit(self, op: str | SubmitRequest, operand: str | None = None,
               payload: Any = None, *, spec: ExecSpec | None = None,
               **params) -> int:
        """Enqueue one kernel request; returns its request id immediately.

        Two spellings are admitted.  The typed form passes a
        :class:`SubmitRequest` as the sole positional argument — its
        :class:`~repro.kernels.execspec.ExecSpec` rides along into
        admission preflight and the coalescing key.  The positional form
        ``submit(op, operand, payload, **params)`` is unchanged (an
        optional ``spec=`` keyword attaches a spec there too).

        Raises :class:`QueueFull` (and counts the rejection) when
        ``max_queue`` requests are already waiting — backpressure belongs
        to the caller, not to an unbounded buffer.
        """
        if isinstance(op, SubmitRequest):
            if operand is not None or payload is not None or params or \
                    spec is not None:
                raise TypeError(
                    "submit(SubmitRequest) takes no other arguments; put "
                    "operand/payload/params/spec on the request object")
            treq = op
            op, operand, payload = treq.op, treq.operand, treq.payload
            params, spec = dict(treq.params), treq.spec
        # trace completeness invariant: EVERY submit attempt — including
        # validation failures, preflight rejections and QueueFull — retires
        # exactly one closed root span, so the root starts before any check
        # can raise and every exit path below closes it.
        root = self._t_start("request", op=str(op), operand=str(operand))
        try:
            if op not in OPS:
                raise ValueError(f"unknown op {op!r}: expected one of {OPS}")
            if spec is not None and not isinstance(spec, ExecSpec):
                raise TypeError(
                    f"spec must be an ExecSpec, got {type(spec).__name__}")
            record = self.registry.get(operand)  # fail fast: unknown operand
            with obs_trace.children_of(self.tracer, root), \
                    obs_trace.phase("svc.preflight") as pre:
                try:
                    self._preflight(op, record)  # ... infeasible launches
                except LaunchPlanError:
                    self._t_end(pre, status="rejected")
                    raise
            if self.max_queue is not None and \
                    len(self.queue) >= self.max_queue:
                self.stats["rejected"] += 1
                raise QueueFull(
                    f"admission queue is full ({self.max_queue} waiting); "
                    "step() the service or shed load")
        except QueueFull:
            self._t_end(root, status="rejected", reason="queue_full")
            raise
        except LaunchPlanError:
            self._t_end(root, status="rejected", reason="preflight")
            raise
        except BaseException:
            self._t_end(root, status="error")
            raise
        rid = self._next_rid
        self._next_rid += 1
        req = KernelRequest(rid=rid, op=op, operand=operand,
                            payload=payload, params=dict(params), spec=spec,
                            submit_t=timer.now_s(), span=root)
        if root is not None:
            root.attrs["rid"] = rid
            req.queued_span = self._t_start("queued", parent=root)
        self._by_rid[rid] = req
        super().submit(req)
        self.stats["submitted"] += 1
        return rid

    # -- tracing helpers (no-ops when the service has no tracer) -----------
    def _t_start(self, name: str, parent: Span | None = None,
                 links=(), **attrs) -> Span | None:
        if self.tracer is None:
            return None
        return self.tracer.start(name, parent=parent, links=links, **attrs)

    def _t_end(self, span: Span | None, status: str = "ok", **attrs) -> None:
        if self.tracer is not None:
            self.tracer.end(span, status=status, **attrs)

    def poll(self, rid: int) -> Any | None:
        """Result of request ``rid`` if it finished, else None.  Raises on a
        failed request (the error travels to the caller, not the log)."""
        req = self._by_rid[rid]
        if req.error is not None:
            raise RuntimeError(f"request {rid} ({req.op}) failed: {req.error}")
        return req.result

    def release(self, rid: int) -> None:
        """Drop a delivered request and its result.  Long-running servers
        call this after ``poll`` shows the request finished — without it
        every request's result array is retained for the life of the
        service.  Releasing an unfinished request is refused (it would
        complete later and land in ``completed`` with no handle left to
        remove it — the exact leak this method exists to prevent)."""
        req = self._by_rid.get(rid)
        if req is None:
            return
        if not req.done:
            raise ValueError(
                f"request {rid} has not finished; poll() until it completes "
                "before releasing it")
        self._by_rid.pop(rid)
        # a finished request may still be sitting in its slot (released
        # between execute and the next eviction round): clear the slot so
        # _evict_done cannot resurrect it into `completed` later
        for i, occupant in enumerate(self.slots):
            if occupant is req:
                self.retire(req)           # keep served/failed stats honest
                self.slots[i] = None
                return
        try:
            self.completed.remove(req)
        except ValueError:
            pass

    def drain(self, max_steps: int = 10_000) -> list[KernelRequest]:
        """Run the loop until every submitted request completes."""
        return self.run(max_steps=max_steps)

    def latency_percentiles(self) -> dict[str, float]:
        """p50/p95/p99 of request latency (submit -> result landed), in us,
        over the most recent 8192 retired requests (bounded window).
        Empty service reports zeros."""
        if not self._latencies_us:
            return {"p50_us": 0.0, "p95_us": 0.0, "p99_us": 0.0}
        lat = np.asarray(self._latencies_us)
        p50, p95, p99 = np.percentile(lat, [50, 95, 99])
        return {
            "p50_us": round(float(p50), 1),
            "p95_us": round(float(p95), 1),
            "p99_us": round(float(p99), 1),
        }

    # -- launch preflight --------------------------------------------------
    def _operand_plans(self, record: RegisteredOperand) -> dict[str, LaunchPlan]:
        """Live launch plans for every op this operand can serve, derived
        from the *current* tuned tiles (not the registration snapshot): a
        tune that drifts out of the VMEM envelope after registration is
        caught at the next submit."""
        plans: dict[str, LaunchPlan] = {}
        if record.kind == "matrix" and record.slab_meta is not None:
            tuned = record.tuned
            # worst case: the widest RHS tile any coalesced group runs
            k = widest_k_tile(tuned.k_block)
            if record.mode == "sharded":
                plans["spmv"] = plan_spmm_sell_sharded(
                    record.slab_meta, k=k,
                    x_dtype=record.slab_meta.val_dtype,
                    n_devices=self.registry.n_devices,
                    w_block=tuned.w_block, k_block=tuned.k_block,
                    window_cols=record.sharded.window_cols)
            elif record.mode == "stream":
                plans["spmv"] = plan_spmm_sell_stream(
                    record.slab_meta, k=k,
                    x_dtype=record.slab_meta.val_dtype,
                    w_block=tuned.w_block, k_block=tuned.k_block,
                    col_tile=tuned.col_tile, row_tile=tuned.row_tile)
            else:
                plans["spmv"] = plan_spmm_sell(
                    record.slab_meta, k=k,
                    x_dtype=record.slab_meta.val_dtype,
                    w_block=tuned.w_block, k_block=tuned.k_block)
        elif record.kind == "graph" and record.slab_meta is not None:
            # worst case: a full coalesced group, pow2-padded
            k = pow2_ceil(max(1, self.n_slots))
            plans["bfs"] = plan_bfs_sell(record.slab_meta, k=k)
            plans["pagerank"] = plan_pagerank_sell(
                record.slab_meta, k=k,
                dtype=str(record.device_arrays["out_degree"].dtype))
        elif record.kind == "fft":
            plans["fft"] = plan_fft_stockham(
                record.n, batch=8,
                dtype=str(record.device_arrays["wre"].dtype))
        elif record.kind == "ft":
            ft = record.ft
            plans["ft"] = plan_ft(
                ft["shape"], k=max(1, min(ft["max_group"],
                                          pow2_ceil(self.n_slots))),
                dtype=str(record.device_arrays["re"].dtype))
        elif record.kind == "moe" and record.slab_meta is not None:
            m = record.moe
            plans["moe_dispatch"] = plan_moe_dispatch(
                record.slab_meta, k=m["d_model"], x_dtype=m["dtype"],
                top_k=m["top_k"], k_block=_moe_k_block(m["d_model"]))
        return plans

    def _preflight(self, op: str, record: RegisteredOperand) -> None:
        """Admission-time launch-contract check: an operand whose plan
        violates a contract (VMEM budget, pow2 tiles, dtype flow) is
        rejected HERE with a structured :class:`LaunchPlanError` — no
        kernel launch, no opaque XLA failure deep inside a request."""
        plan = self._operand_plans(record).get(op)
        if plan is None:                # op/kind mismatch: fails at execute
            return
        self._g_vmem.set(plan.peak_vmem_bytes)
        try:
            plan.raise_if_invalid()
        except LaunchPlanError:
            self.stats["preflight_rejected"] += 1
            raise

    def plans(self) -> dict[str, dict[str, dict]]:
        """Observability: the current launch-plan summary for every
        registered operand.

        FROZEN contract: the outer key is the registered operand *name*,
        the inner key is the *op* it can serve (``spmv`` / ``bfs`` /
        ``pagerank`` / ``fft``), and each leaf is
        :meth:`repro.analysis.launchplan.LaunchPlan.summary` verbatim
        (``kernel``, ``ok``, ``n_launches``, ``peak_vmem_bytes``,
        ``resident_bytes``, ``violations``).  Dashboards key on these
        names; renames are breaking changes.

        The schema reference lives with the producing types —
        ``LaunchPlan.summary`` for plan leaves, the service's
        :class:`~repro.obs.MetricsRegistry` (``self.metrics``) for every
        counter/gauge/histogram name — not in downstream docs."""
        return {
            name: {op: plan.summary()
                   for op, plan in
                   self._operand_plans(self.registry.get(name)).items()}
            for name in self.registry.names()
        }

    # -- SlotLoop hooks ----------------------------------------------------
    def done(self, req: KernelRequest) -> bool:
        return req.done

    def admit(self, slot: int, req: KernelRequest) -> None:
        # queue residency ends, slot residency begins
        self._t_end(req.queued_span)
        if req.span is not None:
            req.exec_span = self._t_start("execute", parent=req.span,
                                          slot=slot)

    def observe_step(self, queued: int, in_flight: int) -> None:
        self._g_queue.set(queued)
        self._g_inflight.set(in_flight)

    def retire(self, req: KernelRequest) -> None:
        ok = req.error is None
        self.stats["served" if ok else "failed"] += 1
        if req.done_t:
            lat_us = (req.done_t - req.submit_t) * 1e6
            self._latencies_us.append(lat_us)
            cls = OP_CLASS.get(req.op, "kernel")
            self.metrics.histogram(
                f"latency_us_class_{cls}",
                f"submit->result latency of the {cls} request "
                "class").observe(lat_us)
        status = "ok" if ok else "error"
        self._t_end(req.queued_span)   # idempotent: usually closed at admit
        self._t_end(req.exec_span, status=status)
        if ok:
            self._t_end(req.span)
        else:
            self._t_end(req.span, status="error", error=req.error)

    def execute(self, active: Sequence[tuple[int, KernelRequest]]) -> None:
        self.stats["steps"] += 1
        groups: dict[tuple, list[KernelRequest]] = {}
        for _, req in active:
            if not req.done:
                groups.setdefault(req.group_key, []).append(req)
        for (op, operand, _speckey), reqs in groups.items():
            self.stats["groups"] += 1
            self.stats["max_group"] = max(self.stats["max_group"], len(reqs))
            if len(reqs) > 1:
                self.stats["coalesced"] += len(reqs)
            self.metrics.histogram(
                "group_size", "requests per coalesced launch group"
            ).observe(len(reqs))
            # the fan-in point: ONE launch span, linked to the root span of
            # every request it serves (N request trees -> one batched call)
            launch = self._t_start(
                "launch", op=op, operand=operand, group_size=len(reqs),
                links=[r.span for r in reqs if r.span is not None])
            try:
                with obs_trace.children_of(self.tracer, launch):
                    self._run_group(op, self.registry.get(operand), reqs)
            except Exception as exc:  # noqa: BLE001 - errors belong to requests
                for req in reqs:
                    if not req.done:
                        req.error = f"{type(exc).__name__}: {exc}"
                self._t_end(launch, status="error")
            else:
                self._t_end(launch)
        now = timer.now_s()
        for _, req in active:
            if req.done and not req.done_t:
                req.done_t = now

    # -- kernel dispatch ---------------------------------------------------
    def _run_group(self, op: str, operand: RegisteredOperand,
                   reqs: list[KernelRequest]) -> None:
        runner = getattr(self, f"_run_{op}")
        runner(operand, reqs)

    def _count_launch(self, operand: RegisteredOperand, *,
                      op: str | None = None,
                      wall_us: float | None = None) -> None:
        """The launch-counter hook: one batched core call per coalesced
        group, visible in ``stats['launches']`` and per operand.  When the
        caller measured the call (``op`` + ``wall_us``), the launch also
        lands in the wall-time histogram and the launch profiler — paired
        with the operand's static preflight plan so planned-vs-measured
        residuals are queryable (:meth:`repro.obs.LaunchProfiler.residuals`)."""
        self.stats["launches"] += 1
        operand.launches += 1
        if op is not None and wall_us is not None:
            self.metrics.histogram(
                f"launch_wall_us_{op}",
                f"measured wall time of batched {op} launches").observe(wall_us)
            self.profiler.record(
                op=op, operand=operand.name, wall_us=wall_us,
                plan=operand.plans.get(op))

    def _fetched(self, operand: RegisteredOperand, op: str, out,
                 sw: Stopwatch):
        """Bring a launch's result to the host (``svc.fetch``: the wait for
        the device and the copy back), stop the launch's stopwatch and
        count the launch.  Returns numpy arrays (a tuple stays a tuple)."""
        with obs_trace.phase("svc.fetch"):
            out = tuple(map(np.asarray, out)) if isinstance(out, tuple) \
                else np.asarray(out)
        sw.stop()
        self._count_launch(operand, op=op, wall_us=sw.elapsed_us)
        return out

    @staticmethod
    def _validated(reqs: list[KernelRequest], check) -> tuple[list, list]:
        """Validate each request's payload BEFORE stacking the group: a
        malformed request fails alone, never its coalesced groupmates.
        Returns (good requests, their checked payloads)."""
        good, payloads = [], []
        for req in reqs:
            try:
                payloads.append(check(req))
            except Exception as exc:  # noqa: BLE001 - belongs to the request
                req.error = f"{type(exc).__name__}: {exc}"
                continue
            good.append(req)
        return good, payloads

    def _run_spmv(self, operand, reqs):
        """The whole group is ONE batched core launch: request vectors
        become RHS columns.  Operands registered on the streaming schedule
        (``mode == "stream"`` — resident footprint over the VMEM budget)
        run the out-of-VMEM ``spmm_sell_stream`` pipeline instead, counted
        in ``stats['streamed_launches']``."""
        from repro.kernels import sell_core

        if operand.kind != "matrix":
            raise TypeError(f"operand {operand.name!r} is not a matrix")
        import jax.numpy as jnp

        arrs, tuned = operand.device_arrays, operand.tuned
        n_cols = operand.n_cols
        dtype = np.dtype(operand.slab_meta.val_dtype)

        def check(req):
            # JAX clamps out-of-bounds gathers, so a wrong-sized x would
            # return garbage as a "success" — validate explicitly
            x = np.asarray(req.payload, dtype)
            if x.shape != (n_cols,):
                raise ValueError(f"x must have shape ({n_cols},), got {x.shape}")
            return x

        with obs_trace.phase("svc.prepare"):
            good, xs = self._validated(reqs, check)
            if not good:
                return
            # pow2-pad the RHS stack BEFORE the jitted core: jax.jit keys
            # on the pre-pad (n_cols, k) shape, so without this every
            # distinct group size would trace its own program (_pow2_pad)
            x_stack = jnp.asarray(np.stack(_pow2_pad(xs), axis=1))
        sw = Stopwatch().start()
        with obs_trace.phase("svc.launch"):
            if operand.mode == "sharded":
                from repro.kernels import sell_shard

                y = sell_shard.spmm_sell_sharded(
                    operand.sharded, x_stack, mesh=self.registry.mesh,
                    w_block=tuned.w_block, k_block=tuned.k_block,
                    interpret=self.interpret,
                )
                self.stats["sharded_launches"] += 1
            elif operand.mode == "stream":
                y = sell_core.spmm_sell_stream(
                    arrs["cols"], arrs["vals"], arrs["rows"], x_stack,
                    n_rows=operand.n, w_block=tuned.w_block,
                    k_block=tuned.k_block, col_tile=tuned.col_tile,
                    row_tile=tuned.row_tile, interpret=self.interpret,
                )
                self.stats["streamed_launches"] += 1
            else:
                y = sell_core.spmm_sell(
                    arrs["cols"], arrs["vals"], arrs["rows"], x_stack,
                    n_rows=operand.n, w_block=tuned.w_block,
                    k_block=tuned.k_block, interpret=self.interpret,
                )
        y = self._fetched(operand, "spmv", y, sw)
        with obs_trace.phase("svc.split"):
            for i, req in enumerate(good):
                req.result = y[:, i]

    def _count_graph_steps(self, operand, steps: int, columns: int) -> None:
        """Node steps a graph launch ran, and the slots they swept: every
        step reads each padded slot once per state column."""
        self.stats["graph_steps"] += steps
        self.stats["graph_slots"] += (
            steps * operand.split["padded_slots"] * int(columns))

    def _run_bfs(self, operand, reqs):
        """The whole group is one batched drive: sources become frontier
        columns, every level is a single launch set."""
        from repro.kernels import bfs as bfs_k

        if operand.kind != "graph":
            raise TypeError(f"operand {operand.name!r} is not a graph")
        arrs = operand.device_arrays

        def check(req):
            source = int(req.params.get("source", 0))
            if not 0 <= source < operand.n:
                raise ValueError(f"source {source} out of range [0, {operand.n})")
            return source

        with obs_trace.phase("svc.prepare"):
            good, sources = self._validated(reqs, check)
            if not good:
                return
            # a singleton group keeps the 1-D fast path (no RHS axis to
            # drag through every gather); larger groups batch sources as
            # columns, padded to a power of two (repeat the last source)
            # so 1..n_slots group sizes share log2 compiled programs
            batch = sources[0] if len(good) == 1 else _pow2_pad(sources)
        sw = Stopwatch().start()
        with obs_trace.phase("svc.launch"):
            if operand.sharded is not None:
                from repro.kernels import sell_shard

                dist = sell_shard.bfs_sell_sharded(
                    operand.sharded, batch, mesh=self.registry.mesh,
                    interpret=self.interpret,
                )
                self.stats["sharded_launches"] += 1
            else:
                dist = bfs_k.bfs_sell(
                    arrs["adj"], arrs["nodes"], operand.n, batch,
                    interpret=self.interpret,
                )
        dist = self._fetched(operand, "bfs", dist, sw)
        self._count_graph_steps(operand, bfs_k.levels_run(dist),
                                np.size(batch))
        with obs_trace.phase("svc.split"):
            if len(good) == 1:
                good[0].result = dist
            else:
                for i, req in enumerate(good):
                    req.result = dist[:, i]

    def _run_pagerank(self, operand, reqs):
        """The whole group is one batched drive: (damping, iters) configs
        become iterate columns, every power step is a single launch set."""
        from repro.kernels import pagerank as pr_k

        if operand.kind != "graph":
            raise TypeError(f"operand {operand.name!r} is not a graph")
        arrs = operand.device_arrays

        def check(req):
            return (float(req.params.get("damping", 0.85)),
                    int(req.params.get("iters", 20)))

        with obs_trace.phase("svc.prepare"):
            good, configs = self._validated(reqs, check)
            if not good:
                return
            if len(good) == 1:                 # 1-D fast path (_run_bfs)
                damping, iters = configs[0]
            else:                              # pow2-padded columns, ditto
                configs = _pow2_pad(configs)
                damping = [d for d, _ in configs]
                iters = [i for _, i in configs]
        sw = Stopwatch().start()
        with obs_trace.phase("svc.launch"):
            if operand.sharded is not None:
                from repro.kernels import sell_shard

                rank = sell_shard.pagerank_sell_sharded(
                    operand.sharded, arrs["out_degree"],
                    mesh=self.registry.mesh, damping=damping, iters=iters,
                    interpret=self.interpret,
                )
                self.stats["sharded_launches"] += 1
            else:
                rank = pr_k.pagerank_sell(
                    arrs["adj"], arrs["nodes"], arrs["out_degree"],
                    operand.n, damping=damping, iters=iters,
                    interpret=self.interpret,
                )
        rank = self._fetched(operand, "pagerank", rank, sw)
        self._count_graph_steps(operand, int(np.max(iters)), np.size(iters))
        with obs_trace.phase("svc.split"):
            if len(good) == 1:
                good[0].result = rank
            else:
                for i, req in enumerate(good):
                    req.result = rank[:, i]

    def _run_fft(self, operand, reqs):
        """True micro-batch: stack every request's signal rows into one
        batched Stockham call against the operand's precomputed twiddles.
        A payload is a real signal batch, or a tuple ``(re, im)`` of the
        split planes of a complex one."""
        from repro.kernels import fft as fft_k

        if operand.kind != "fft":
            raise TypeError(f"operand {operand.name!r} is not an fft plan")
        import jax.numpy as jnp

        n = operand.n
        dtype = operand.device_arrays["wre"].dtype

        def check(req):
            split = isinstance(req.payload, tuple)
            planes = req.payload if split else (req.payload,)
            if split and len(planes) != 2:
                raise ValueError(f"split planes are a tuple (re, im), got "
                                 f"{len(planes)} items")
            if any(np.iscomplexobj(p) for p in planes):
                # a real-dtype cast would silently drop the imaginary plane
                raise TypeError("complex signals are not supported; "
                                "pass split re/im planes as a tuple (re, im)")
            re = np.atleast_2d(np.asarray(planes[0], dtype))
            im = (np.atleast_2d(np.asarray(planes[1], dtype)) if split
                  else np.zeros_like(re))
            if re.shape != im.shape:
                raise ValueError(f"re and im planes differ in shape: "
                                 f"{re.shape} != {im.shape}")
            if re.ndim != 2:
                raise ValueError(f"signal must be 1-D or 2-D (batch, n), "
                                 f"got shape {re.shape}")
            if re.shape[0] == 0:
                raise ValueError("empty signal batch (0 rows)")
            if re.shape[-1] != n:
                raise ValueError(f"signal length {re.shape[-1]} != "
                                 f"registered fft length {n}")
            return re, im

        with obs_trace.phase("svc.prepare"):
            good, sigs = self._validated(reqs, check)
            if not good:
                return
            spans, lo = [], 0
            for re, _ in sigs:
                spans.append((lo, lo + re.shape[0]))
                lo += re.shape[0]
            batch_re = jnp.asarray(np.concatenate([r for r, _ in sigs]))
            batch_im = jnp.asarray(np.concatenate([i for _, i in sigs]))
        sw = Stopwatch().start()
        with obs_trace.phase("svc.launch"):
            out = fft_k.fft_stockham(
                batch_re, batch_im,
                operand.device_arrays["wre"], operand.device_arrays["wim"],
                b_block=min(8, batch_re.shape[0]), interpret=self.interpret,
            )
        re, im = self._fetched(operand, "fft", out, sw)
        with obs_trace.phase("svc.split"):
            for req, (lo, hi) in zip(good, spans):
                req.result = (re[lo:hi], im[lo:hi])

    def _run_ft(self, operand, reqs):
        """Each request is one NPB FT time step t >= 0 (the payload, an int)
        of the operand's resident spectrum; its result is a (1025,) complex
        array, u_t at the 1024 checksum points and last the checksum
        (:func:`repro.kernels.fft.ft_step`).  A group's steps stack as the
        program's group axis, at most the plan's ``max_group`` a launch,
        each launch padded to a power of two."""
        from repro.kernels import fft as fft_k

        if operand.kind != "ft":
            raise TypeError(f"operand {operand.name!r} is not an ft field")
        import jax.numpy as jnp

        ft, arrs = operand.ft, operand.device_arrays

        def check(req):
            t = req.payload
            if isinstance(t, (bool, np.bool_)) or not isinstance(
                    t, (int, np.integer)):
                raise TypeError(f"ft payload must be an int time step, got "
                                f"{type(t).__name__}")
            if t < 0:
                raise ValueError(f"ft time step must be >= 0, got {t}")
            return int(t)

        with obs_trace.phase("svc.prepare"):
            good, steps = self._validated(reqs, check)
        width = max(1, ft["max_group"])
        for lo in range(0, len(good), width):
            part = good[lo:lo + width]
            with obs_trace.phase("svc.prepare"):
                # cast on the host: jnp.asarray(x, dtype) of a float64
                # array runs a device program of its own
                decay = jnp.asarray(fft_k.ft_decay(
                    ft["alpha"], _pow2_pad(steps[lo:lo + width])
                ).astype(arrs["re"].dtype))
            sw = Stopwatch().start()
            with obs_trace.phase("svc.launch"):
                out = fft_k.ft_step(arrs["re"], arrs["im"], decay,
                                    b_blocks=ft["b_blocks"],
                                    interpret=self.interpret)
            out = self._fetched(operand, "ft", out, sw)
            self.stats["ft_steps"] += len(part)
            self.stats["ft_axis_passes"] += fft_k.FT_AXES
            with obs_trace.phase("svc.split"):
                for i, req in enumerate(part):
                    req.result = out[i]

    def _run_moe_dispatch(self, operand, reqs):
        """The whole group is ONE batched combine SpMM: each request's
        per-step routing matrix becomes a block of a block-diagonal
        operand, the expert-output stacks concatenate as its RHS rows, and
        one SELL launch produces every request's combined activations.
        This is the fusion point where ServeEngine's MoE traffic coalesces
        with kernel traffic on the shared slot loop."""
        from repro.kernels import ops

        if operand.kind != "moe":
            raise TypeError(f"operand {operand.name!r} is not a moe envelope")
        m = operand.moe
        d, top_k = m["d_model"], m["top_k"]

        def check(req):
            p = req.payload
            if not isinstance(p, dict):
                raise TypeError("moe_dispatch payload must be a dict with "
                                "indptr/indices/data/x")
            indptr = np.asarray(p["indptr"], np.int64)
            indices = np.asarray(p["indices"], np.int32)
            data = np.asarray(p["data"], np.dtype(m["dtype"]))
            x = np.asarray(p["x"], np.dtype(m["dtype"]))
            if x.ndim != 2 or x.shape[1] != d:
                raise ValueError(
                    f"x must have shape (n_slots, {d}), got {x.shape}")
            n_tok = indptr.shape[0] - 1
            if n_tok < 1 or n_tok > operand.n:
                raise ValueError(
                    f"routing rows {n_tok} outside the registered envelope "
                    f"(0, {operand.n}]")
            widths = np.diff(indptr)
            if widths.min(initial=0) < 0 or len(indices) != indptr[-1] \
                    or len(data) != indptr[-1]:
                raise ValueError("malformed routing CSR")
            if widths.max(initial=0) > top_k:
                raise ValueError(
                    f"routing row carries {int(widths.max())} entries, "
                    f"envelope top_k is {top_k}")
            if indices.size and (indices.min() < 0
                                 or indices.max() >= x.shape[0]):
                raise ValueError("routing column index out of range")
            return (indptr, indices, data, x)

        with obs_trace.phase("svc.prepare"):
            good, payloads = self._validated(reqs, check)
            if not good:
                return
            csr, x_stack, spans = _block_diagonal(payloads, m["dtype"])
        spec = ExecSpec(dispatch="sell", vl=m["c"],
                        k_block=_moe_k_block(d),
                        interpret=self.interpret)
        sw = Stopwatch().start()
        with obs_trace.phase("svc.launch"):
            y = ops.moe_dispatch(csr, x_stack, spec=spec, top_k=top_k)
        self.stats["moe_dispatch_launches"] += 1
        y = self._fetched(operand, "moe_dispatch", y, sw)
        with obs_trace.phase("svc.split"):
            for req, (lo, hi) in zip(good, spans):
                req.result = y[lo:hi]
