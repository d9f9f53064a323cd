"""The benchmark's harness: one run of one cell, driven by ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name the cell gives:

* ``bench/configs/<config>.json`` (sizes, limits) and ``<config>.py`` beside
  it (the ``Workload``: operands and requests from the seed, the plain
  reference, the work counted from the operand's shape);
* ``bench/traffic/<traffic>.json`` (the op, the loop and its parameters),
  driven by the general loop ``bench/traffic/<loop>.py``;
* ``bench/metrics/<metric>.py`` (a ``read(run)`` that returns the number,
  or None where the run has nothing to read).  Where a metric has no file
  of its own, its family's reader serves it: the name before its first
  ``.`` (``idle_share.graph`` -> ``idle_share.py``), or that name's last
  word (``bfs_roofline`` -> ``roofline.py``).

A run registers the operand through ``KernelRegistry``, warms up the
cell's own shapes with the cheapest request of each, drives the traffic
through ``KernelService.submit`` -> ``step`` -> ``poll`` for the window,
reads the device's peak memory, frees the service, and checks the window's
own results against the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import importlib.util
import json
import os
import shutil
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
#: requests still unanswered this long after the window closes never came
ANSWER_WAIT_S = 60.0
#: the ``Driver``'s longest calls (and stretches between calls) kept for the
#: log, each with the CPU time of its thread and process inside it
SLOW_KEPT = 8


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: str):
    """Import the file at ``path`` as a module of its own."""
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` and everything it names."""

    name: str
    chips: int
    config: dict           # the configuration's file, as run
    traffic: dict          # the traffic mix's file
    end_to_end: list       # metric entries this cell reports
    per_layer: list

    @property
    def op(self) -> str:
        return self.traffic["op"]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def find_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files read."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(os.path.join(ROOT, config["file"])),
        traffic=read_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def reader_path(metric: str) -> str:
    """The reader of ``metric``: its own file, else its family's."""
    family = metric.split(".")[0]
    for name in (metric, family, family.rsplit("_", 1)[-1]):
        path = os.path.join(BENCH_DIR, "metrics", name + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for metric {metric!r}")


def workload(cell: Cell, seed: int):
    """The configuration's ``Workload`` for ``seed``."""
    module = load_module(os.path.join(BENCH_DIR, "configs",
                                      cell.config["name"] + ".py"))
    return module.Workload(cell.config, seed)


@dataclasses.dataclass
class Record:
    """One request of the window, on the host's ``perf_counter`` clock."""

    index: int
    due: float
    submitted: float
    done: float | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.done is not None and self.error is None


class Driver:
    """Submits, steps and polls one service; records every request, and
    keeps the results the workload samples for the check.  Keeps its
    :data:`SLOW_KEPT` longest calls, and stretches between calls, with
    when each began and the CPU time of the thread and of the whole
    process inside it: a stall in which neither advances is the process
    held off its cores, not Python or another thread at work."""

    def __init__(self, svc, work, op: str):
        import jax

        self.svc, self.work, self.op = svc, work, op
        self.annotate = jax.profiler.TraceAnnotation
        self.clock = time.perf_counter
        self.answer_wait_s = ANSWER_WAIT_S
        self.records: list[Record] = []
        self.pending: dict[int, Record] = {}
        self.results: dict[int, object] = {}
        self.born = self.clock()
        self._slow: list[tuple] = []
        self._last = self._now()

    def _now(self) -> tuple[float, float, float]:
        return self.clock(), time.thread_time(), time.process_time()

    @property
    def slow(self) -> list[tuple[str, float, float, float, float]]:
        """(call, began s after the ``Driver`` was made, wall s, thread CPU s,
        process CPU s) of the longest calls, longest first."""
        return [(n, t, w, c, p) for w, n, t, c, p in sorted(self._slow,
                                                            reverse=True)]

    def _keep(self, name: str, began: tuple, end: tuple):
        wall, cpu, proc = (b - a for a, b in zip(began, end))
        item = (wall, name, began[0] - self.born, cpu, proc)
        if len(self._slow) < SLOW_KEPT:
            heapq.heappush(self._slow, item)
        elif wall > self._slow[0][0]:
            heapq.heapreplace(self._slow, item)

    def _note(self, name: str, began: tuple) -> None:
        """Keep the call ``name`` begun at ``began``, and the stretch before
        it, if they are among the longest."""
        end = self._now()
        self._keep("between", self._last, began)
        self._keep(name, began, end)
        self._last = end

    def submit(self, i: int, due: float) -> None:
        payload, params = self.work.request(self.op, i)
        began = self._now()
        with self.annotate("submit"):
            rec = Record(index=i, due=due, submitted=began[0])
            rid = self.svc.submit(self.op, self.work.name, payload, **params)
        self.records.append(rec)
        self.pending[rid] = rec
        self._note("submit", began)

    def step(self) -> None:
        began = self._now()
        with self.annotate("step"):
            self.svc.step()
        self._note("step", began)

    def collect(self) -> list[Record]:
        """Poll every pending request; returns those that finished."""
        finished = []
        began = self._now()
        with self.annotate("poll"):
            now = self.clock()
            for rid, rec in list(self.pending.items()):
                try:
                    out = self.svc.poll(rid)
                except RuntimeError as exc:      # the request failed
                    rec.error, out = str(exc), None
                if out is None and rec.error is None:
                    continue
                rec.done = now
                del self.pending[rid]
                if rec.error is None and self.work.keep(self.op, rec.index):
                    # a copy: a view would keep its whole group's output
                    self.results[rec.index] = np.array(out)
                self.svc.release(rid)
                finished.append(rec)
        self._note("poll", began)
        return finished


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float
    window_s: float
    records: list
    stats: dict            # service counters over the window
    work: dict             # the workload's counts of completed work
    peaks: dict
    trace: object = None   # trace_reduce.Trace of a traced run
    trace_window: tuple = (0.0, 0.0)   # the window on the trace's clock

    @property
    def done(self) -> list:
        return [r for r in self.records if r.ok]


def group_widths(traffic: dict, n_slots: int) -> list[int]:
    """The pow2 group widths a traffic mix can form: closed clients always
    step together; an open loop can group anything up to the slot count."""
    def pow2(k):
        return 1 << (max(1, k) - 1).bit_length()
    if traffic["loop"] == "closed":
        return [pow2(min(traffic["clients"], n_slots))]
    return sorted({pow2(k) for k in range(1, n_slots + 1)})


def warmup(svc, work, cell: Cell) -> None:
    """Serve the workload's cheapest request of every shape the window
    will use, so nothing compiles inside it."""
    import jax

    with jax.profiler.TraceAnnotation("warmup"):
        widths = group_widths(cell.traffic, svc.n_slots)
        for group in work.warmup(cell.op, widths):
            rids = [svc.submit(cell.op, work.name, payload, **params)
                    for payload, params in group]
            svc.drain()
            for rid in rids:
                svc.poll(rid)
                svc.release(rid)


class WindowWatch:
    """Counts, for the log, what the process did besides serving while the
    window ran: programs compiled (JAX's monitoring events) and garbage
    collections with the longest pause."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.compiles, self.gc_runs, self.gc_max_s = 0, 0, 0.0
        self._gc_start = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event, duration, **_):
        if self.on and event == self.COMPILE:
            self.compiles += 1

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self.on:
            self.gc_runs += 1
            self.gc_max_s = max(self.gc_max_s,
                                time.perf_counter() - self._gc_start)

    def close(self) -> None:
        self.on = False
        gc.callbacks.remove(self._gc)


def check_device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked, {len(devices)} found")
    return devices


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed path inside
    the checkout (or ``JAX_COMPILATION_CACHE_DIR``), every program kept."""
    import jax

    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        break_path=None) -> tuple[dict, dict]:
    """One run of one cell; returns the result line as a dict, and notes
    for the log (window length, generator lateness, service counters).

    ``break_path`` serves the tests: a hook that breaks the timed path
    after set-up."""
    cell = find_cell(cell_name)
    devices = check_device(cell.chips, require_tpu)
    import jax

    from repro.service import KernelRegistry, KernelService
    from repro.service.tunecache import TuneCache

    from bench import counters, trace_reduce

    enable_cache()
    peaks = counters.peaks(devices[0].device_kind) if require_tpu else {}
    work = workload(cell, seed)
    os.makedirs(CACHE_DIR, exist_ok=True)
    cache = TuneCache(os.path.join(CACHE_DIR, "tune.json"))
    registry = KernelRegistry(cache=cache)
    work.register(registry)
    cache.save()
    svc = KernelService(registry, n_slots=int(cell.traffic["slots"]))
    warmup(svc, work, cell)
    if break_path is not None:
        break_path(svc)
    loop = load_module(os.path.join(BENCH_DIR, "traffic",
                                    cell.traffic["loop"] + ".py"))
    driver = Driver(svc, work, cell.op)
    trace_dir = os.path.join(CACHE_DIR, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = dict(svc.stats)
    watch = WindowWatch()
    watch.on = True
    with jax.profiler.TraceAnnotation("window"):
        t0 = loop.drive(driver, cell.traffic, seconds, seed)
        setup_s = t0 - t_start
    watch.close()
    ends = [r.done for r in driver.records if r.done is not None]
    window_s = (max(ends) if ends else driver.clock()) - t0
    if trace:
        jax.profiler.stop_trace()
    stats = {k: v - before[k] for k, v in svc.stats.items()}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    records, results, slow = driver.records, driver.results, driver.slow
    del driver, svc, registry
    gc.collect()

    checks = work.check(cell.op, results)
    done = [r.index for r in records if r.ok]
    result_run = Run(setup_s=setup_s, window_s=window_s,
                     records=records, stats=stats,
                     work=work.work(cell.op, done, stats), peaks=peaks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    breakdown, longest_gaps = None, None
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                               host_stand_in=not require_tpu)
        lo, hi = tr.span("window")
        first = min((s for n, s, _ in tr.spans
                     if n == "submit" and lo <= s <= hi), default=lo)
        last = max((e for n, _, e in tr.spans
                    if n == "poll" and lo <= e <= hi), default=hi)
        result_run.trace, result_run.trace_window = tr, (first, last)
        device["busy_s"] = trace_reduce.busy(tr, first, last) * 1e-9
        device["window_s"] = (last - first) * 1e-9
        breakdown = {"device_ops": trace_reduce.top_ops(tr, first, last),
                     "idle_gaps": trace_reduce.idle_by_label(tr, first, last)}
        longest_gaps = trace_reduce.longest_gaps(tr, first, last)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(reader_path(m["name"]))
        value = reader.read(result_run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for r in records if not r.ok)
    correct = (failed == 0 and bool(done) and all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in checks))
    line = {"correct": bool(correct), "attempted": len(records),
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    def quantiles(values):
        v = sorted(values)
        return {f"p{q}": v[min(len(v) - 1, int(q / 100 * len(v)))]
                for q in (50, 90, 95, 99, 100)} if v else {}

    notes = {"window_s": window_s, "requests_done": len(done),
             "generator_late_s": quantiles(
                 [r.submitted - r.due for r in records]),
             "latency_s": quantiles([r.done - r.due for r in records
                                     if r.ok]),
             "compiles_in_window": watch.compiles,
             "gc_in_window": watch.gc_runs, "gc_max_s": watch.gc_max_s,
             "slow_calls": slow, "longest_idle_gaps": longest_gaps,
             "stats": stats}
    return line, notes
