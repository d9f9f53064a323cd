"""Request tracing and phase spans: spans, ring buffer, JSONL exporter.

A :class:`Span` is one timed stage of one request's life (``request`` →
``preflight`` / ``queued`` / ``execute``) or one batched launch.  Spans
form trees through ``parent_id`` and fan *in* through ``links``: a
coalesced launch span links the root spans of every request it serves, so
one batched core call is queryable from any of its N requests and vice
versa.  ``trace_id`` names the tree (the root span's id), which is what
the completeness invariant counts: every submitted request — including
rejected and failed ones — must retire exactly one closed root span.

Closed spans land in a bounded ring buffer (a long-running server must
not grow one span per request forever); ``dropped`` counts evictions so
an exporter can state its own truncation.  :meth:`Tracer.export_jsonl`
writes one span per line, the ``scripts/obs_report.py`` dashboard input.

:func:`phase` marks where the program is inside a request or a launch
(``svc.prepare``, ``graph.level``, ...).  A phase does two things, each
only when switched on:

* with :func:`annotate` on, it opens a ``jax.profiler.TraceAnnotation``
  of its name, so it lands on the host plane of a profiler trace, on the
  same clock as the device's program executions;
* inside :func:`children_of` (the service wraps each submit and each
  launch in it when it has a tracer), it closes into that tracer's ring
  as a child of the current span, so phases share the request trees' ids.

With both off, :func:`phase` returns one shared null context: no clock
read, no allocation.

:func:`scope` marks a phase inside one jitted program (``ft.pass``), where
a host phase cannot: the program's Python body runs once, while it is
traced.  Its operations carry the name on the device.  The switches are process-wide, like
:func:`repro.obs.profile.install`: the serving loop is single-threaded.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
from collections import deque

from repro.obs import timer

__all__ = ["Span", "Tracer", "annotate", "annotating", "children_of",
           "phase", "scope"]


@dataclasses.dataclass(slots=True)
class Span:
    """One timed stage.  ``end_us is None`` means still open."""

    span_id: int
    name: str
    trace_id: int
    parent_id: int | None = None
    start_us: float = 0.0
    end_us: float | None = None
    status: str = "ok"              # ok | error | rejected
    attrs: dict = dataclasses.field(default_factory=dict)
    links: tuple[int, ...] = ()     # fan-in: span ids this span aggregates

    @property
    def open(self) -> bool:
        return self.end_us is None

    @property
    def duration_us(self) -> float:
        return 0.0 if self.end_us is None else self.end_us - self.start_us

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "trace_id": self.trace_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_us": round(self.start_us, 1),
            "end_us": None if self.end_us is None else round(self.end_us, 1),
            "duration_us": round(self.duration_us, 1),
            "status": self.status,
            "attrs": self.attrs,
            "links": list(self.links),
        }


class Tracer:
    """Span factory + bounded buffer of closed spans.

    ``start``/``end`` are the hot-path API (a dict insert and a clock read
    each); the context-manager :meth:`span` is for code with one obvious
    scope.  ``end`` is idempotent — closing a span twice keeps the first
    verdict, so retire paths can close defensively without double-count.
    """

    def __init__(self, capacity: int = 8192):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ids = itertools.count(1)
        self._open: dict[int, Span] = {}
        self._closed: deque[Span] = deque(maxlen=capacity)
        self.dropped = 0            # closed spans evicted by the ring bound

    # -- lifecycle ---------------------------------------------------------
    def start(self, name: str, parent: Span | None = None,
              links=(), **attrs) -> Span:
        sid = next(self._ids)
        span = Span(
            span_id=sid,
            name=name,
            trace_id=parent.trace_id if parent is not None else sid,
            parent_id=parent.span_id if parent is not None else None,
            start_us=timer.now_us(),
            attrs=attrs,
            links=tuple(l.span_id if isinstance(l, Span) else int(l)
                        for l in links) if links else (),
        )
        self._open[sid] = span
        return span

    def end(self, span: Span | None, status: str = "ok", **attrs) -> None:
        if span is None or span.end_us is not None:
            return
        span.end_us = timer.now_us()
        span.status = status
        if attrs:
            span.attrs.update(attrs)
        self._open.pop(span.span_id, None)
        if len(self._closed) == self.capacity:
            self.dropped += 1
        self._closed.append(span)

    @contextlib.contextmanager
    def span(self, name: str, parent: Span | None = None, **attrs):
        s = self.start(name, parent=parent, **attrs)
        try:
            yield s
        except BaseException:
            self.end(s, status="error")
            raise
        self.end(s)

    # -- queries -----------------------------------------------------------
    @property
    def open_count(self) -> int:
        return len(self._open)

    def open_spans(self) -> list[Span]:
        return list(self._open.values())

    def spans(self) -> list[Span]:
        """Closed spans currently in the ring, oldest first."""
        return list(self._closed)

    def closed_roots(self, name: str | None = None) -> list[Span]:
        """Closed parentless spans, optionally filtered by name.  The trace
        completeness invariant counts ``closed_roots("request")`` — launch
        spans are also roots (they fan in N request trees, so no single
        parent is right) and must not inflate the request count."""
        return [s for s in self._closed
                if s.parent_id is None and (name is None or s.name == name)]

    def children(self, span: Span) -> list[Span]:
        return [s for s in self._closed if s.parent_id == span.span_id]

    def reset(self) -> None:
        self._open.clear()
        self._closed.clear()
        self.dropped = 0

    # -- exporters ---------------------------------------------------------
    def export_jsonl(self, path_or_file, include_open: bool = True) -> int:
        """One span per line (closed spans, then still-open ones flagged
        ``"open": true`` so the dashboard can count orphans).  Returns the
        number of spans written."""

        def _write(fh) -> int:
            n = 0
            for span in self._closed:
                fh.write(json.dumps(span.to_dict()) + "\n")
                n += 1
            if include_open:
                for span in self._open.values():
                    doc = span.to_dict()
                    doc["open"] = True
                    fh.write(json.dumps(doc) + "\n")
                    n += 1
            return n

        if hasattr(path_or_file, "write"):
            return _write(path_or_file)
        with open(path_or_file, "w", encoding="utf-8") as fh:
            return _write(fh)


# -- phases ----------------------------------------------------------------
#: ``jax.profiler.TraceAnnotation`` while :func:`annotate` is on, else None
_ANNOTATION = None
#: (tracer, span) that phases close into as children, set by
#: :func:`children_of`, else None
_PARENT: tuple[Tracer, Span] | None = None
#: either of the two is set: the one global :func:`phase` reads
_ON = False
_NULL = contextlib.nullcontext()


def _refresh() -> None:
    global _ON
    _ON = _ANNOTATION is not None or _PARENT is not None


def annotate(on: bool) -> bool:
    """Turn phase annotation on the profiler's clock on or off; returns
    the previous setting.  JAX is imported only when it is turned on."""
    global _ANNOTATION
    prev = _ANNOTATION is not None
    if on:
        import jax.profiler

        _ANNOTATION = jax.profiler.TraceAnnotation
    else:
        _ANNOTATION = None
    _refresh()
    return prev


def annotating() -> bool:
    return _ANNOTATION is not None


class _Phase:
    """One open phase: its profiler annotation and its ring span, each
    where switched on."""

    __slots__ = ("name", "attrs", "annotation", "span", "outer")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.annotation = self.span = self.outer = None

    def __enter__(self) -> Span | None:
        global _PARENT
        if _ANNOTATION is not None:
            self.annotation = _ANNOTATION(self.name)
            self.annotation.__enter__()
        if _PARENT is not None:
            tracer, parent = self.outer = _PARENT
            self.span = tracer.start(self.name, parent=parent, **self.attrs)
            _PARENT = (tracer, self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        global _PARENT
        if self.outer is not None:
            _PARENT = self.outer
            self.outer[0].end(self.span,
                              status="ok" if exc_type is None else "error")
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        return False


def phase(name: str, **attrs):
    """Context manager over one phase of the program called ``name``.
    Entering it gives the phase's ring span, or None where it has none;
    a status set on that span with :meth:`Tracer.end` inside the phase is
    kept (``end`` keeps its first verdict)."""
    if not _ON:
        return _NULL
    return _Phase(name, attrs)


@contextlib.contextmanager
def _parented(tracer: Tracer, span: Span):
    global _PARENT
    outer, _PARENT = _PARENT, (tracer, span)
    _refresh()
    try:
        yield span
    finally:
        _PARENT = outer
        _refresh()


def children_of(tracer: Tracer | None, span: Span | None):
    """Context in which phases close into ``tracer``'s ring as children of
    ``span``; the shared null context where either is None."""
    if tracer is None or span is None:
        return _NULL
    return _parented(tracer, span)


def scope(name: str, **attrs):
    """A phase of a jitted program: a ``jax.named_scope`` over the
    operations traced inside it, so each carries the phase, with its
    attributes (``ft.pass/axis=2``), in its metadata and in the profiler's
    device events."""
    import jax

    return jax.named_scope("/".join(
        [name] + [f"{k}={v}" for k, v in attrs.items()]))
