"""Tracing riding the charge-attribution clock, with host-time stamps.

A :class:`Tracer` produces **spans** — named, categorized windows of a
task's timeline.  Each span carries two times.  Its *model* time is
charged by the clock (below).  Its *host* time is two stamps of
:func:`repro.core.clock.host_now`, taken as the span opens and closes
and kept in one process-wide bounded ring, the **host record**
(:func:`host_spans`), which outlives the tracer that made the spans.
The span context lives in the same thread-local slot as the charge owner
(:mod:`repro.core.clock`), travels across worker/sender/pool threads
through ``bind_charge_owner``, and is charged by ``Clock.sleep`` itself:
every model-second a thread sleeps lands on the innermost span open on
that thread (``Span.self_seconds``) and on the tracer's per-task
category tally.  That tally is what makes ``TaskStats.time_budget()``
exact — it is fed by the very same ``sleep`` calls that feed
``Clock.charged``, so the decomposition and the total can never drift.

Two export formats:

* :meth:`Tracer.export_jsonl` — the canonical, deterministic form.  One
  span per line, sorted by a semantic key, carrying only seed-stable
  fields (ids, names, categories, attrs, per-span self seconds) — byte-
  identical across same-seed runs of a deterministic scenario.  Global
  virtual timestamps are deliberately excluded: concurrent tasks all
  advance the shared virtual clock, so start offsets depend on thread
  interleaving even when every per-task quantity is exact.
* :meth:`Tracer.export_chrome` — Chrome trace-event JSON (``ph: "X"``
  complete events over host microseconds, the virtual start in
  ``args``), loadable in Perfetto / ``chrome://tracing`` for a visual
  timeline.  Interleaving-dependent by construction; no byte-stability
  claim.

While a JAX profiler trace is running, each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so the program's spans
appear on the profiler's host plane beside the device's operations.

Span discipline: ``Tracer.span(...)`` may only be used as a ``with``
context manager (lint rule R006) — a leaked open span would swallow
every later charge on its thread and corrupt the time-budget sum.
"""

from __future__ import annotations

import functools
import json
import sys
import threading
from collections import deque
from typing import NamedTuple

from ..core.clock import (_swap_trace_context, current_trace_context,
                          host_now, trace_context)

#: span categories with a fixed place in ``TaskStats.time_budget()``;
#: spans may use other categories, but these are the vocabulary the
#: data/control planes charge under (and the budget reports in order)
CATEGORIES = ("startup", "overhead", "wire", "integrity", "backoff",
              "replica", "session", "queue", "other")


# --------------------------------------------------------------------------
# the host record
# --------------------------------------------------------------------------
class HostSpan(NamedTuple):
    """One closed span on the host clock (``host_now`` seconds)."""

    name: str
    category: str
    t0: float
    t1: float
    thread: int


class HostSpans(NamedTuple):
    """What :func:`host_spans` returns: the spans the ring holds, in
    close order; how many older ones it dropped; and the latest host
    time any dropped span ended (``-inf`` when none was dropped)."""

    spans: list
    dropped: int
    dropped_until: float = float("-inf")

    def whole_since(self, t: float) -> bool:
        """True when every span that ended after host time ``t`` is
        still held: a window opening at ``t`` lost nothing."""
        return self.dropped_until <= t


class _HostRing:
    """Bounded ring of ``(name, category, host_t0, host_t1, thread)``
    with an exact ``dropped`` count and the latest end of a dropped
    span.  One per process: every enabled :class:`Tracer` appends to
    it, so readers that run after a tracer's owner is gone still see
    its spans."""

    #: a 51 s window of the benchmark's transfer cell closes about 92 k
    #: spans (about 2,700 per task of 80 files of 12.5 MB, 33 per file,
    #: most of them hasher calls), a checkpoint cycle under a thousand;
    #: this holds the window and the checks after it more than twice
    #: over, in about 40 MB when full
    SIZE = 1 << 18

    def __init__(self, size: int = SIZE):
        self._spans: deque = deque(maxlen=size)
        self._lock = threading.Lock()
        self.recorded = 0
        self.dropped_until = float("-inf")

    def append(self, item: tuple) -> None:
        spans = self._spans
        with self._lock:
            if len(spans) == spans.maxlen:
                self.dropped_until = max(self.dropped_until, spans[0][3])
            spans.append(item)
            self.recorded += 1

    def snapshot(self, name: str | None = None) -> HostSpans:
        with self._lock:
            items = list(self._spans)
            dropped = self.recorded - len(items)
            until = self.dropped_until
        return HostSpans([HostSpan._make(i) for i in items
                          if name is None or i[0] == name], dropped, until)


_HOST = _HostRing()


def host_spans(name: str | None = None) -> HostSpans:
    """The process's host record: every span an enabled tracer closed
    (and every ``Tracer.record`` given host stamps), as
    :class:`HostSpan` in close order, only those named ``name`` when it
    is given; plus how many spans the bounded ring has dropped since the
    process started, and when the latest of them ended.  A reader of a
    window opening at host time ``t`` checks ``whole_since(t)``."""
    return _HOST.snapshot(name)


#: ``jax.profiler.TraceAnnotation``, looked up once JAX is imported: no
#: profiler trace can run in a process that has not imported it, so the
#: tracer never imports JAX itself
_TRACE_ANNOTATION = None


def _annotation(name: str):
    """An entered ``TraceAnnotation`` of ``name`` while a JAX profiler
    trace is running, else None."""
    global _TRACE_ANNOTATION
    cls = _TRACE_ANNOTATION
    if cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        cls = _TRACE_ANNOTATION = prof.TraceAnnotation
    if not cls.is_enabled():
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


class Span:
    """One traced window.  ``self_seconds`` is the model time charged by
    the owning thread while this span was its innermost — the
    deterministic quantity; ``t0``/``t1`` are global virtual timestamps;
    ``host_t0``/``host_t1`` are ``host_now`` seconds (None for a
    retroactive window recorded without them).

    A span is its own ``with`` guard AND its own thread-local trace
    context: entering swaps it into the clock's attribution slot,
    ``Clock.sleep`` calls :meth:`charge` on it directly, and exiting
    restores the parent context and records the span.  One object per
    span — this path runs per traced storage op, so the earlier
    three-object form (guard + span + child context) was measurable
    fleet CPU."""

    __slots__ = ("tracer", "trace_id", "task_id", "name", "category",
                 "attrs", "t0", "t1", "host_t0", "host_t1",
                 "self_seconds", "thread", "_prev", "_entered", "_ann")

    #: duck-type marker for ``Clock.sleep``-compatible contexts: both
    #: Span and the root _SpanCtx expose ``span``/``charge``
    def __init__(self, tracer, trace_id, task_id, name, category,
                 attrs, t0, thread):
        self.tracer = tracer
        self.trace_id = trace_id
        self.task_id = task_id
        self.name = name
        self.category = category
        self.attrs = attrs
        self.t0 = t0
        self.t1 = None
        self.host_t0 = None
        self.host_t1 = None
        self.self_seconds = 0.0
        self.thread = thread
        self._prev = None
        self._entered = False
        self._ann = None

    @property
    def span(self):
        """As a trace context, a Span is its own innermost span."""
        return self

    def charge(self, model_seconds: float) -> None:
        # hot path: this runs on EVERY Clock.sleep under a span.  The
        # span is owned by the thread that opened it, so the owner
        # accumulates lock-free; only a charge from a thread the
        # context was rebound onto (bind_charge_owner inside an open
        # span) pays the lock.  The per-task tally is folded once, at
        # span close.
        if self.thread == threading.get_ident():
            self.self_seconds += model_seconds
        else:
            with self.tracer._lock:
                self.self_seconds += model_seconds

    def __enter__(self):
        self.thread = threading.get_ident()
        self.t0 = self.tracer._now()
        self._prev = _swap_trace_context(self)
        self._entered = True
        self.host_t0 = host_now()
        self._ann = _annotation(self.name)
        return self

    def __exit__(self, *exc):
        if self._entered:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            self.host_t1 = host_now()
            self._entered = False
            _swap_trace_context(self._prev)
            self.t1 = self.tracer._now()
            self.tracer._record_span(self)
        return False

    def key(self):
        """Deterministic sort key for the canonical export."""
        return (self.trace_id, self.task_id, self.category, self.name,
                json.dumps(self.attrs, sort_keys=True),
                self.self_seconds)


class _SpanCtx:
    """Root trace context for a task binding: which trace/task spans
    opened on this thread attach to, before any span is open.
    Installed via ``repro.core.clock.trace_context`` and captured
    across threads by ``bind_charge_owner``.  ``charge`` is the
    duck-typed hook ``Clock.sleep`` calls — at the root there is no
    open span, so the charge lands in the budget's ``other``
    remainder."""

    __slots__ = ("tracer", "trace_id", "task_id")

    #: a root context has no innermost span
    span = None

    def __init__(self, tracer, trace_id, task_id):
        self.tracer = tracer
        self.trace_id = trace_id
        self.task_id = task_id

    def charge(self, model_seconds: float) -> None:
        return


class _NullCM:
    """Shared no-op context manager: what a disabled tracer's ``bind``
    and ``span`` return, so instrumented code pays one attribute lookup
    and an empty ``with`` when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullCM()


class Tracer:
    """Fleet-wide span collector.  Thread-safe; bounded (``max_spans``
    ring with an exact ``spans_dropped`` counter, mirroring the
    StatusBus subscriber discipline).  ``clock`` is any object with a
    ``virtual_elapsed`` attribute, read for the virtual timestamps; host
    stamps come from ``host_now`` and also go to the process's host
    record (:func:`host_spans`)."""

    MAX_SPANS = 65536

    def __init__(self, clock=None, enabled: bool = True,
                 max_spans: int = MAX_SPANS):
        self.enabled = enabled
        self.clock = clock
        self.max_spans = max_spans
        self._spans: deque = deque()
        #: task_id -> {category -> model seconds charged under a span}
        self._tally: dict[str, dict[str, float]] = {}
        self.spans_recorded = 0
        self.spans_dropped = 0
        self.binds = 0
        self._lock = threading.Lock()

    # ---- binding ---------------------------------------------------------
    def bind(self, trace_id: str, task_id: str):
        """Root binding for a task run: every span opened (on this
        thread or any ``bind_charge_owner``-crossed thread) while the
        block is active attaches to ``trace_id``/``task_id``."""
        if not self.enabled:
            return _NULL_CM
        self.binds += 1
        return trace_context(_SpanCtx(self, trace_id, task_id))

    def span(self, name: str, category: str | None = "other", **attrs):
        """Open a span; ``with`` context manager ONLY (lint R006).
        Outside any tracer binding (no task context on this thread)
        there is nothing to attach to, so the no-op guard comes back.
        ``category=None`` takes the enclosing span's category (``other``
        at the binding's root): an instrumentation span opened inside a
        charging one then leaves the time budget as it was."""
        return self._open(current_trace_context(), name, category, attrs)

    def _open(self, parent, name: str, category: str | None,
              attrs: dict):
        if not self.enabled:
            return _NULL_CM
        if parent is None or not isinstance(parent, (Span, _SpanCtx)):
            return _NULL_CM
        if category is None:
            category = getattr(parent, "category", "other")
        return Span(self, parent.trace_id, parent.task_id, name,
                    category, attrs, 0.0, 0)

    def record(self, name: str, category: str, t0: float, t1: float,
               trace_id: str = "", task_id: str = "",
               host_t0: float | None = None, host_t1: float | None = None,
               **attrs) -> None:
        """Record a retroactive window (queue wait, breaker state
        window, federation handoff) that was observed, not slept
        through: it appears in exports but charges nothing to the
        time-budget tallies.  ``t0``/``t1`` are virtual; with
        ``host_t0``/``host_t1`` (``host_now`` seconds) the window also
        joins the host record, and without them it stays out of it."""
        if not self.enabled:
            return
        span = Span(self, trace_id, task_id, name, category, attrs,
                    t0, threading.get_ident())
        span.t1 = t1
        span.host_t0, span.host_t1 = host_t0, host_t1
        self._record_span(span)

    # ---- charge plumbing -------------------------------------------------
    def _now(self) -> float:
        return self.clock.virtual_elapsed if self.clock is not None \
            else 0.0

    def _record_span(self, span: Span) -> None:
        with self._lock:
            if span.task_id and span.self_seconds:
                per = self._tally.setdefault(span.task_id, {})
                per[span.category] = per.get(span.category, 0.0) \
                    + span.self_seconds
            if len(self._spans) >= self.max_spans:
                self._spans.popleft()
                self.spans_dropped += 1
            self._spans.append(span)
            self.spans_recorded += 1
        if span.host_t0 is not None and span.host_t1 is not None:
            _HOST.append((span.name, span.category, span.host_t0,
                          span.host_t1, span.thread))

    # ---- tallies ---------------------------------------------------------
    def category_seconds(self, task_id: str) -> dict[str, float]:
        """Snapshot of the per-category model seconds charged under
        spans for ``task_id`` (cumulative across runs/resumes — callers
        wanting a per-run delta snapshot before and after)."""
        with self._lock:
            return dict(self._tally.get(task_id, {}))

    def forget(self, task_id: str) -> None:
        """Drop a finished/exported task's tally so the table stays
        bounded over a long-lived fleet (sibling of ``Clock.forget``)."""
        with self._lock:
            self._tally.pop(task_id, None)

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    # ---- exports ---------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """Canonical deterministic export: one span per line, sorted by
        semantic key, seed-stable fields only.  Returns the number of
        lines written."""
        spans = sorted(self.spans(), key=Span.key)
        with open(path, "w") as fh:
            for s in spans:
                fh.write(json.dumps(
                    {"trace_id": s.trace_id, "task_id": s.task_id,
                     "name": s.name, "category": s.category,
                     "attrs": s.attrs,
                     "self_seconds": round(s.self_seconds, 9)},
                    sort_keys=True) + "\n")
        return len(spans)

    def export_chrome(self, path: str) -> int:
        """Chrome trace-event JSON over *host* microseconds (``host_now``)
        — open it in Perfetto (ui.perfetto.dev) or ``chrome://tracing``.
        Complete (``ph: "X"``) events; pid = task, tid = a stable
        per-thread index in first-seen order; ``args`` keeps the virtual
        start (``virtual_t0``) and the model seconds charged.  A window
        recorded without host stamps has no place on it and is left
        out."""
        spans = [s for s in self.spans()
                 if s.host_t0 is not None and s.host_t1 is not None]
        tids: dict[int, int] = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s.thread, len(tids))
            events.append({
                "name": s.name, "cat": s.category, "ph": "X",
                "ts": round(s.host_t0 * 1e6, 3),
                "dur": round(max(0.0, s.host_t1 - s.host_t0) * 1e6, 3),
                "pid": s.task_id or s.trace_id or "fleet",
                "tid": tid,
                "args": dict(s.attrs, trace_id=s.trace_id,
                             virtual_t0=s.t0,
                             self_seconds=round(s.self_seconds, 9)),
            })
        with open(path, "w") as fh:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, fh)
        return len(events)


#: shared disabled tracer: the default for a bare ``TransferService``
#: so un-instrumented construction paths pay (almost) nothing
NULL_TRACER = Tracer(enabled=False)


def thread_span(name: str, category: str | None = None, **attrs):
    """``Tracer.span`` on whichever tracer is bound on this thread (the
    no-op guard where none is) — for code that holds no tracer of its
    own, such as connectors and hashers.  ``with`` only (lint R006)."""
    ctx = current_trace_context()
    if ctx is None:
        return _NULL_CM
    return ctx.tracer._open(ctx, name, category, attrs)


def spanned(name: str, category: str | None = None):
    """Decorator: every call of the function runs under
    ``thread_span(name, category)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if current_trace_context() is None:
                return fn(*args, **kwargs)
            with thread_span(name, category):
                return fn(*args, **kwargs)
        return call
    return wrap
