"""Span-based request tracing for the async serving plane.

A sampled request carries a ``trace_id`` from ``submit`` to its future's
resolution; the stations along the way — the flush that batched it (with
its flush reason), the per-shard RPC, ensemble assembly, the in-worker
evaluation on the far side of the ``mp_shards`` pipe — each record one
span tied to that trace.  Spans are plain dicts:

    {"name": str, "trace": str, "span": str, "parent": str | None,
     "ts": float (epoch seconds), "dur_ms": float, "attrs": {...}}

The wire form of a trace context is ``(trace_id, parent_span_id)`` — a
picklable 2-tuple the process-shard protocol appends to its eval
messages; the worker answers with a finished span dict that the parent
records verbatim (worker spans carry their pid in ``attrs``).

Sampling is the cost knob: ``sample=0.0`` (the default) makes
``sample_request`` a constant ``None`` and ``span(...)`` return a shared
no-op context manager — tracing off is a handful of predictable branch
checks on the hot path, nothing else.  The sampler uses its own
``random.Random(seed)``: it never touches numpy global state or any
env/agent rng, which is what keeps traced and untraced runs
bit-identical.

A :class:`Span` is also the one phase timer of the served path: given an
annotation type (``jax.profiler.TraceAnnotation``, see
:func:`profiler_annotation`) it enters the phase on the profiler's trace,
on the same clock as the device's events, and given a histogram it
observes the phase's wall time (and, with ``cpu_hist``, the thread's CPU
time) — for every request, sampled or not.  :class:`GcPauses` puts every
garbage collection on the same two sinks.
"""
from __future__ import annotations

import gc
import random
import sys
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

WireContext = Tuple[str, str]       # (trace_id, parent_span_id)


class _NullSpan:
    """Shared no-op span: context manager with inert ids."""

    __slots__ = ()
    trace_id: Optional[str] = None
    span_id: Optional[str] = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


def no_phase(*args, **kwargs) -> _NullSpan:
    """The phase factory of a service without observability: every phase
    is the shared no-op span (no clock, no annotation, no histogram)."""
    return NULL_SPAN


def profiler_annotation():
    """``jax.profiler.TraceAnnotation`` when jax is already imported, else
    ``None``: observability never imports jax itself, so host-only
    processes (shard workers, socket hosts) stay jax-free."""
    if "jax" not in sys.modules:
        return None
    import jax.profiler
    return jax.profiler.TraceAnnotation


class Span:
    """One timed interval, on up to three sinks: a JSONL span when it
    belongs to a sampled trace (``tracer`` and ``trace_id`` given), a
    profiler annotation when given the annotation type, and the wall time
    in ``hist`` (the thread's CPU time in ``cpu_hist``) when given them.
    Durations come from ``time.perf_counter``; ``ts`` is read once, at
    entry, and only for a recorded span."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "attrs", "_t0", "_ts", "_cpu0", "_ann", "_hist",
                 "_cpu_hist")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 trace_id: Optional[str], parent_id: Optional[str],
                 attrs: Dict, annotate=None, hist=None, cpu_hist=None):
        self._tracer = tracer if trace_id is not None else None
        self.name = name
        self.trace_id = trace_id if self._tracer is not None else None
        self.span_id = (self._tracer._next_span_id()
                        if self._tracer is not None else None)
        self.parent_id = parent_id
        self.attrs = attrs
        self._ann = annotate(name) if annotate is not None else None
        self._hist = hist
        self._cpu_hist = cpu_hist

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._tracer is not None:
            self._ts = time.time()
        self._t0 = time.perf_counter()
        if self._cpu_hist is not None:      # CPU interval inside the wall
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, exc_type, *exc) -> None:
        if self._cpu_hist is not None:
            self._cpu_hist.observe((time.thread_time() - self._cpu0) * 1e3)
        dur_ms = (time.perf_counter() - self._t0) * 1e3
        if self._hist is not None:
            self._hist.observe(dur_ms)
        if self._tracer is not None:
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            self._tracer.record({
                "name": self.name, "trace": self.trace_id,
                "span": self.span_id, "parent": self.parent_id,
                "ts": self._ts, "dur_ms": dur_ms, "attrs": self.attrs})
        if self._ann is not None:
            self._ann.__exit__(exc_type, *exc)
        return None


class GcPauses:
    """``gc.callbacks`` hook: each collection is a ``gc.gen<k>`` profiler
    annotation (entered on ``"start"``, left on ``"stop"``, on whichever
    thread collects) and one ``runtime.gc_ms.gen<k>`` observation, ms.

    The hook holds its registry by weak reference (only the histograms,
    made up front, strongly) and leaves ``gc.callbacks`` once the registry
    is gone, or on :meth:`close`.  Collections never overlap (the
    interpreter runs one at a time), so one pending start is all the state
    there is.  A collection can start while its own thread holds a
    histogram's lock, so the callback takes no lock but those histograms'
    own, which are re-entrant (``MetricsRegistry.histogram``)."""

    def __init__(self, metrics, annotate=None):
        self._hists = tuple(metrics.histogram(f"runtime.gc_ms.gen{g}")
                            for g in range(len(gc.get_threshold())))
        self._annotate = annotate
        self._ann = None
        self._t0 = 0.0
        gc.callbacks.append(self)
        self._detach = weakref.finalize(metrics, _drop_gc_hook, self)
        self._detach.atexit = False

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if self._annotate is not None:
                self._ann = self._annotate(f"gc.gen{gen}")
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        ms = (time.perf_counter() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._hists[gen].observe(ms)

    def close(self) -> None:
        self._detach()


def _drop_gc_hook(hook: GcPauses) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:
        pass


class Tracer:
    """Sampling span recorder.

    Parameters
    ----------
    sample:    fraction of requests that get a trace (0 disables).
    writer:    optional callback invoked with each finished span dict
               (the ``Obs`` umbrella wires a JSONL appender here).
    max_spans: in-memory ring capacity for :meth:`spans`.
    seed:      sampler seed — deterministic, isolated from user rngs.
    """

    def __init__(self, sample: float = 0.0,
                 writer: Optional[Callable[[dict], None]] = None,
                 max_spans: int = 20_000, seed: int = 0):
        self.sample = float(sample)
        self.enabled = self.sample > 0.0
        self._writer = writer
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=int(max_spans))
        self._n_traces = 0
        self._n_spans = 0

    # -- trace/span identity ---------------------------------------------
    def sample_request(self) -> Optional[str]:
        """A fresh trace id for a sampled request, else ``None``."""
        if not self.enabled:
            return None
        with self._lock:
            if self.sample < 1.0 and self._rng.random() >= self.sample:
                return None
            self._n_traces += 1
            return f"t{self._n_traces:08x}"

    def _next_span_id(self) -> str:
        with self._lock:
            self._n_spans += 1
            return f"s{self._n_spans:08x}"

    # -- span creation / recording ---------------------------------------
    def span(self, name: str, trace_id: Optional[str],
             parent: Optional[str] = None, **attrs):
        """Context manager recording one span on exit; a ``None``
        ``trace_id`` (unsampled request, tracing off) returns the shared
        no-op span."""
        if trace_id is None or not self.enabled:
            return NULL_SPAN
        return Span(self, name, trace_id, parent, attrs)

    def wire_context(self, span) -> Optional[WireContext]:
        """The picklable context an RPC message carries: the worker's
        span will hang off ``span`` in the assembled trace."""
        if span is None or span.trace_id is None:
            return None
        return (span.trace_id, span.span_id)

    def record(self, rec: dict) -> None:
        """Store a finished span (local exit or worker-shipped)."""
        with self._lock:
            self._spans.append(rec)
        if self._writer is not None:
            self._writer(rec)

    # -- reporting --------------------------------------------------------
    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._spans)
