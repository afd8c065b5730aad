"""Context-manager spans with parent/child linkage, exportable as Chrome
trace-event JSON (chrome://tracing and https://ui.perfetto.dev both load it).

The point is *timeline visibility*: ingest double-buffering overlap (a
``ingest/generate`` span running while the previous batch's device match is
still in flight), stacked query dispatches across shard threads, and
maintenance backfill cycles all land on ONE timeline, one track per thread.

  * ``span(name, **args)`` — context manager; on exit one complete event
    (``ph: "X"``) is appended to a bounded ring buffer (old spans fall off,
    memory never grows);
  * parent/child linkage rides a thread-local stack: each finished span
    records its parent's id in ``args.parent`` (the Chrome viewer already
    nests same-thread spans by ts/dur; the explicit id survives export);
  * ``span.set(**args)`` adds arguments known only inside the region (an
    outcome, a cache hit) to the span's exported ``args``;
  * ``export_chrome_trace()`` -> the trace-event JSON object; timestamps
    are microseconds since tracer start, durations microseconds, as the
    format requires.

A span is two ``perf_counter`` reads, two list ops, and one locked deque
append — cheap enough for per-batch (NOT per-record) hot-path use; the
``telemetry_overhead`` bench lane measures exactly this budget.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from repro.core.telemetry import metrics


class _Span:
    """One in-flight span (the context manager ``Tracer.span`` returns)."""

    __slots__ = ("tracer", "name", "cat", "args", "span_id", "parent_id",
                 "_t0")

    def __init__(self, tracer, name, cat, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.span_id = 0
        self.parent_id = 0
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        t = self.tracer
        self._t0 = t._clock()
        stack = t._stack()
        self.parent_id = stack[-1] if stack else 0
        self.span_id = t._next_id()
        stack.append(self.span_id)
        return self

    def set(self, **args) -> None:
        """Add ``args`` (JSON-able scalars) to the span's arguments."""
        self.args.update(args)

    def __exit__(self, *exc) -> None:
        t = self.tracer
        t1 = t._clock()
        stack = t._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        args = dict(self.args) if self.args else {}
        args["id"] = self.span_id
        if self.parent_id:
            args["parent"] = self.parent_id
        t._record({
            "name": self.name,
            "cat": self.cat,
            "ph": "X",
            "ts": (self._t0 - t._epoch) * 1e6,
            "dur": (t1 - self._t0) * 1e6,
            "pid": t._pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args,
        })


class _NullSpan:
    """Returned while telemetry is disabled: costs one attribute check."""

    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **args) -> None:
        return None

    def __exit__(self, *exc):
        return None


_NULL = _NullSpan()


class Tracer:
    """Bounded ring buffer of finished spans.  ``capacity`` bounds memory;
    the newest spans win (a long benchmark keeps its tail, which is what a
    timeline of "what was the system doing" wants)."""

    def __init__(self, *, capacity: int = 16384, clock=time.perf_counter):
        self._events = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._local = threading.local()
        self._clock = clock
        self._epoch = clock()
        self._pid = os.getpid()
        self._id_lock = threading.Lock()
        self._id = 0
        self.dropped = 0            # spans that pushed older ones off

    # -- internals ---------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _next_id(self) -> int:
        with self._id_lock:
            self._id += 1
            return self._id

    def _record(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    # -- public ------------------------------------------------------------
    def span(self, name: str, *, cat: str = "fluxsieve", **args):
        """Context manager timing one region.  ``args`` must be JSON-able
        scalars (they land verbatim in the exported trace)."""
        if not metrics.enabled():
            return _NULL
        return _Span(self, name, cat, args)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def spans(self) -> list:
        with self._lock:
            return list(self._events)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._epoch = self._clock()

    def export_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (load in Perfetto or
        chrome://tracing).  ``displayTimeUnit`` and per-event ``ph``/``ts``/
        ``dur`` follow the trace-event format spec."""
        with self._lock:
            events = [dict(ev) for ev in self._events]
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"source": "fluxsieve.telemetry",
                          "spans_dropped": self.dropped},
        }


# -- the process-wide default tracer -----------------------------------------
TRACER = Tracer()


def span(name: str, *, cat: str = "fluxsieve", **args):
    return TRACER.span(name, cat=cat, **args)


def export_chrome_trace() -> dict:
    return TRACER.export_chrome_trace()


def reset() -> None:
    TRACER.reset()
