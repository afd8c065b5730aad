"""Process-wide metrics registry — counters, gauges, log2 latency histograms.

FluxSieve's headline claim is speedups at *very low computational overhead*
(paper §1, §5); a system built to serve observability data must itself be
observable, and cheaply so.  This module is the single registry every plane
(ingest, match, query, arrangement, maintenance) reports through:

  * **Counter** — monotonic float/int accumulator (``_total`` suffix by
    convention);
  * **Gauge** — settable level (device bytes resident, live arrangements),
    with ``track_max`` for high-water marks;
  * **Histogram** — fixed-bucket base-2 latency histogram: one bucket per
    binary exponent of the observed value, so p50/p99 come from bucket
    interpolation **without retaining samples** and an ``observe`` is one
    lock + two adds, never an allocation.

Hot-path discipline: call sites cache the metric object at import time
(``_D2H = telemetry.counter(...)``) so the hot path pays one short
per-metric lock, not a registry lookup.  ``reset()`` zeroes values *in
place* — cached handles stay valid across benchmark suites and tests.
``set_enabled(False)`` turns every mutation into an early return; the
``telemetry_overhead`` bench lane A/Bs exactly this switch.

Metric naming scheme (see docs/TELEMETRY.md): ``fluxsieve_<plane>_<what>``
with unit suffixes (``_total``, ``_bytes_total``, ``_seconds``); the plane
token is one of ``ingest | match | query | arrangement | maintenance |
store | events``.
"""
from __future__ import annotations

import math
import threading

# Histogram bucket span: 2^-20 s (~1 us) .. 2^10 s (~17 min).  Values
# outside clamp into the edge buckets; min/max are tracked exactly so
# clamping never distorts the reported extremes.
LOG2_MIN = -20
LOG2_MAX = 10
NUM_BUCKETS = LOG2_MAX - LOG2_MIN + 1   # bucket i covers [2^(MIN+i), 2^(MIN+i+1))

_ENABLED = True


def set_enabled(flag: bool) -> None:
    """Globally enable/disable telemetry mutation (spans and events consult
    this too).  Reads (snapshots, exports) always work."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in (labels or {}).items()))


class Counter:
    """Monotonic accumulator.  ``inc`` returns the new value (callers that
    maintain a paired high-water gauge use it)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = dict(labels or {})
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        if not _ENABLED:
            return self._value
        with self._lock:
            self._value += n
            return self._value

    @property
    def value(self):
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0

    def _snapshot(self) -> dict:
        return {"labels": self.labels, "value": self._value}


class Gauge:
    """Settable level.  ``inc``/``dec`` adjust (process-wide aggregation
    across several owners of one resource); ``track_max`` ratchets — the
    peak-gauge idiom (``g_peak.track_max(g.inc(n))``)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = dict(labels or {})
        self._value = 0
        self._lock = threading.Lock()

    def set(self, v) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value = v

    def inc(self, n=1):
        if not _ENABLED:
            return self._value
        with self._lock:
            self._value += n
            return self._value

    def dec(self, n=1):
        return self.inc(-n)

    def track_max(self, v) -> None:
        if not _ENABLED:
            return
        with self._lock:
            if v > self._value:
                self._value = v

    @property
    def value(self):
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0

    def _snapshot(self) -> dict:
        return {"labels": self.labels, "value": self._value}


class Histogram:
    """Fixed-bucket base-2 histogram: percentiles without sample retention.

    ``observe(v)`` buckets ``v`` (seconds) by binary exponent — O(1), no
    allocation, one short lock.  ``quantile(q)`` walks the cumulative
    counts and interpolates *geometrically* inside the target bucket
    (buckets are exponential, so the geometric mean is the unbiased
    midpoint); the result is exact to within one octave and clamped to the
    exact observed [min, max]."""

    __slots__ = ("name", "labels", "_counts", "_count", "_sum",
                 "_min", "_max", "_lock")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = dict(labels or {})
        self._counts = [0] * NUM_BUCKETS
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    @staticmethod
    def bucket_index(v: float) -> int:
        if v <= 0.0:
            return 0
        e = math.frexp(v)[1] - 1        # floor(log2 v)
        return min(max(e - LOG2_MIN, 0), NUM_BUCKETS - 1)

    @staticmethod
    def bucket_bounds(i: int) -> tuple:
        """(lo, hi) of bucket ``i`` in seconds."""
        return (2.0 ** (LOG2_MIN + i), 2.0 ** (LOG2_MIN + i + 1))

    def observe(self, v: float) -> None:
        if not _ENABLED:
            return
        v = float(v)
        i = self.bucket_index(v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> float:
        """Interpolated q-quantile (0..1); NaN when empty."""
        with self._lock:
            if self._count == 0:
                return math.nan
            target = q * self._count
            cum = 0
            for i, c in enumerate(self._counts):
                if c == 0:
                    continue
                if cum + c >= target:
                    lo, _ = self.bucket_bounds(i)
                    frac = (target - cum) / c
                    est = lo * (2.0 ** frac)
                    return min(max(est, self._min), self._max)
                cum += c
            return self._max

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * NUM_BUCKETS
            self._count = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = -math.inf

    def _snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            count, total = self._count, self._sum
            mn = self._min if count else None
            mx = self._max if count else None
        out = {"labels": self.labels, "count": count, "sum": total,
               "min": mn, "max": mx}
        if count:
            out["p50"] = self.quantile(0.50)
            out["p90"] = self.quantile(0.90)
            out["p99"] = self.quantile(0.99)
            out["buckets"] = {f"{self.bucket_bounds(i)[1]:.9g}": c
                              for i, c in enumerate(counts) if c}
        return out


class MetricsRegistry:
    """Thread-safe get-or-create registry of labeled metrics.  One
    process-wide default instance (module functions below) is the normal
    interface; private registries exist for tests."""

    _KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}      # (kind, name, label key) -> metric
        self._help = {}         # name -> help string

    def _get(self, kind: str, name: str, labels: dict, help: str):
        key = (kind, name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                for k, n, _ in self._metrics:
                    if n == name and k != kind:
                        raise ValueError(
                            f"metric {name!r} already registered as {k}")
                m = self._KINDS[kind](name, labels)
                self._metrics[key] = m
                if help:
                    self._help.setdefault(name, help)
        return m

    def counter(self, name: str, *, labels: dict = None,
                help: str = "") -> Counter:
        return self._get("counter", name, labels, help)

    def gauge(self, name: str, *, labels: dict = None,
              help: str = "") -> Gauge:
        return self._get("gauge", name, labels, help)

    def histogram(self, name: str, *, labels: dict = None,
                  help: str = "") -> Histogram:
        return self._get("histogram", name, labels, help)

    def reset(self) -> None:
        """Zero every metric IN PLACE — handles cached by call sites stay
        valid (benchmark suites isolate this way)."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m._reset()

    def collect(self) -> list:
        """-> [(kind, name, metric)] sorted by (name, labels)."""
        with self._lock:
            items = sorted(self._metrics.items(),
                           key=lambda kv: (kv[0][1], kv[0][2], kv[0][0]))
        return [(kind, name, m) for (kind, name, _), m in items]

    def help_text(self, name: str) -> str:
        return self._help.get(name, "")

    def snapshot(self) -> dict:
        """JSON-able {"counters": {name: [series...]}, "gauges": ...,
        "histograms": ...} — the per-suite provenance block BENCH_*.json
        embeds and the five-plane assertion in tests reads."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for kind, name, m in self.collect():
            out[kind + "s"].setdefault(name, []).append(m._snapshot())
        return out


# -- the process-wide default registry ---------------------------------------
REGISTRY = MetricsRegistry()


def counter(name: str, *, labels: dict = None, help: str = "") -> Counter:
    return REGISTRY.counter(name, labels=labels, help=help)


def gauge(name: str, *, labels: dict = None, help: str = "") -> Gauge:
    return REGISTRY.gauge(name, labels=labels, help=help)


def histogram(name: str, *, labels: dict = None, help: str = "") -> Histogram:
    return REGISTRY.histogram(name, labels=labels, help=help)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def reset() -> None:
    REGISTRY.reset()
