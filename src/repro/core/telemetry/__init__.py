"""FluxSieve's unified telemetry plane.

One process-wide registry of counters/gauges/histograms, one span tracer
with Chrome-trace export, one structured event log, and the exporters that
serialize all three.  Every plane (ingest, match, query, arrangement,
maintenance) reports through this package; see docs/TELEMETRY.md for the
naming scheme and snapshot schema.

Typical call-site idiom — cache handles at import time, mutate on the hot
path, never look up:

    from repro.core.telemetry import metrics, trace

    _DISPATCH = metrics.counter("fluxsieve_match_dispatch_total",
                                help="Fused device dispatches.")
    ...
    with trace.span("match/dispatch", batch=n):
        _DISPATCH.inc()
"""
from repro.core.telemetry import events, export, metrics, trace
from repro.core.telemetry.events import emit
from repro.core.telemetry.export import (merge_dumps, merge_snapshots,
                                         prometheus_text, snapshot,
                                         write_dump)
from repro.core.telemetry.metrics import (counter, enabled, gauge, histogram,
                                          set_enabled)
from repro.core.telemetry.trace import export_chrome_trace, span


def reset() -> None:
    """Zero all metrics in place, clear spans and events.  Cached metric
    handles stay valid (benchmark suites and tests isolate this way)."""
    metrics.reset()
    trace.reset()
    events.reset()


def suppressed(site: str, err: BaseException) -> None:
    """Account an intentionally swallowed error.  Every ``except ...: pass``
    style handler routes through here so suppressed failures stay
    observable: bumps ``fluxsieve_errors_suppressed_total{site}`` and emits
    an ``error_suppressed`` event.  Never raises (safe from ``__del__`` at
    interpreter teardown, when the registry may already be torn down)."""
    try:
        metrics.counter("fluxsieve_errors_suppressed_total",
                        labels={"site": site},
                        help="Errors intentionally swallowed, by site.").inc()
        emit("error_suppressed", plane=site.split(".", 1)[0], site=site,
             error=f"{type(err).__name__}: {err}")
    except Exception:       # noqa: BLE001 — observability must not throw
        pass


__all__ = [
    "counter", "gauge", "histogram", "enabled", "set_enabled",
    "span", "export_chrome_trace", "emit", "suppressed",
    "prometheus_text", "snapshot", "write_dump", "merge_dumps",
    "merge_snapshots", "reset", "metrics", "trace", "events", "export",
]
