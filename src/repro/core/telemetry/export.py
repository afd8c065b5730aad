"""Exporters: Prometheus text exposition format + JSON snapshot + trace dump.

``prometheus_text()`` renders the whole registry in the text format every
Prometheus-compatible scraper understands (`# HELP` / `# TYPE` headers,
``name{label="v"} value`` samples, histograms as cumulative ``_bucket{le=}``
series plus ``_sum``/``_count``).  ``snapshot()`` is the JSON-able dict the
benchmarks embed per suite; ``write_dump(dir, prefix=...)`` writes all
three artifacts (``metrics.prom``, ``snapshot.json``, ``trace.json``) for
offline inspection — the trace loads directly in https://ui.perfetto.dev.

Multi-process telemetry: registries are per-process, so the process worker
model dumps with per-worker prefixes (``maint-0.metrics.prom`` ...) and
``merge_dumps(dir)`` folds every per-process snapshot/trace in a directory
into ONE ``merged.*`` artifact set: counters and histogram buckets sum,
gauges sum (per-process levels of one fleet add), min/max merge exactly,
quantiles re-interpolate from the merged buckets, and traces concatenate —
distinct pids give each process its own Perfetto track.
"""
from __future__ import annotations

import json
import math
import time
from pathlib import Path

from repro.core.telemetry import events as _events
from repro.core.telemetry import metrics
from repro.core.telemetry import trace as _trace


def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _labels_text(labels: dict, extra: dict = None) -> str:
    pairs = dict(labels or {})
    if extra:
        pairs.update(extra)
    if not pairs:
        return ""
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in sorted(pairs.items()))
    return "{" + body + "}"


def prometheus_text(registry: "metrics.MetricsRegistry" = None) -> str:
    """Render the registry in Prometheus text exposition format."""
    reg = registry if registry is not None else metrics.REGISTRY
    # group series under one HELP/TYPE header per metric name
    by_name = {}
    for kind, name, m in reg.collect():
        by_name.setdefault(name, (kind, []))[1].append(m)
    lines = []
    for name in sorted(by_name):
        kind, series = by_name[name]
        help_text = reg.help_text(name)
        if help_text:
            lines.append(f"# HELP {name} {_escape(help_text)}")
        lines.append(f"# TYPE {name} {kind}")
        for m in series:
            if kind in ("counter", "gauge"):
                lines.append(f"{name}{_labels_text(m.labels)} {m.value}")
            else:
                cum = 0
                with m._lock:
                    counts = list(m._counts)
                    count, total = m._count, m._sum
                for i, c in enumerate(counts):
                    if not c:
                        continue
                    cum += c
                    le = f"{m.bucket_bounds(i)[1]:.9g}"
                    lines.append(f"{name}_bucket"
                                 f"{_labels_text(m.labels, {'le': le})} "
                                 f"{cum}")
                lines.append(f"{name}_bucket"
                             f"{_labels_text(m.labels, {'le': '+Inf'})} "
                             f"{count}")
                lines.append(f"{name}_sum{_labels_text(m.labels)} {total}")
                lines.append(f"{name}_count{_labels_text(m.labels)} {count}")
    return "\n".join(lines) + "\n"


def snapshot() -> dict:
    """Full JSON-able telemetry snapshot: metrics + recent events."""
    out = metrics.snapshot()
    out["events"] = _events.events()
    out["generated_at"] = time.time()
    return out


def write_dump(directory, *, prefix: str = "") -> dict:
    """Write metrics.prom, snapshot.json, and trace.json into ``directory``.
    ``prefix`` namespaces one process's artifacts (``maint-0.metrics.prom``)
    so N processes can dump into one directory for ``merge_dumps``.
    Returns {artifact name: path} for logging."""
    d = Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    paths = {}
    prom = d / f"{prefix}metrics.prom"
    prom.write_text(prometheus_text())
    paths["metrics"] = str(prom)
    snap = d / f"{prefix}snapshot.json"
    snap.write_text(json.dumps(snapshot(), indent=2, default=str))
    paths["snapshot"] = str(snap)
    tr = d / f"{prefix}trace.json"
    tr.write_text(json.dumps(_trace.export_chrome_trace()))
    paths["trace"] = str(tr)
    return paths


# -- multi-process merge ------------------------------------------------------

def _merge_series(kind: str, into: list, series: list) -> None:
    """Merge one snapshot's series list into the accumulator, matching on
    label sets."""
    def key(s):
        return tuple(sorted((str(k), str(v))
                            for k, v in (s.get("labels") or {}).items()))

    index = {key(s): s for s in into}
    for s in series:
        acc = index.get(key(s))
        if acc is None:
            into.append(json.loads(json.dumps(s)))   # deep copy
            index[key(s)] = into[-1]
            continue
        if kind in ("counters", "gauges"):
            acc["value"] = acc.get("value", 0) + s.get("value", 0)
            continue
        acc["count"] = acc.get("count", 0) + s.get("count", 0)
        acc["sum"] = acc.get("sum", 0.0) + s.get("sum", 0.0)
        for bound in ("min", "max"):
            vals = [v for v in (acc.get(bound), s.get(bound))
                    if v is not None]
            acc[bound] = ((min(vals) if bound == "min" else max(vals))
                          if vals else None)
        buckets = dict(acc.get("buckets") or {})
        for le, c in (s.get("buckets") or {}).items():
            buckets[le] = buckets.get(le, 0) + c
        if buckets:
            acc["buckets"] = buckets


def _requantile(acc: dict) -> None:
    """Recompute p50/p90/p99 of a merged histogram series by geometric
    interpolation over the merged buckets (the same estimator the live
    Histogram uses), clamped to the merged exact [min, max]."""
    count = acc.get("count", 0)
    buckets = acc.get("buckets") or {}
    if not count or not buckets:
        for q in ("p50", "p90", "p99"):
            acc.pop(q, None)
        return
    ordered = sorted(((float(le), c) for le, c in buckets.items()))
    for q, label in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
        target = q * count
        cum = 0
        est = ordered[-1][0]
        for hi, c in ordered:
            if cum + c >= target:
                lo = hi / 2.0
                frac = (target - cum) / c
                est = lo * (2.0 ** frac)
                break
            cum += c
        mn = acc.get("min")
        mx = acc.get("max")
        if mn is not None:
            est = max(est, mn)
        if mx is not None:
            est = min(est, mx)
        acc[label] = est


def merge_snapshots(snaps: list) -> dict:
    """Fold per-process snapshots into one: counters/histogram buckets sum,
    gauges sum (each process's level of one shared fleet), min/max merge
    exactly, quantiles re-interpolate, events concatenate."""
    merged = {"counters": {}, "gauges": {}, "histograms": {},
              "events": [], "generated_at": 0.0}
    for snap in snaps:
        for kind in ("counters", "gauges", "histograms"):
            for name, series in (snap.get(kind) or {}).items():
                _merge_series(kind, merged[kind].setdefault(name, []),
                              series)
        merged["events"].extend(snap.get("events") or [])
        merged["generated_at"] = max(merged["generated_at"],
                                     float(snap.get("generated_at") or 0.0))
    for series in merged["histograms"].values():
        for acc in series:
            _requantile(acc)
    return merged


def prometheus_from_snapshot(snap: dict) -> str:
    """Render a (possibly merged) snapshot dict in Prometheus text format —
    same grammar ``scripts/check_prom_format.py`` validates for the live
    registry rendering."""
    lines = []
    kinds = (("counters", "counter"), ("gauges", "gauge"),
             ("histograms", "histogram"))
    names = sorted({name for key, _ in kinds
                    for name in (snap.get(key) or {})})
    by_name = {}
    for key, kind in kinds:
        for name, series in (snap.get(key) or {}).items():
            by_name[name] = (kind, series)
    for name in names:
        kind, series = by_name[name]
        lines.append(f"# TYPE {name} {kind}")
        for s in series:
            labels = s.get("labels") or {}
            if kind in ("counter", "gauge"):
                lines.append(f"{name}{_labels_text(labels)} "
                             f"{s.get('value', 0)}")
                continue
            count = s.get("count", 0)
            buckets = sorted(((float(le), le, c) for le, c
                              in (s.get("buckets") or {}).items()))
            cum = 0
            for _, le, c in buckets:
                cum += c
                lines.append(f"{name}_bucket"
                             f"{_labels_text(labels, {'le': le})} {cum}")
            lines.append(f"{name}_bucket"
                         f"{_labels_text(labels, {'le': '+Inf'})} {count}")
            lines.append(f"{name}_sum{_labels_text(labels)} "
                         f"{s.get('sum', 0.0)}")
            lines.append(f"{name}_count{_labels_text(labels)} {count}")
    return "\n".join(lines) + "\n"


def merge_dumps(directory, *, out_prefix: str = "merged.") -> dict:
    """Fold every per-process dump in ``directory`` (all ``*snapshot.json``
    / ``*trace.json``, prefixed or not, except previous merge outputs)
    into ``merged.metrics.prom`` / ``merged.snapshot.json`` /
    ``merged.trace.json``.  One snapshot then covers every plane across
    every worker process; the merged trace shows one Perfetto track group
    per pid.  Returns {artifact name: path}."""
    d = Path(directory)
    snaps = []
    for p in sorted(d.glob("*snapshot.json")):
        if p.name.startswith(out_prefix):
            continue
        try:
            snaps.append(json.loads(p.read_text()))
        except ValueError:
            continue
    merged = merge_snapshots(snaps)
    trace_events = []
    dropped = 0
    for p in sorted(d.glob("*trace.json")):
        if p.name.startswith(out_prefix):
            continue
        try:
            tr = json.loads(p.read_text())
        except ValueError:
            continue
        trace_events.extend(tr.get("traceEvents") or [])
        dropped += int((tr.get("otherData") or {}).get("spans_dropped", 0))
    paths = {}
    prom = d / f"{out_prefix}metrics.prom"
    prom.write_text(prometheus_from_snapshot(merged))
    paths["metrics"] = str(prom)
    snap = d / f"{out_prefix}snapshot.json"
    snap.write_text(json.dumps(merged, indent=2, default=str))
    paths["snapshot"] = str(snap)
    tr_path = d / f"{out_prefix}trace.json"
    tr_path.write_text(json.dumps({
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "otherData": {"source": "fluxsieve.telemetry.merged",
                      "spans_dropped": dropped,
                      "processes": len(snaps)},
    }))
    paths["trace"] = str(tr_path)
    return paths
