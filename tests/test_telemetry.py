"""Unified telemetry plane — registry, spans, events, exporters.

The contract under test:

  * the registry is thread-safe (12 concurrent writers lose no update) and
    ``reset()`` zeroes in place so cached handles stay valid;
  * log2 histograms report percentiles within one octave of numpy's answer
    WITHOUT retaining samples, with exact min/max;
  * the span tracer is a bounded ring buffer (memory never grows) whose
    Chrome-trace export is valid trace-event JSON with parent/child linkage;
  * one end-to-end ingest -> query -> backfill run lands series from all
    FIVE planes (ingest, match, query, arrangement, maintenance) in one
    ``telemetry.snapshot()`` — the paper's unified-plane claim, applied to
    our own observability;
  * the orphan sweeper collects crash-leaked spill dirs (and ONLY those);
  * a missing spill dir at load surfaces as a counter + structured event,
    not just a warning.
"""
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from repro.core import telemetry
from repro.core.control_plane import ControlBus
from repro.core.maintenance import BackfillWorker, SpillGC
from repro.core.matcher import compile_bundle
from repro.core.object_store import ObjectStore
from repro.core.patterns import Rule, RuleSet
from repro.core.query.engine import Query, QueryEngine
from repro.core.query.mapper import QueryMapper
from repro.core.query.store import RETIRED_MARKER, SegmentStore
from repro.core.stream_processor import StreamProcessor
from repro.core.telemetry.metrics import Histogram, MetricsRegistry
from repro.core.telemetry.trace import Tracer
from repro.core.updater import MatcherUpdater
from repro.data.generator import LogGenerator, WorkloadSpec
from repro.data.pipeline import IngestPipeline


# ---------------------------------------------------------------------------
# Registry: thread safety, in-place reset, kind collision, enable gate
# ---------------------------------------------------------------------------

def test_registry_thread_safety_12_writers():
    """12 writer threads × 2000 increments each: no lost update on the
    counter, the gauge aggregate, or the histogram count — and get-or-create
    races resolve to ONE metric object per (name, labels)."""
    reg = MetricsRegistry()
    threads, per_thread = 12, 2000
    start = threading.Barrier(threads)
    errors = []

    def writer(i):
        try:
            start.wait()
            c = reg.counter("t_ops_total")
            g = reg.gauge("t_level")
            h = reg.histogram("t_lat_seconds")
            lc = reg.counter("t_labeled_total", labels={"worker": str(i % 3)})
            for k in range(per_thread):
                c.inc()
                g.inc(2)
                g.dec()
                h.observe(1e-4 * (k + 1))
                lc.inc()
        except Exception as e:  # noqa: BLE001 — surfaced in the main thread
            errors.append(e)

    ts = [threading.Thread(target=writer, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    assert reg.counter("t_ops_total").value == threads * per_thread
    assert reg.gauge("t_level").value == threads * per_thread
    assert reg.histogram("t_lat_seconds").count == threads * per_thread
    by_label = reg.snapshot()["counters"]["t_labeled_total"]
    assert sorted(s["labels"]["worker"] for s in by_label) == ["0", "1", "2"]
    assert sum(s["value"] for s in by_label) == threads * per_thread


def test_reset_zeroes_in_place_and_handles_stay_valid():
    reg = MetricsRegistry()
    c = reg.counter("r_total")
    h = reg.histogram("r_seconds")
    c.inc(5)
    h.observe(0.25)
    reg.reset()
    assert c.value == 0 and h.count == 0
    # the CACHED handle keeps working — same object the registry serves
    c.inc(3)
    assert reg.counter("r_total") is c
    assert reg.snapshot()["counters"]["r_total"][0]["value"] == 3


def test_kind_collision_rejected():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_set_enabled_gates_all_mutation():
    reg = MetricsRegistry()
    c, g, h = reg.counter("e_total"), reg.gauge("e_g"), reg.histogram("e_s")
    assert telemetry.enabled()
    telemetry.set_enabled(False)
    try:
        c.inc()
        g.set(7)
        h.observe(0.1)
        with telemetry.span("gated"):
            pass
        telemetry.emit("gated_event", plane="test")
    finally:
        telemetry.set_enabled(True)
    assert c.value == 0 and g.value == 0 and h.count == 0
    assert not any(e["kind"] == "gated_event" for e in telemetry.events.events())


# ---------------------------------------------------------------------------
# Histogram: percentile accuracy vs numpy, without sample retention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_within_one_octave_of_numpy(dist):
    """Log2 buckets guarantee any quantile is within ONE octave (factor of
    2) of the exact sample quantile — the design's accuracy bound."""
    rng = np.random.default_rng(42)
    if dist == "lognormal":
        samples = rng.lognormal(mean=-7.0, sigma=1.5, size=5000)
    elif dist == "uniform":
        samples = rng.uniform(1e-5, 1e-2, size=5000)
    else:
        # asymmetric split so no tested quantile falls in the empty gap
        # between modes (there numpy interpolates into no-data territory
        # and no histogram can follow)
        samples = np.concatenate([rng.normal(2e-4, 2e-5, 3000),
                                  rng.normal(5e-2, 5e-3, 2000)]).clip(1e-6)
    h = Histogram("acc_seconds", {})
    for v in samples:
        h.observe(float(v))
    for q in (0.50, 0.90, 0.99):
        est = h.quantile(q)
        true = float(np.quantile(samples, q))
        assert abs(math.log2(est / true)) <= 1.0, \
            f"{dist} p{int(q * 100)}: est {est:.3g} vs true {true:.3g}"
    assert h.quantile(0.0) == float(samples.min())   # clamped to exact min
    assert h.quantile(1.0) == float(samples.max())   # and exact max
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum(), rel=1e-9)


def test_histogram_bucket_edges():
    h = Histogram("edge_seconds", {})
    # exact powers of two land in the bucket they OPEN: [2^e, 2^(e+1))
    i = h.bucket_index(2.0 ** -10)
    lo, hi = h.bucket_bounds(i)
    assert lo == 2.0 ** -10 and hi == 2.0 ** -9
    # out-of-span values clamp to the edge buckets, never raise
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(1e-30) == 0
    assert h.bucket_index(1e9) == len(h._counts) - 1


# ---------------------------------------------------------------------------
# Tracer: ring-buffer bound, Chrome-trace validity, parent linkage
# ---------------------------------------------------------------------------

def test_span_ring_buffer_is_bounded():
    tr = Tracer(capacity=32)
    for i in range(100):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 32
    assert tr.dropped == 68
    # newest spans won (the tail of the timeline is what survives)
    assert [ev["name"] for ev in tr.spans()][-1] == "s99"
    doc = tr.export_chrome_trace()
    assert doc["otherData"]["spans_dropped"] == 68


def test_chrome_trace_export_is_valid_trace_event_json():
    tr = Tracer()
    with tr.span("outer", cat="test", phase="setup"):
        time.sleep(0.001)
        with tr.span("inner", cat="test"):
            time.sleep(0.001)
    doc = json.loads(json.dumps(tr.export_chrome_trace()))  # JSON round-trip
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"                      # complete events
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
        assert isinstance(ev["dur"], float) and ev["dur"] > 0.0
        assert ev["pid"] == os.getpid()
        assert isinstance(ev["tid"], int)
        assert ev["cat"] == "test"
    inner, outer = evs  # inner exits first — ring order is completion order
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert "parent" not in outer["args"]            # root span
    assert outer["args"]["phase"] == "setup"        # span args survive export
    # temporal containment: the child ran inside the parent
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_prometheus_text_renders_and_histograms_are_cumulative():
    reg = MetricsRegistry()
    reg.counter("p_total", help='say "hi"\nok').inc(4)
    reg.counter("p_labeled_total", labels={"path": "a"}).inc(1)
    reg.counter("p_labeled_total", labels={"path": "b"}).inc(2)
    h = reg.histogram("p_seconds", help="latency")
    for v in (1e-4, 2e-4, 1e-3, 1e-2):
        h.observe(v)
    text = telemetry.prometheus_text(reg)
    assert '# HELP p_total say \\"hi\\"\\nok' in text
    assert "# TYPE p_total counter" in text
    assert "p_total 4" in text
    assert 'p_labeled_total{path="a"} 1' in text
    assert 'p_labeled_total{path="b"} 2' in text
    assert "# TYPE p_seconds histogram" in text
    assert 'p_seconds_bucket{le="+Inf"} 4' in text
    assert "p_seconds_count 4" in text
    # cumulative bucket counts are monotone nondecreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("p_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 4


# ---------------------------------------------------------------------------
# End to end: one snapshot carries series from all five planes
# ---------------------------------------------------------------------------

def make_world(tmp_path, *, num_records=4000, segment_size=1000, hold_back=0):
    spec = WorkloadSpec(num_records=num_records, ultra_rate=1e-3,
                        high_rate=1e-2, seed=13, text_width=256)
    gen = LogGenerator(spec)
    rules = [Rule(i, t.term, t.term, fields=(t.fieldname,))
             for i, t in enumerate(spec.planted)]
    # one DENSE rule (matches most records): too dense for seal-time
    # postings, so querying it exercises the bitmap-scan class and the
    # shared arrangement plane
    rules.append(Rule(len(rules), "dense_a", "a", fields=("content1",)))
    full = RuleSet(tuple(rules))
    initial = full.without_ids([hold_back])
    bus, ostore = ControlBus(), ObjectStore()
    proc = StreamProcessor(compile_bundle(initial, spec.content_fields),
                           bus=bus, store=ostore)
    store = SegmentStore(segment_size=segment_size, root=tmp_path)
    updater = MatcherUpdater(ostore, bus, spec.content_fields,
                             initial=initial)
    IngestPipeline(gen, store, proc).run(batch_size=1000)
    mapper = QueryMapper(initial, version_id=0)
    engine = QueryEngine(store, mapper=mapper)
    return dict(spec=spec, gen=gen, full=full, bus=bus, ostore=ostore,
                proc=proc, store=store, updater=updater, mapper=mapper,
                engine=engine, late=spec.planted[hold_back])


FIVE_PLANE_SERIES = {
    "ingest": "fluxsieve_ingest_records_total",
    "match": "fluxsieve_match_dispatch_total",
    "query": "fluxsieve_query_total",
    "arrangement": "fluxsieve_arrangement_uploads_total",
    "maintenance": "fluxsieve_maintenance_segments_backfilled_total",
}


def test_end_to_end_snapshot_covers_all_five_planes(tmp_path):
    """Ingest -> query -> late-rule backfill, then ONE snapshot: every
    plane reported, the trace timeline has spans from ingest, match, query
    AND maintenance, and the event log saw epoch publishes, manifest
    commits, and lease acquisitions."""
    telemetry.reset()
    w = make_world(tmp_path)
    # query (fluxsieve path -> arrangement uploads)
    late = w["late"]
    q = Query(terms=((late.fieldname, late.term),), mode="count")
    h = w["updater"].submit(w["full"], asynchronous=False)
    assert h.published, h.error
    w["proc"].poll_updates()
    w["mapper"].notify(w["full"], version_id=w["proc"].active_version_id)
    worker = BackfillWorker(w["store"], w["bus"], w["ostore"])
    rep = worker.run_until_converged()
    assert rep.segments_backfilled > 0
    res = w["engine"].execute(q, path="fluxsieve")
    assert res.count == w["gen"].true_count(late)
    # the dense rule has no seal-time postings -> bitmap-scan class ->
    # shared-arrangement uploads + the stacked device dispatch
    q_dense = Query(terms=(("content1", "a"),), mode="copy")
    r_dense = w["engine"].execute(q_dense, path="fluxsieve")
    assert r_dense.count == w["engine"].execute(q_dense,
                                               path="full_scan").count
    assert "bitmap" in r_dense.path_classes, r_dense.path_classes

    snap = telemetry.snapshot()
    counters = snap["counters"]
    for plane, name in FIVE_PLANE_SERIES.items():
        assert name in counters, f"{plane} plane missing from snapshot"
        assert sum(s["value"] for s in counters[name]) > 0, \
            f"{plane} plane series {name} is zero"
    # ingest stage latencies landed as histograms
    stages = {s["labels"]["stage"]
              for s in snap["histograms"]["fluxsieve_ingest_stage_seconds"]
              if s["count"]}
    assert {"generate", "dispatch", "store"} <= stages
    # the trace timeline saw multiple planes
    cats = {ev["cat"] for ev in telemetry.export_chrome_trace()["traceEvents"]}
    assert {"ingest", "match", "query", "maintenance"} <= cats
    # structured events from the storage + maintenance planes
    kinds = {e["kind"] for e in snap["events"]}
    assert {"epoch_publish", "manifest_commit"} <= kinds
    # the exporters accept the real registry end to end
    text = telemetry.prometheus_text()
    assert "# TYPE fluxsieve_query_latency_seconds histogram" in text
    json.dumps(snap, default=str)   # snapshot is JSON-able


# ---------------------------------------------------------------------------
# Spans inside the program: one served request, one ingest seal
# ---------------------------------------------------------------------------

def test_span_set_adds_args_and_null_span_takes_them():
    tr = Tracer()
    with tr.span("outer", n=1) as sp:
        sp.set(outcome="admitted")
    assert tr.spans()[0]["args"]["outcome"] == "admitted"
    assert tr.spans()[0]["args"]["n"] == 1
    telemetry.set_enabled(False)
    try:
        with tr.span("off") as sp:
            sp.set(outcome="ignored")
    finally:
        telemetry.set_enabled(True)
    assert len(tr) == 1


def _spans_of(names, *, want, timeout_s=5.0):
    """The tracer's finished spans once every name in ``want`` is there
    (a server thread records its last span after the client has read the
    response)."""
    t_end = time.monotonic() + timeout_s
    while True:
        spans = [s for s in telemetry.trace.TRACER.spans()
                 if s["name"] in names]
        if want <= {s["name"] for s in spans} or time.monotonic() > t_end:
            return spans
        time.sleep(0.01)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.serve.frontend import FrontEnd
    w = make_world(tmp_path_factory.mktemp("served"))
    with FrontEnd(w["engine"]) as fe:
        yield fe


@pytest.mark.parametrize("mode", ["count", "ids"])
def test_served_request_spans_share_the_wire_id(served, mode):
    """Queue wait, request and response carry the request's wire id; the
    engine's stages nest under its ``query/execute`` span, and only a
    query that reads rows materialises them."""
    from repro.serve.frontend import ServeClient
    telemetry.reset()
    rid = 4100 + len(mode)
    with ServeClient(served.host, served.port) as client:
        # the dense rule and a planted term: the stacked bitmap path
        resp = client.request("query", id=rid, mode=mode,
                              terms=[["content1", "a"],
                                     ["content2", "HIGHneedle2x"]])
    assert resp["status"] == 200 and resp["id"] == rid
    stages = {"query/plan", "query/arrangement", "query/stacked_dispatch",
              "query/device_wait", "query/materialize"}
    serve = {"serve/queue_wait", "serve/request", "serve/respond"}
    spans = _spans_of(serve | stages | {"query/execute"},
                      want=serve | {"query/execute"})
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name in serve:
        (span,) = by_name[name]
        assert span["args"]["request"] == rid, name
    assert by_name["serve/queue_wait"][0]["args"]["outcome"] == "admitted"
    (execute,) = by_name["query/execute"]
    assert execute["args"]["parent"] == \
        by_name["serve/request"][0]["args"]["id"]
    children = {s["name"] for s in spans
                if s["args"].get("parent") == execute["args"]["id"]}
    assert {"query/plan", "query/arrangement", "query/stacked_dispatch",
            "query/device_wait"} <= children
    assert ("query/materialize" in children) == (mode == "ids")
    (arrangement,) = by_name["query/arrangement"]
    assert arrangement["args"]["hit"] in (True, False)


def test_ingest_spans_seal_spill_and_wal_truncation(tmp_path):
    """A rooted ingest with the WAL on: each seal holds its segment's
    spill, and each stored batch truncates the journal once."""
    spec = WorkloadSpec(num_records=3000, ultra_rate=1e-3, high_rate=1e-2,
                        seed=5, text_width=128)
    rules = RuleSet(tuple(Rule(i, t.term, t.term, fields=(t.fieldname,))
                          for i, t in enumerate(spec.planted)))
    proc = StreamProcessor(compile_bundle(rules, spec.content_fields),
                           backend="dfa_ref")
    store = SegmentStore(segment_size=1000, root=tmp_path)
    telemetry.reset()
    IngestPipeline(LogGenerator(spec), store, proc, wal=True).run(
        batch_size=500)
    spans = telemetry.trace.TRACER.spans()

    def named(name):
        return [s for s in spans if s["name"] == name]

    seals, spills = named("store/seal"), named("store/spill")
    assert len(seals) == len(spills) == 3
    assert [s["args"]["rows"] for s in seals] == [1000] * 3
    assert sorted(s["args"]["parent"] for s in spills) == \
        sorted(s["args"]["id"] for s in seals)
    stores, truncs = named("ingest/store"), named("ingest/wal_truncate")
    assert len(stores) == len(truncs) == 6
    assert sorted(s["args"]["parent"] for s in truncs) == \
        sorted(s["args"]["id"] for s in stores)
    assert {s["args"]["parent"] for s in seals} <= \
        {s["args"]["id"] for s in stores}


# ---------------------------------------------------------------------------
# Satellite: orphan-dir sweep (crash between spill and manifest commit)
# ---------------------------------------------------------------------------

def _age(path, seconds):
    old = time.time() - seconds
    os.utime(path, (old, old))


def test_spillgc_sweeps_orphan_dirs(tmp_path):
    """A ``segment-*`` dir absent from the root manifest and never
    tombstoned (crash between spill and manifest registration) is swept
    once past the generous horizon; live and young dirs survive."""
    w = make_world(tmp_path)
    n_live = len(w["store"].segments)
    assert n_live >= 2
    # fabricate two orphans: one old (collectable), one fresh (in-flight)
    old_orphan = tmp_path / "segment-7001"
    old_orphan.mkdir()
    (old_orphan / "content.npy").write_bytes(b"x" * 512)
    _age(old_orphan, 7200)
    fresh_orphan = tmp_path / "segment-7002"
    fresh_orphan.mkdir()
    (fresh_orphan / "content.npy").write_bytes(b"y" * 512)

    orphans = telemetry.metrics.REGISTRY.counter(
        "fluxsieve_maintenance_gc_orphans_deleted_total")
    before = orphans.value
    rep = SpillGC(w["store"], orphan_grace_s=3600.0).run_cycle()
    assert rep.orphans_deleted == 1
    assert rep.dirs_deleted == 0
    assert rep.bytes_deleted == 512
    assert rep.dirs_kept_grace == 1         # the fresh orphan waits
    assert not old_orphan.exists()
    assert fresh_orphan.exists()
    assert len(w["store"].segments) == n_live   # live segments untouched
    assert orphans.value == before + 1
    ev = [e for e in telemetry.events.events(kind="gc_sweep")
          if e.get("orphans_deleted")]
    assert ev and ev[-1]["orphans_deleted"] == 1

    # reload sanity: the sweep removed nothing the manifest knows about
    reopened = SegmentStore.load(tmp_path)
    assert reopened.num_records == w["store"].num_records


def test_spillgc_never_sweeps_pre_manifest_stores(tmp_path):
    """Without an on-disk root manifest the unregistered dirs ARE the
    data — the orphan sweep must refuse to run."""
    root = tmp_path / "pre_manifest"
    root.mkdir()
    d = root / "segment-0"
    d.mkdir()
    (d / "content.npy").write_bytes(b"z" * 64)
    _age(d, 7200)
    store = SegmentStore(root=root)     # fresh store: manifest never written
    assert not store.manifest.path.exists()
    rep = SpillGC(store, orphan_grace_s=0.0).run_cycle()
    assert rep.orphans_deleted == 0
    assert d.exists()


def test_spillgc_still_collects_tombstoned_dirs(tmp_path):
    """The RETIRED path is unchanged by the orphan sweep: a drained
    tombstoned dir collects under its own (short) grace window."""
    w = make_world(tmp_path)
    seg = w["store"].segments[0]
    assert w["store"].retire_segments([seg])
    marker = seg.path / RETIRED_MARKER
    assert marker.exists()
    _age(marker, 120)
    rep = SpillGC(w["store"], grace_s=60.0).run_cycle()
    assert rep.dirs_deleted == 1
    assert rep.orphans_deleted == 0
    assert not seg.path.exists()


# ---------------------------------------------------------------------------
# Satellite: missing spill dir at load -> counter + structured event
# ---------------------------------------------------------------------------

def test_missing_spill_dir_records_event_and_counter(tmp_path):
    import shutil
    w = make_world(tmp_path)
    victim = w["store"].segments[0]
    shutil.rmtree(victim.path)
    missing = telemetry.metrics.REGISTRY.counter(
        "fluxsieve_store_segments_missing_total")
    before = missing.value
    with pytest.warns(RuntimeWarning, match="missing"):
        reopened = SegmentStore.load(tmp_path)
    assert len(reopened.segments) == len(w["store"].segments) - 1
    assert missing.value == before + 1
    evs = telemetry.events.events(kind="segment_missing")
    assert evs and evs[-1]["dir"] == victim.path.name
