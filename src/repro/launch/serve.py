"""Serving drivers — both planes that live under ``repro.serve``:

**Front-end mode** (``--port``): build an enriched store from the synthetic
log workload, then serve it over the socket/HTTP front end
(``repro.serve.frontend``) with per-client admission control, bounded
backpressure, deadline shedding, and the ``/metrics`` Prometheus scrape —
the query plane's real ingress (docs/SERVING.md)::

    PYTHONPATH=src python -m repro.launch.serve --port 7171 \\
        --records 20000 --rules 200 --segment-size 4000 \\
        --max-inflight 8 --rate-per-client 100

**Model mode** (``--arch``): batched generation over log-derived prompts,
with the serving telemetry fed back through the FluxSieve ingestion path
(the paper's recurrent-dashboard loop over serving logs)::

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --reduced \\
        --requests 16 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import compile_cache
from repro.core.matcher import compile_bundle
from repro.core.patterns import Rule, RuleSet
from repro.core.query.engine import Query, QueryEngine
from repro.core.query.mapper import QueryMapper
from repro.core.query.store import SegmentStore
from repro.core.stream_processor import StreamProcessor
from repro.data import tokenizer
from repro.data.generator import LogGenerator, WorkloadSpec


def _serve_frontend(args) -> int:
    """Build a world (``launch.ingest.build_world``) and serve it."""
    from repro.launch.ingest import build_world
    from repro.serve.frontend import FrontEnd

    spec = WorkloadSpec(num_records=args.records)
    world = build_world(spec, rules=args.rules, store=args.store,
                        segment_size=args.segment_size,
                        index_fields=spec.content_fields, shards=args.shards)
    proc, store, engine = world.proc, world.store, world.engine
    print(f"ingested {world.times.records} records into "
          f"{len(store.segments)} segments "
          f"({world.times.throughput():,.0f} rec/s)")

    def ingest_sink(batch):
        out = proc.process(batch)
        store.append(out)
        return len(batch)

    fe = FrontEnd(engine, host=args.host, port=args.port,
                  max_inflight=args.max_inflight, max_queue=args.max_queue,
                  rate_per_client=args.rate_per_client, burst=args.burst,
                  default_deadline_s=args.deadline,
                  ingest=ingest_sink).start()
    print(f"serving on {fe.host}:{fe.port} "
          f"(max_inflight={fe.max_inflight} max_queue={fe.max_queue} "
          f"rate_per_client={fe.admission.rate}/s "
          f"burst={fe.admission.burst}); routes: query/standing/ingest, "
          f"GET /metrics, GET /healthz", flush=True)
    try:
        if args.serve_seconds is not None:
            time.sleep(args.serve_seconds)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        fe.close()
        engine.close()
    return 0


def _serve_model(args) -> int:
    import jax

    from repro.models.model import Model
    from repro.serve.engine import Request, ServeEngine

    model = Model.from_name(args.arch, reduced=args.reduced)
    if not model.cfg.supports_decode:
        raise SystemExit(f"{model.cfg.name} is encoder-only; no decode")
    params = model.init(jax.random.key(args.seed))
    engine = ServeEngine(model, params, batch_size=args.batch,
                         max_cache=args.prompt_len + args.max_new + 1)

    # prompts from the log corpus (fixed-width -> equal-length buckets)
    wspec = WorkloadSpec(num_records=args.requests, seed=args.seed)
    gen = LogGenerator(wspec)
    raw = gen.batch(0, args.requests)
    toks = tokenizer.encode_bytes(raw.columns["content1"])[:, :args.prompt_len]
    toks = np.maximum(toks, 1) % model.cfg.vocab_size
    for i in range(args.requests):
        engine.submit(Request(i, toks[i].astype(np.int32),
                              max_new_tokens=args.max_new))
    responses = engine.run()
    for r in sorted(responses, key=lambda r: r.request_id)[:8]:
        print(f"req {r.request_id:3d}: {r.new_tokens} tokens, "
              f"prefill {r.prefill_ms:.1f} ms, decode {r.decode_ms:.1f} ms")
    print(f"served {len(responses)} requests")

    # telemetry -> FluxSieve ingestion -> analytical plane
    slow_rule = RuleSet((Rule(0, "served", "serve request", fields=("content1",)),))
    bundle = compile_bundle(slow_rule, ("content1",))
    proc = StreamProcessor(bundle, backend="dfa_ref")
    telemetry = proc.process(engine.telemetry_batch())
    store = SegmentStore(segment_size=1024)
    store.append(telemetry)
    store.seal()
    qe = QueryEngine(store, mapper=QueryMapper(slow_rule))
    res = qe.execute(Query(terms=(("content1", "serve request"),),
                           mode="count"), path="fluxsieve")
    print(f"telemetry dashboard: {res.count} serve records "
          f"({res.latency_s * 1e3:.2f} ms via {res.path})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    # front-end mode
    ap.add_argument("--port", type=int, default=None,
                    help="serve the query front end on this port "
                         "(0 = ephemeral; omit for model mode)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--records", type=int, default=20_000,
                    help="front end: synthetic records to ingest before "
                         "serving")
    ap.add_argument("--rules", type=int, default=200)
    ap.add_argument("--segment-size", type=int, default=4000)
    ap.add_argument("--shards", type=int, default=1,
                    help="front end: sharded query executor width")
    ap.add_argument("--store", default=None, help="spill directory")
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="concurrent requests executing against the engine")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="admitted requests allowed to wait for a slot "
                         "before queue_full shedding")
    ap.add_argument("--rate-per-client", type=float, default=100.0,
                    help="token-bucket refill rate per client id (req/s)")
    ap.add_argument("--burst", type=float, default=None,
                    help="token-bucket capacity (default: rate)")
    ap.add_argument("--deadline", type=float, default=5.0,
                    help="default request deadline seconds (clients may "
                         "override per request)")
    ap.add_argument("--serve-seconds", type=float, default=None,
                    help="serve for N seconds then exit (default: forever)")
    # model mode
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.port is not None:
        return _serve_frontend(args)
    if args.arch is None:
        ap.error("pass --port (query front end) or --arch (model serving)")
    return _serve_model(args)


if __name__ == "__main__":
    raise SystemExit(main())
