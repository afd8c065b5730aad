"""Open-loop queries over the wire: a seeded Poisson schedule of filter
queries sent to the program's ``FrontEnd`` over ``QueryEngine`` by a
load-generator process, from many client ids, at a fixed rate.

Set-up reopens the store a query server restarts with
(``SegmentStore.load``).  The store of a (configuration, seed, program
source) is made once, by ``prepare``: the program's ``IngestPipeline``
ingests the generated records and spills them under ``_work/stores``.
The entry runs ``prepare`` in a child process before the set-up clock
starts, so every run's set-up is the same restart.  Every distinct query
of the mix is then served twice over the wire, which compiles its shapes
and builds its arrangements.

Latency runs from each request's due time to its full response.  After
the window every answered request is compared with the reference's
answer over the generated records.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from chipbench import schedule, world
from chipbench.gen import Generator
from chipbench.harness import tree_digest
from chipbench.reference import Corpus

STORES_KEPT = 6     # stores kept per configuration: one set of seeds
FAILED = ("timeout", "lost", "conn_error")


def _key(run) -> str:
    yard = tree_digest(run.home / "chipbench")
    return f"{run.config['name']}-{run.seed}-{yard}"


def _store_dir(run):
    return (run.work / "stores" /
            f"{_key(run)}-{tree_digest(run.root / 'src')}")


def prepared(run) -> bool:
    return (_store_dir(run) / "READY").exists()


def prepare(run) -> None:
    """Ingest the seed's records through the program into a spilled
    store, keeping the newest ``STORES_KEPT`` of the configuration."""
    from repro.core.query.store import SegmentStore
    from repro.data.pipeline import IngestPipeline
    cfg = run.config
    d = _store_dir(run)
    d.parent.mkdir(exist_ok=True)
    shutil.rmtree(d, ignore_errors=True)
    old = sorted((p for p in d.parent.glob(f"{cfg['name']}-*")
                  if p.is_dir()), key=lambda p: p.stat().st_mtime)
    for p in old[:max(0, len(old) - STORES_KEPT + 1)]:
        shutil.rmtree(p, ignore_errors=True)
    cols = Generator(cfg, run.seed).records(0, cfg["store_records"])
    _, _, proc = world.processor(cfg)
    store = SegmentStore(segment_size=cfg["segment_size"], root=d,
                         index_fields=tuple(cfg["text_index_fields"]))
    IngestPipeline(world.Source(cols), store, proc,
                   wal=cfg["wal"]).run(batch_size=cfg["batch_size"],
                                       limit=cfg["store_records"])
    (d / "READY").write_text("")
    # write the store back now, not during the next set-up
    os.sync()
    run.note(f"store built at {d.name}")


def setup(run):
    from repro.core.query.engine import QueryEngine
    from repro.core.query.mapper import QueryMapper
    from repro.core.query.profiler import QueryProfiler
    from repro.core.query.store import SegmentStore
    from repro.serve.frontend import FrontEnd, ServeClient
    cfg, traffic = run.config, run.traffic
    if not prepared(run):       # a run past the entry, as tests make
        prepare(run)
    d = _store_dir(run)
    os.utime(d)
    store = SegmentStore.load(d, segment_size=cfg["segment_size"],
                              index_fields=tuple(cfg["text_index_fields"]))
    engine = QueryEngine(store, mapper=QueryMapper(world.program_ruleset(cfg)),
                         profiler=QueryProfiler())
    fcfg = cfg["frontend"]
    fe = FrontEnd(engine, max_inflight=fcfg["max_inflight"],
                  max_queue=fcfg["max_queue"],
                  rate_per_client=fcfg["rate_per_client"],
                  burst=fcfg["burst"],
                  default_deadline_s=fcfg["deadline_s"]).start()
    state = {"run": run, "store": store, "engine": engine, "fe": fe}
    try:
        with ServeClient(fe.host, fe.port, client_id="warmup",
                         timeout=600.0) as client:
            for _ in range(2):
                for qi, mode, _ in schedule.kinds(traffic):
                    got = client.query(traffic["queries"][qi]["terms"],
                                       mode=mode, name=f"q{qi}")
                    if got.get("status") != 200:
                        raise RuntimeError(f"warm-up q{qi} {mode}: {got}")
    except BaseException:
        close(state)
        raise
    sizes = {s.num_records for s in store.segments}
    state["shapes"] = {"segment_records": sizes.pop() if len(sizes) == 1
                       else None,
                       "block": engine.plan_executor.block_n,
                       "preds": {f"q{i}": len(q["terms"]) for i, q in
                                 enumerate(traffic["queries"])}}
    return state


def measure(state, window) -> None:
    run = state["run"]
    traffic = run.traffic
    sched = schedule.build(traffic, run.seed, run.seconds)
    plan = {"host": state["fe"].host, "port": state["fe"].port,
            "lead_s": 1.0, "pool": traffic["connections"],
            "timeout_s": traffic["client_timeout_s"],
            "requests": [[off, client, f"q{qi}",
                          traffic["queries"][qi]["terms"], mode]
                         for off, client, qi, mode in sched]}
    plan_path = run.work / "loadgen-plan.json"
    out_path = run.work / "loadgen-out.json"
    plan_path.write_text(json.dumps(plan))
    out_path.unlink(missing_ok=True)
    env = dict(os.environ, PYTHONPATH=str(run.home))
    window.shapes = state["shapes"]
    window.begin()
    proc = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen", str(plan_path),
         str(out_path)], cwd=run.home, env=env, stdout=subprocess.PIPE,
        text=True)
    try:
        line = proc.stdout.readline().split()
        if len(line) != 2 or line[0] != "ready":
            raise RuntimeError(f"load generator did not start: {line}")
        # perf_counter and monotonic differ by a constant in one boot
        t0 = float(line[1]) + time.perf_counter() - time.monotonic()
        proc.wait(timeout=run.seconds + traffic["client_timeout_s"] + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    window.end(t0, t0 + run.seconds)
    if proc.returncode:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    out = json.loads(out_path.read_text())
    state["rows"] = out["rows"]
    state["names"] = [r[2] for r in plan["requests"]]
    state["modes"] = [r[4] for r in plan["requests"]]
    state["opened"] = out["opened"]
    run.note(f"loadgen: cpu {out['cpu_s']} s; stalls (s after t0, s late) "
             f"{out['stalls']}")
    window.loadgen = out["rows"]


def report(state, window):
    run = state["run"]
    rows = state["rows"]
    timeout_ms = run.traffic["client_timeout_s"] * 1e3
    lat, kinds = [], {}
    for due, sent, done, status, _, _ in rows:
        if status == 200:
            lat.append((done - due) * 1e3)
        else:
            kinds[str(status)] = kinds.get(str(status), 0) + 1
            # a failed request misses any latency limit
            lat.append(max(timeout_ms, 0 if done is None
                           else (done - due) * 1e3))
    p50, p95 = np.percentile(lat, [50, 95])
    half = len(lat) // 2       # rows are in due order
    run.note(f"p95_by_half_ms {np.percentile(lat[:half], 95)} "
             f"{np.percentile(lat[half:], 95)}")
    late = [(s - d) * 1e3 if s is not None else 0.0 for d, s, *_ in rows]
    worst = int(np.argmax(late))
    t0 = rows[0][0] if rows else 0.0
    by_second = {}
    for due, _, _, status, _, _ in rows:
        if status != 200:
            k = int(due - t0)
            by_second[k] = by_second.get(k, 0) + 1
    run.note(f"failures_by_kind {json.dumps(kinds, sort_keys=True)}; "
             f"by second of the window {by_second}")
    run.note(f"loadgen late_p95_ms {np.percentile(late, 95)} late_max_ms "
             f"{late[worst]} at request {worst} of {len(rows)}, "
             f"extra_connections {state['opened']}")
    from repro.core.query.engine import Query
    classes = {}
    for qi, mode, _ in schedule.kinds(run.traffic):
        q = Query(terms=tuple(map(tuple, run.traffic["queries"][qi]["terms"])),
                  mode="count" if mode == "count" else "copy")
        classes[f"q{qi}/{mode}"] = state["engine"].plan(q).class_counts()
    run.note(f"path_classes {json.dumps(classes, sort_keys=True)}")
    return ({"query_p50_ms": p50, "query_p95_ms": p95}, len(rows),
            sum(kinds.values()))


def _truth(state) -> dict:
    """Reference answers (sorted record indices) per query of the mix,
    kept per (configuration, seed, yardstick) once computed."""
    run = state["run"]
    path = run.work / "truth" / f"{_key(run)}-{run.cell['traffic']}.json"
    if path.exists():
        return {k: np.asarray(v, np.int64)
                for k, v in json.loads(path.read_text()).items()}
    corpus = Corpus(Generator(run.config, run.seed).records(
        0, run.config["store_records"]))
    truth = {f"q{i}": corpus.answer(q["terms"])
             for i, q in enumerate(run.traffic["queries"])}
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps({k: v.tolist() for k, v in truth.items()}))
    return truth


def verify(state, control: bool) -> list:
    run = state["run"]
    cfg = run.config
    truth = _truth(state)
    served = {}
    if control:
        # the control: the reference in the program's place, answering
        # from a store that lost its last sealed segment, which breaks
        # "each acknowledged record is queryable"
        kept = cfg["store_records"] - cfg["segment_size"]
        served = {k: v[v < kept] for k, v in truth.items()}
    wrong = unanswered = 0
    for row, name, mode in zip(state["rows"], state["names"],
                               state["modes"]):
        status, count, ids = row[3], row[4], row[5]
        if status in FAILED:
            unanswered += 1
            continue
        if status != 200:
            continue
        if control:
            count = len(served[name])
            ids = (served[name] * 1000).tolist()
        want = truth[name]
        if count != len(want) or (mode == "ids"
                                  and ids != (want * 1000).tolist()):
            wrong += 1
    unsealed = cfg["store_records"] - state["store"].sealed_rows
    return [("wrong_answers", wrong, 0), ("unanswered", unanswered, 0),
            ("rows_unsealed", unsealed, 0)]


def close(state) -> None:
    state["fe"].close()
    state["engine"].close()
