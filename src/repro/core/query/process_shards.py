"""Process-backed query sharding — ``ShardedQueryExecutor`` across the GIL.

``ShardedQueryExecutor`` partitions a physical plan's tasks across threads;
useful overlap, but one GIL.  ``ProcessQueryPool`` runs the same
segment-hash sharding (``lease.shard_of``, the read-side analogue of the
maintenance pool's shard map) as N spawn *processes*:

  * each shard process opens the store via ``SegmentStore.load`` and keeps
    only its hash shard of the segment list;
  * each shard builds its own ``QueryEngine`` — and therefore **leases its
    own arrangements**: the Shared-Arrangements guarantee (each word column
    crosses H2D once per maintenance epoch) holds *per process*, so the
    per-column upload multiplicity a process contributes is exactly 1 per
    epoch regardless of how many queries it serves;
  * a query broadcast returns counts (count mode) or per-segment matched
    row ids (ids mode) over the pipe; the parent sums counts / unions ids.

Failure semantics mirror the thread sharder's graceful degradation: a
shard that errors, stalls, or dies contributes a *failed* shard (the
merged result is marked partial with its segments accounted as failed)
and is respawned for the next query — never a poisoned pool.
"""
from __future__ import annotations

import multiprocessing as mp
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from repro.core import telemetry
from repro.core.maintenance.lease import shard_of
from repro.device import on_cpu

_SHARD_DEATHS = telemetry.counter(
    "fluxsieve_query_shard_process_deaths_total",
    help="Query shard processes that died or timed out mid-query.")


def _shard_main(cfg: dict, conn) -> None:
    """Shard child: store (this shard's segments only) + private engine +
    private arrangement store.  Serves query commands until EOF."""
    from repro.core import faults
    from repro.core.query.engine import QueryEngine, Query, filter_expired
    from repro.core.query.mapper import QueryMapper
    from repro.core.query.store import SegmentStore

    store = SegmentStore.load(cfg["root"], segment_size=cfg["segment_size"],
                              index_fields=tuple(cfg["index_fields"]))
    index, shards = cfg["shard_index"], cfg["num_shards"]
    store.segments = [s for s in store.segments
                      if shard_of(s.segment_id, shards) == index]
    engine = QueryEngine(store, mapper=QueryMapper(cfg["ruleset"]),
                         backend=cfg["backend"], block_n=cfg["block_n"])
    ident = f"{cfg['worker_id']}/shard-{index}"

    while True:
        try:
            cmd = conn.recv()
        except (EOFError, OSError, KeyboardInterrupt):
            break
        try:
            op = cmd[0]
            if op == "stop":
                conn.send(("bye", None))
                break
            elif op == "query":
                terms, mode, path = cmd[1], cmd[2], cmd[3]
                faults.fire("query.shard", shard=index, worker=ident)
                # ids mode plans as a copy query (id-producing path
                # classes); count mode may legally answer from metadata
                q = Query(terms=tuple(tuple(t) for t in terms),
                          mode="copy" if mode == "ids" else "count")
                if mode == "ids":
                    plan = engine.plan(q, path=path)
                    per_seg = engine.executor.execute(plan, engine.planner)
                    count, ids_by_seg = 0, {}
                    for task, (ids, stats) in zip(plan.tasks, per_seg):
                        if ids is None:
                            continue
                        if isinstance(ids, (int, np.integer)):
                            count += int(ids)
                            continue
                        ids, _ = filter_expired(task, ids, True)
                        count += len(ids)
                        if len(ids):
                            ids_by_seg[int(task.seg.segment_id)] = \
                                np.asarray(ids, np.int64)
                    reply = ("result", {"count": count,
                                        "ids": ids_by_seg,
                                        "segments": len(plan.tasks)})
                else:
                    r = engine.execute(q, path=path)
                    reply = ("result", {
                        "count": int(r.count), "ids": None,
                        "segments": r.segments_total,
                        "scanned": r.segments_scanned,
                        "pruned": r.segments_pruned,
                        "fallback": r.segments_fallback,
                        "failed": r.segments_failed})
            elif op == "refresh":
                deltas = store.refresh()
                # refresh may have pulled in segments of other shards
                # (new seals land wherever the manifest says) — re-filter
                store.segments = [s for s in store.segments
                                  if shard_of(s.segment_id, shards) == index]
                reply = ("ok", deltas)
            elif op == "stats":
                reply = ("stats", {
                    "uploads_per_column": dict(
                        engine.arrangements.upload_counts()),
                    "h2d_bytes": int(engine.arrangements.h2d_bytes),
                    "device_bytes_peak": int(
                        engine.arrangements.device_bytes_peak),
                    "segments": len(store.segments)})
            elif op == "reset_stats":
                engine.arrangements.uploads.clear()
                engine.arrangements.h2d_bytes = 0
                engine.arrangements.device_bytes_peak = \
                    engine.arrangements.device_bytes
                reply = ("ok", None)
            else:
                reply = ("error", f"unknown command {cmd[0]!r}")
        except faults.InjectedCrash:
            import os
            import signal
            os.kill(os.getpid(), signal.SIGKILL)
        except BaseException as e:  # noqa: BLE001 — report, keep serving
            reply = ("error", f"{type(e).__name__}: {e}")
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            break


@dataclass
class ProcessQueryResult:
    """Merged result of one query fanned across shard processes."""
    count: int = 0
    ids: dict = field(default_factory=dict)     # segment_id -> row ids
    segments_total: int = 0
    segments_failed: int = 0
    shards_served: int = 0
    shards_failed: int = 0
    latency_s: float = 0.0

    @property
    def partial(self) -> bool:
        return self.shards_failed > 0


class ProcessQueryPool:
    """N query shards as spawn processes over one spilled store root.

    ``ruleset`` is the active (picklable) RuleSet the shard mappers serve;
    queries broadcast as ``(terms, mode)`` where mode is ``"count"``
    (merged count) or ``"ids"`` (merged per-segment matched row ids).
    ``stats()`` reads each shard's private arrangement accounting — the
    bench's per-process upload-multiplicity evidence.
    """

    def __init__(self, root, ruleset, *, shards: int = 2,
                 backend: str = "ref", block_n: int = 1024,
                 segment_size: int = 100_000,
                 index_fields: tuple = (), worker_id: str = "query-proc",
                 recv_timeout: float = 120.0):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if not on_cpu():
            raise RuntimeError(
                "ProcessQueryPool runs on the CPU backend only: its shard "
                "processes build query executors and would need the device "
                "this process holds; use QueryEngine(shards=N) (thread "
                "shards)")
        self.root = Path(root)
        self.shards = shards
        self.recv_timeout = float(recv_timeout)
        self._ctx = mp.get_context("spawn")
        self._cfg_base = {
            "root": str(self.root), "ruleset": ruleset, "backend": backend,
            "block_n": block_n,
            "segment_size": int(segment_size),
            "index_fields": tuple(index_fields),
            "num_shards": shards, "worker_id": worker_id,
        }
        self._workers = [self._spawn(i) for i in range(shards)]

    def _spawn(self, index: int) -> dict:
        parent_conn, child_conn = self._ctx.Pipe()
        cfg = {**self._cfg_base, "shard_index": index}
        proc = self._ctx.Process(
            target=_shard_main, args=(cfg, child_conn),
            name=f"{self._cfg_base['worker_id']}-{index}", daemon=True)
        proc.start()
        child_conn.close()
        return {"index": index, "proc": proc, "conn": parent_conn,
                "alive": True}

    def _ensure_workers(self) -> None:
        for i, w in enumerate(self._workers):
            if w["alive"] and w["proc"].is_alive():
                continue
            self._mark_dead(w)
            self._workers[i] = self._spawn(w["index"])

    def _mark_dead(self, w: dict) -> None:
        if not w["alive"]:
            return
        w["alive"] = False
        try:
            w["conn"].close()
        except OSError:
            pass
        if w["proc"].is_alive():
            w["proc"].kill()
        w["proc"].join(timeout=5.0)

    def _request(self, w: dict, cmd: tuple):
        if not w["alive"]:
            return None
        try:
            w["conn"].send(cmd)
            deadline = time.monotonic() + self.recv_timeout
            while True:
                if w["conn"].poll(0.05):
                    return w["conn"].recv()
                if not w["proc"].is_alive() and not w["conn"].poll(0.05):
                    raise EOFError("shard process died")
                if time.monotonic() > deadline:
                    raise TimeoutError("shard command timed out")
        except (EOFError, BrokenPipeError, ConnectionResetError, OSError,
                TimeoutError):
            self._mark_dead(w)
            _SHARD_DEATHS.inc()
            telemetry.emit("query_shard_death", plane="query",
                           shard=w["index"], command=cmd[0])
            return None

    def close(self) -> None:
        for w in self._workers:
            if w["alive"]:
                try:
                    w["conn"].send(("stop",))
                except (BrokenPipeError, OSError):
                    pass
        for w in self._workers:
            if w["alive"]:
                w["proc"].join(timeout=5.0)
            self._mark_dead(w)

    def __del__(self):
        try:
            self.close()
        except Exception:   # noqa: BLE001 — interpreter teardown
            pass

    # -- query surface -----------------------------------------------------
    def execute(self, terms, *, mode: str = "count",
                path: str = "fluxsieve") -> ProcessQueryResult:
        """Fan one query out to every shard and merge.  ``terms`` is the
        ``Query.terms`` tuple (picklable); a dead/failed shard degrades the
        result to partial rather than raising — the thread sharder's
        contract, held across processes."""
        self._ensure_workers()
        t0 = time.perf_counter()
        out = ProcessQueryResult()
        # broadcast first, then collect: shards execute concurrently
        inflight = []
        for w in self._workers:
            try:
                w["conn"].send(("query", tuple(terms), mode, path))
                inflight.append(w)
            except (BrokenPipeError, OSError):
                self._mark_dead(w)
                _SHARD_DEATHS.inc()
                out.shards_failed += 1
        for w in inflight:
            reply = self._collect(w)
            if reply is None or reply[0] != "result":
                out.shards_failed += 1
                continue
            r = reply[1]
            out.count += r["count"]
            out.segments_total += r["segments"]
            out.segments_failed += r.get("failed", 0)
            if r["ids"]:
                out.ids.update(r["ids"])
            out.shards_served += 1
        out.latency_s = time.perf_counter() - t0
        return out

    def _collect(self, w: dict):
        try:
            deadline = time.monotonic() + self.recv_timeout
            while True:
                if w["conn"].poll(0.05):
                    reply = w["conn"].recv()
                    if reply[0] == "error":
                        telemetry.emit("query_shard_error", plane="query",
                                       shard=w["index"], error=reply[1])
                        return None
                    return reply
                if not w["proc"].is_alive() and not w["conn"].poll(0.05):
                    raise EOFError("shard process died mid-query")
                if time.monotonic() > deadline:
                    raise TimeoutError("shard query timed out")
        except (EOFError, BrokenPipeError, ConnectionResetError, OSError,
                TimeoutError):
            self._mark_dead(w)
            _SHARD_DEATHS.inc()
            telemetry.emit("query_shard_death", plane="query",
                           shard=w["index"], command="query")
            return None

    def refresh(self) -> None:
        """Every shard re-reads the on-disk world (new seals, maintenance
        installs) and re-filters to its hash shard."""
        self._ensure_workers()
        for w in self._workers:
            self._request(w, ("refresh",))

    def stats(self) -> list:
        """Per-shard arrangement accounting:
        ``[{"uploads_per_column", "h2d_bytes", "device_bytes_peak",
        "segments"}, ...]`` — each shard's PRIVATE arrangement store, so
        ``max(uploads_per_column.values()) == 1`` per epoch per process is
        the Shared-Arrangements invariant held across the GIL boundary."""
        self._ensure_workers()
        out = []
        for w in self._workers:
            reply = self._request(w, ("stats",))
            out.append(reply[1] if reply is not None
                       and reply[0] == "stats" else None)
        return out

    def reset_stats(self) -> None:
        """Zero every shard's upload/H2D accounting (bench lane
        boundaries)."""
        self._ensure_workers()
        for w in self._workers:
            self._request(w, ("reset_stats",))
