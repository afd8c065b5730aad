"""ProcessMaintenancePool — the maintenance plane as real OS processes.

``MaintenanceWorkerPool`` fans workers out on *threads*: correct, but every
worker shares one GIL, so the committed backfill scaling is capped by the
single-process CPU ceiling its own bench calibrates.  This pool escapes
that ceiling: each worker is a ``multiprocessing`` (spawn) child that

  * opens the store itself via ``SegmentStore.load`` (the on-disk
    manifest / fence / checkpoint machinery is already process-safe),
  * coordinates purely through the **durable** control plane — the
    ``DurableControlBus`` topic logs for targets/acks and the
    ``DurableLeaseManager`` for per-segment leases + fencing epochs, both
    living under ``<root>/control-bus/`` — never through Python object
    sharing, and
  * survives SIGKILL: a killed worker's lease expires, its replacement
    (respawned under the SAME worker id, hence the same consumer group)
    re-derives the target from the topic history, resumes from the
    row-watermark checkpoints, and the fencing epoch granted to any
    successor rejects the zombie's late writes.

What is shared vs per-process:

  * shared (via the filesystem): segment spill dirs + manifest, bus topic
    logs + committed offsets, the lease/epoch table, object-store blobs;
  * per-process: the ``SegmentStore`` object and its column caches, the
    compiled-matcher cache (jitted engines cannot cross a process
    boundary — each worker warms its own once per target version, see
    ``BackfillWorker.warm_matchers``), telemetry registries (merged after
    the fact via per-process ``write_dump`` prefixes).

The parent keeps the thread pool's surface — ``run_cycle`` /
``run_until_converged`` / ``worker_ids`` / ``pending_segments`` /
``set_target`` / ``leases`` — so launchers and tests swap worker models
with one flag.  Between cycles the parent calls ``store.refresh()`` on its
own store object (when given) so its post-convergence assertions see the
children's installs.
"""
from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import signal
import time
from pathlib import Path

from repro.core import telemetry
from repro.core.control_plane import CONTROL_DIRNAME, DurableControlBus
from repro.core.maintenance.backfill import BackfillReport, merge_reports
from repro.core.maintenance.lease import DurableLeaseManager
from repro.device import on_cpu

_DEATHS = telemetry.counter(
    "fluxsieve_maintenance_worker_deaths_total",
    help="Maintenance worker processes that died mid-cycle (killed, "
         "crashed, or stalled past the command timeout).")
_RESPAWNS = telemetry.counter(
    "fluxsieve_maintenance_worker_respawns_total",
    help="Maintenance worker processes respawned under their old identity.")


def _worker_main(cfg: dict, conn) -> None:
    """Child entry point (spawn target — module level, import-safe).

    Builds the whole maintenance stack from the durable world: store from
    the manifest, bus + leases from ``<root>/control-bus/``, artifacts
    from the shared object store.  Then serves pipe commands until EOF.

    An ``InjectedCrash`` escaping the worker is honored as a REAL hard
    kill (``SIGKILL`` to self): the PR 7 kill-point machinery extends to
    processes — no Python cleanup, no atexit, exactly what a crashed or
    OOM-killed worker leaves behind.
    """
    from repro.core import faults
    from repro.core.maintenance.backfill import BackfillWorker
    from repro.core.maintenance.scheduler import (MaintenancePolicy,
                                                  MaintenanceScheduler)
    from repro.core.object_store import ObjectStore
    from repro.core.query.store import SegmentStore

    root = Path(cfg["root"])
    store = SegmentStore.load(root, segment_size=cfg["segment_size"],
                              index_fields=tuple(cfg["index_fields"]))
    bus = DurableControlBus(root / CONTROL_DIRNAME)
    leases = DurableLeaseManager(root / CONTROL_DIRNAME,
                                 ttl=cfg["lease_ttl"])
    ostore = ObjectStore(root=cfg["objects_root"])
    scheduler = None
    if cfg["policy"] is not None:
        scheduler = MaintenanceScheduler(
            None, MaintenancePolicy(**cfg["policy"]))
    worker = BackfillWorker(
        store, bus, ostore, worker_id=cfg["worker_id"],
        scheduler=scheduler, backend=cfg["backend"],
        block_n=cfg["block_n"],
        shard_index=cfg["shard_index"], num_shards=cfg["num_shards"],
        leases=leases, rows_per_pass=cfg["rows_per_pass"])

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
            elif op == "cycle":
                store.refresh()     # see the parent's newest seals/compactions
                rep = worker.run_cycle(max_segments=cmd[1])
                acked = (worker._target is not None
                         and not worker._ack_pending)
                reply = ("report", rep, acked)
            elif op == "pending":
                store.refresh()
                worker.poll_target()
                reply = ("pending",
                         [s.segment_id for s in worker.pending_segments()])
            elif op == "set_target":
                worker.set_target(cmd[1])
                reply = ("ok", None)
            elif op == "warm":
                store.refresh()
                worker.poll_target()
                reply = ("ok", worker.warm_matchers())
            elif op == "dump":
                paths = telemetry.write_dump(
                    cmd[1], prefix=f"{cfg['worker_id']}.")
                reply = ("ok", [str(p) for p in paths.values()])
            else:
                reply = ("error", f"unknown command {op!r}")
        except faults.InjectedCrash:
            # a REAL hard kill, not an exception unwind: the parent sees
            # EOF, the lease table sees an expiry, the checkpoint files
            # see nothing at all
            os.kill(os.getpid(), signal.SIGKILL)
        except BaseException as e:  # noqa: BLE001 — isolate, report, serve on
            reply = ("error", f"{type(e).__name__}: {e}")
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            break


class ProcessMaintenancePool:
    """N sharded, leased backfill workers as spawn *processes* over one
    durable root.  Same calling surface as ``MaintenanceWorkerPool``.

    ``root`` must be a spilled store root (the children reopen it via
    ``SegmentStore.load``); ``objects_root`` the shared ``ObjectStore``
    root holding the compiled engine artifacts.  ``store`` may pass the
    parent's own ``SegmentStore`` object — it is refreshed after every
    cycle so the parent observes the children's installs.

    No ``matcher_cache`` parameter exists by design: compiled matchers
    are jitted closures that cannot cross a process boundary, so the
    cache is strictly per-process (each worker warms its own once per
    target version).  ``scheduler`` degrades gracefully: only its
    *policy* (a plain dataclass) ships to the children — profiler heat
    lives in the parent and cannot steer child-side ordering.
    """

    def __init__(self, root, *, num_workers: int = 2, store=None,
                 objects_root=None, scheduler=None, policy=None,
                 backend: str = "dfa_ref", block_n: int = 256,
                 rows_per_pass: int = None,
                 worker_prefix: str = "maint", lease_ttl: float = 30.0,
                 segment_size: int = 100_000, index_fields: tuple = (),
                 recv_timeout: float = 120.0, respawn: bool = True):
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        if not on_cpu():
            raise RuntimeError(
                "ProcessMaintenancePool runs on the CPU backend only: its "
                "worker processes build matchers and would need the device "
                "this process holds; use MaintenanceWorkerPool (thread "
                "workers)")
        self.root = Path(root)
        self.store = store
        if objects_root is None:
            raise ValueError(
                "ProcessMaintenancePool needs objects_root: worker "
                "processes fetch compiled artifacts from a shared "
                "file-backed ObjectStore, not from parent memory")
        self.objects_root = str(objects_root)
        if policy is None and scheduler is not None:
            policy = scheduler.policy
        self._policy_dict = (dataclasses.asdict(policy)
                             if policy is not None else None)
        self.num_workers = num_workers
        self.recv_timeout = float(recv_timeout)
        self.respawn = respawn
        self.leases = DurableLeaseManager(self.root / CONTROL_DIRNAME,
                                          ttl=lease_ttl)
        self.bus = DurableControlBus(self.root / CONTROL_DIRNAME)
        self._ctx = mp.get_context("spawn")
        self._cfg_base = {
            "root": str(self.root), "objects_root": self.objects_root,
            "backend": backend, "block_n": block_n,
            "rows_per_pass": rows_per_pass, "lease_ttl": float(lease_ttl),
            "segment_size": int(segment_size),
            "index_fields": tuple(index_fields),
            "num_shards": num_workers, "policy": self._policy_dict,
        }
        self._prefix = worker_prefix
        self._workers = [self._spawn(i) for i in range(num_workers)]
        self._deaths_last_cycle = 0

    # -- process lifecycle -------------------------------------------------
    def _spawn(self, index: int) -> dict:
        worker_id = f"{self._prefix}-{index}"
        parent_conn, child_conn = self._ctx.Pipe()
        cfg = {**self._cfg_base, "worker_id": worker_id,
               "shard_index": index}
        proc = self._ctx.Process(target=_worker_main,
                                 args=(cfg, child_conn),
                                 name=worker_id, daemon=True)
        proc.start()
        child_conn.close()
        return {"index": index, "worker_id": worker_id, "proc": proc,
                "conn": parent_conn, "alive": True}

    def _ensure_workers(self) -> None:
        """Respawn any dead worker under its OLD identity: same worker id
        means same consumer group, so the replacement resumes from the
        committed offsets (or re-derives the target from topic history)
        and from the on-disk row-watermark checkpoints."""
        for i, w in enumerate(self._workers):
            if w["alive"] and w["proc"].is_alive():
                continue
            self._mark_dead(w)
            self._workers[i] = self._spawn(w["index"])
            _RESPAWNS.inc()
            telemetry.emit("worker_respawn", plane="maintenance",
                           worker=w["worker_id"])

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
        """Send + receive with a liveness deadline.  Returns the reply or
        None when the worker died (killed mid-command, crashed, or stalled
        past ``recv_timeout`` — stalls are treated as deaths, the
        replacement takes over from durable state)."""
        if not w["alive"]:
            return None
        try:
            w["conn"].send(cmd)
            deadline = time.monotonic() + self.recv_timeout
            while True:
                if w["conn"].poll(0.05):
                    return w["conn"].recv()
                if not w["proc"].is_alive() and not w["conn"].poll(0.05):
                    raise EOFError("worker process died")
                if time.monotonic() > deadline:
                    raise TimeoutError("worker command timed out")
        except (EOFError, BrokenPipeError, ConnectionResetError, OSError,
                TimeoutError):
            self._mark_dead(w)
            self._deaths_last_cycle += 1
            _DEATHS.inc()
            telemetry.emit("worker_death", plane="maintenance",
                           worker=w["worker_id"], command=cmd[0])
            return None

    def close(self) -> None:
        """Stop every child (graceful, then forceful)."""
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

    # -- pool surface (MaintenanceWorkerPool-compatible) -------------------
    @property
    def worker_ids(self) -> tuple:
        """Identities acking on ``MAINTENANCE_ACKS`` — pass to
        ``MatcherUpdater.await_maintenance``.  Stable across respawns."""
        return tuple(w["worker_id"] for w in self._workers)

    def set_target(self, ruleset) -> None:
        """Direct (bus-less) targeting of every worker."""
        self._ensure_workers()
        for w in self._workers:
            self._request(w, ("set_target", ruleset))

    def warm_matchers(self) -> int:
        """Ask every worker to poll its target and precompile its delta
        matchers (``BackfillWorker.warm_matchers``) — benches call this so
        compile cost stays out of the timed lanes, exactly like the thread
        pool's shared-cache warmup."""
        self._ensure_workers()
        total = 0
        for w in self._workers:
            reply = self._request(w, ("warm",))
            if reply is not None and reply[0] == "ok":
                total += int(reply[1])
        return total

    def pending_segments(self) -> list:
        """Union of every shard's pending set.  Returns the PARENT store's
        segment objects when a store was attached, else bare segment ids."""
        self._ensure_workers()
        ids = []
        for w in self._workers:
            reply = self._request(w, ("pending",))
            if reply is not None and reply[0] == "pending":
                ids.extend(reply[1])
        if self.store is None:
            return ids
        self.store.refresh()
        wanted = set(ids)
        return [s for s in self.store.segments if s.segment_id in wanted]

    def run_cycle(self, *, max_segments: int = None) -> BackfillReport:
        """One pool cycle: every live worker refreshes its store view,
        polls its offsets, and backfills its shard — concurrently, in its
        own process.  A worker that dies mid-cycle (SIGKILL, injected
        crash, stall) contributes nothing this cycle and is respawned at
        the start of the next one."""
        self._ensure_workers()
        self._deaths_last_cycle = 0
        with telemetry.span("maintenance/process_pool_cycle",
                            cat="maintenance", workers=self.num_workers):
            for w in self._workers:
                if w["alive"]:
                    try:
                        w["conn"].send(("cycle", max_segments))
                        w["_inflight"] = True
                    except (BrokenPipeError, OSError):
                        self._mark_dead(w)
                        self._deaths_last_cycle += 1
                        _DEATHS.inc()
                        w["_inflight"] = False
                else:
                    w["_inflight"] = False
            total = BackfillReport()
            acked_all = True
            for w in self._workers:
                if not w.get("_inflight"):
                    acked_all = False
                    continue
                reply = self._collect(w)
                if reply is None or reply[0] != "report":
                    acked_all = False
                    continue
                merge_reports(total, reply[1], sequential=False)
                acked_all = acked_all and reply[2]
        total.acked = acked_all and self._deaths_last_cycle == 0
        if self.store is not None:
            self.store.refresh()
        return total

    def _collect(self, w: dict):
        """Receive a cycle reply (same liveness discipline as _request,
        but the command was already sent)."""
        try:
            deadline = time.monotonic() + self.recv_timeout
            while True:
                if w["conn"].poll(0.05):
                    reply = w["conn"].recv()
                    if reply[0] == "error":
                        telemetry.emit("worker_cycle_error",
                                       plane="maintenance",
                                       worker=w["worker_id"],
                                       error=reply[1])
                        return None
                    return reply
                if not w["proc"].is_alive() and not w["conn"].poll(0.05):
                    raise EOFError("worker process died mid-cycle")
                if time.monotonic() > deadline:
                    raise TimeoutError("worker cycle timed out")
        except (EOFError, BrokenPipeError, ConnectionResetError, OSError,
                TimeoutError):
            self._mark_dead(w)
            self._deaths_last_cycle += 1
            _DEATHS.inc()
            telemetry.emit("worker_death", plane="maintenance",
                           worker=w["worker_id"], command="cycle")
            return None

    def run_until_converged(self, *, max_cycles: int = 1000
                            ) -> BackfillReport:
        """Cycle the pool until every shard converged (or no live shard can
        make progress).  A cycle that lost a worker never terminates the
        loop — the replacement must first report its shard's true pending
        count."""
        total = BackfillReport()
        last = None
        for _ in range(max_cycles):
            rep = self.run_cycle()
            merge_reports(total, rep)
            last = rep
            if self._deaths_last_cycle:
                continue    # a dead shard's pending count is unknown
            if rep.messages == 0 and (
                    rep.pending_after == 0
                    or (rep.segments_backfilled == 0
                        and rep.segments_partial == 0)):
                break
        total.acked = bool(last is not None and last.acked)
        return total

    # -- telemetry ---------------------------------------------------------
    def write_dumps(self, directory) -> list:
        """Per-process telemetry dumps: every worker writes
        ``<worker_id>.metrics.prom`` / ``.snapshot.json`` / ``.trace.json``
        into ``directory``.  Pair with ``telemetry.export.merge_dumps`` to
        fold them (plus the parent's own dump) into one snapshot."""
        self._ensure_workers()
        paths = []
        for w in self._workers:
            reply = self._request(w, ("dump", str(directory)))
            if reply is not None and reply[0] == "ok":
                paths.extend(reply[1])
        return paths
