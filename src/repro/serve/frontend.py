"""Serving front end — the query plane's ingress (docs/SERVING.md).

The paper's setting is an observability platform answering expensive
filtering queries for *many concurrent external clients*; until now every
query in this repo was an in-process Python method call.  This module is
the missing serving plane: a threaded socket server over
:class:`repro.core.query.engine.QueryEngine` (count / ids / copy plus
standing-query register/refresh routes) and an optional ingest sink,
speaking a small length-prefixed JSON wire protocol, with the full
overload ladder in front of the engine:

  1. **admission control** — a per-client token bucket
     (:class:`TokenBucket` via :class:`AdmissionController`); a client
     above its rate gets an explicit ``429``-style rejection *before* any
     engine work happens;
  2. **bounded backpressure queue** — at most ``max_inflight`` requests
     execute concurrently and at most ``max_queue`` wait for a slot; a
     request arriving past the queue bound is shed with ``503``
     (``queue_full``) instead of growing an unbounded backlog;
  3. **deadline shedding** — a queued request whose deadline expires
     before a slot frees is shed with ``504`` (``deadline``): the server
     never spends engine time on an answer the client stopped waiting for.

Rejected and shed requests are CHEAP (no plan, no dispatch) — that is the
whole point: under overload the admitted subset keeps its tail latency
while the excess is refused, not queued (the `serve_overload` lane in
``benchmarks/bench_serve.py`` proves the p99 bound).

The same port speaks just enough HTTP for operators: ``GET /metrics``
(the long-promised Prometheus scrape over
``telemetry.prometheus_text()``) and ``GET /healthz``.  Protocol sniffing
is unambiguous: a length prefix that decodes to an HTTP verb would claim
a >1 GiB frame, far above ``max_frame_bytes``.

Naming note — the ``repro.serve`` package hosts TWO planes: this module
(the *query/ingest* front end) and the pre-existing *model* serving plane
(``engine.py`` / ``serve_step.py`` / ``kv_cache.py``, batched LM
prefill+decode).  See ``repro/serve/__init__.py`` for the split.

Wire protocol (see docs/SERVING.md for the full reference)::

    frame    := u32_be length | json body (utf-8), length <= max_frame_bytes
    request  := {"route": str, "id": any, "client": str, "deadline_ms": num,
                 ...route params}
    response := {"id": any, "status": int, ...}   # one frame per request

Routes: ``query`` (modes ``count``/``ids``/``copy``), ``standing.register``,
``standing.refresh``, ``ingest``, ``ping``.  Statuses mirror HTTP: 200 ok,
400 bad request, 404 unknown route, 429 admission-rejected, 500 handler
fault, 503 queue full, 504 deadline shed.

Fault sites ``serve.accept`` (accept loop: an injected error drops that
connection, the listener survives) and ``serve.handle`` (per-request: an
injected error becomes a well-formed 500 response; an
:class:`~repro.core.faults.InjectedCrash` kills the handler thread but
``finally`` blocks still restore the inflight gauge) thread the chaos
plane through the ingress — docs/ROBUSTNESS.md has the blast-radius rows.
"""
from __future__ import annotations

import hashlib
import json
import socket
import struct
import threading
import time

import numpy as np

from repro.core import faults, telemetry
from repro.core.query.engine import Query, QueryEngine  # noqa: F401
from repro.core.records import RecordBatch, encode_texts

MAX_FRAME_BYTES = 1 << 20           # 1 MiB: far below any HTTP-verb prefix
_HTTP_VERBS = (b"GET ", b"HEAD", b"POST", b"PUT ", b"DELE", b"OPTI")

ROUTES = ("query", "standing.register", "standing.refresh", "ingest", "ping")

# -- telemetry (handles cached at import; label sets created lazily) ----------
_REQS = {}          # route -> counter
_LAT = {}           # route -> histogram
_REJ = {}           # (route, reason) -> counter
_SHED = {}          # (route, reason) -> counter
_INFLIGHT = telemetry.gauge(
    "fluxsieve_serve_inflight",
    help="Requests currently executing against the engine.")
_QUEUED = telemetry.gauge(
    "fluxsieve_serve_queued",
    help="Admitted requests waiting for an inflight slot.")
_CONNS = telemetry.gauge(
    "fluxsieve_serve_connections",
    help="Open client connections.")
_ERRORS = telemetry.counter(
    "fluxsieve_serve_errors_total",
    help="Requests answered with a 500 (handler fault absorbed).")


def _req_counter(route: str):
    c = _REQS.get(route)
    if c is None:
        c = _REQS[route] = telemetry.counter(
            "fluxsieve_serve_requests_total", labels={"route": route},
            help="Requests received, by route (any outcome).")
    return c


def _latency_hist(route: str):
    h = _LAT.get(route)
    if h is None:
        h = _LAT[route] = telemetry.histogram(
            "fluxsieve_serve_latency_seconds", labels={"route": route},
            help="Served-request latency (admitted requests only).")
    return h


def _rejection(route: str, reason: str):
    key = (route, reason)
    c = _REJ.get(key)
    if c is None:
        c = _REJ[key] = telemetry.counter(
            "fluxsieve_serve_rejections_total",
            labels={"route": route, "reason": reason},
            help="Requests refused before engine work "
                 "(admission / protocol errors).")
    return c


def _shed_counter(route: str, reason: str):
    key = (route, reason)
    c = _SHED.get(key)
    if c is None:
        c = _SHED[key] = telemetry.counter(
            "fluxsieve_serve_shed_total",
            labels={"route": route, "reason": reason},
            help="Admitted requests shed by backpressure "
                 "(queue_full / deadline).")
    return c


# -- admission control --------------------------------------------------------
class TokenBucket:
    """Classic token bucket with an injectable clock (property tests drive
    it with a deterministic clock, no sleeps).

    Starts full at ``burst`` tokens; refills continuously at ``rate``
    tokens/second up to ``burst``; ``try_acquire`` consumes one.  The
    admission invariant (asserted in tests/test_serve_admission.py): over
    ANY window of ``T`` seconds at most ``burst + rate*T`` acquisitions
    succeed, for any arrival pattern."""

    __slots__ = ("rate", "burst", "tokens", "last", "clock")

    def __init__(self, rate: float, burst: float, clock=time.monotonic):
        self.rate = float(rate)
        self.burst = float(burst)
        self.tokens = float(burst)
        self.last = clock()
        self.clock = clock

    def _refill(self, now: float) -> None:
        if now > self.last:
            self.tokens = min(self.burst,
                              self.tokens + (now - self.last) * self.rate)
        self.last = now

    def try_acquire(self) -> bool:
        now = self.clock()
        self._refill(now)
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False

    def full(self) -> bool:
        """Would a refill at the current clock restore full burst?  A full
        bucket is indistinguishable from a fresh one — safe to evict."""
        now = self.clock()
        return (self.tokens + max(0.0, now - self.last) * self.rate
                >= self.burst)


class AdmissionController:
    """Independent per-client token buckets behind one lock.

    One flooding client drains only ITS bucket — another client's admitted
    share is untouched (the independence property test).  Per-client state
    is one bucket (~5 floats); at high client cardinality, buckets that
    have refilled to full are evicted once the table exceeds
    ``max_clients`` — a full bucket is semantically identical to a fresh
    one, so eviction never changes an admission decision (the 100k-client
    bench lane rides this)."""

    def __init__(self, rate_per_client: float, burst: float = None,
                 clock=time.monotonic, max_clients: int = 65536):
        self.rate = float(rate_per_client)
        self.burst = float(burst if burst is not None
                           else max(1.0, rate_per_client))
        self.clock = clock
        self.max_clients = int(max_clients)
        self._buckets = {}
        self._lock = threading.Lock()

    def admit(self, client_id: str) -> bool:
        with self._lock:
            b = self._buckets.get(client_id)
            if b is None:
                if len(self._buckets) >= self.max_clients:
                    self._evict_full_locked()
                b = self._buckets[client_id] = TokenBucket(
                    self.rate, self.burst, self.clock)
            return b.try_acquire()

    def _evict_full_locked(self) -> None:
        for cid in [c for c, b in self._buckets.items() if b.full()]:
            del self._buckets[cid]

    @property
    def num_clients(self) -> int:
        with self._lock:
            return len(self._buckets)


# -- framing ------------------------------------------------------------------
def send_frame(sock: socket.socket, obj: dict) -> None:
    body = json.dumps(obj).encode()
    sock.sendall(struct.pack(">I", len(body)) + body)


def recv_exact(sock: socket.socket, n: int) -> bytes:
    """n bytes or None on EOF/reset mid-read (caller counts a disconnect)."""
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except (ConnectionError, OSError):
            return None
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket, max_bytes: int = MAX_FRAME_BYTES):
    """-> parsed dict, or None on clean EOF.  Raises ProtocolError on a
    malformed frame (oversized/zero length, truncated body, bad JSON)."""
    head = recv_exact(sock, 4)
    if head is None:
        return None
    n = struct.unpack(">I", head)[0]
    if n == 0 or n > max_bytes:
        raise ProtocolError(f"bad frame length {n}", recoverable=False)
    body = recv_exact(sock, n)
    if body is None:
        raise ProtocolError("truncated frame body", recoverable=False)
    try:
        obj = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        # the frame boundary was intact, so the stream is still framed:
        # the connection survives a bad payload
        raise ProtocolError(f"invalid JSON: {e}", recoverable=True) from e
    if not isinstance(obj, dict):
        raise ProtocolError("request must be a JSON object", recoverable=True)
    return obj


class ProtocolError(Exception):
    """A malformed frame.  ``recoverable`` means the stream's framing is
    still trustworthy (respond 400 and keep the connection); otherwise the
    server responds and closes."""

    def __init__(self, msg: str, *, recoverable: bool):
        super().__init__(msg)
        self.recoverable = recoverable


def _digest(arr: np.ndarray) -> dict:
    """Bit-exact column witness: the oracle check in bench/tests compares
    these against a direct in-process QueryEngine call."""
    a = np.ascontiguousarray(arr)
    return {"sha256": hashlib.sha256(a.tobytes()).hexdigest(),
            "dtype": str(a.dtype), "shape": list(a.shape)}


def result_payload(res, mode: str) -> dict:
    """Serialize a QueryResult for the wire.  ``count`` ships the integer;
    ``ids`` ships the matched rows' timestamps (sorted — a stable row
    identity across transports); ``copy`` ships per-column bit-exact
    digests plus the count (materialized payloads stay host-side)."""
    out = {"count": int(res.count), "path": res.path,
           "partial": bool(res.partial), "coverage": float(res.coverage),
           "segments_failed": int(res.segments_failed)}
    if mode == "ids":
        ts = (np.sort(np.asarray(res.records.columns["timestamp"]))
              if res.records is not None and len(res.records) else [])
        out["ids"] = [int(t) for t in ts]
    elif mode == "copy":
        cols = {}
        if res.records is not None and len(res.records):
            order = np.argsort(np.asarray(res.records.columns["timestamp"]),
                               kind="stable")
            for name, arr in sorted(res.records.columns.items()):
                cols[name] = _digest(np.asarray(arr)[order])
        out["columns"] = cols
    return out


# -- the front end ------------------------------------------------------------
class FrontEnd:
    """Threaded serving front end.  ``start()`` binds and returns; the
    acceptor and per-connection handlers run as daemon threads;
    ``close()`` (or ``with FrontEnd(...) as fe:``) shuts everything down.

    ``engine`` answers query/standing routes; ``ingest`` is an optional
    callable ``RecordBatch -> int`` (rows appended) behind the ``ingest``
    route — ``launch/serve.py`` wires the StreamProcessor + store there.
    ``clock`` feeds the admission buckets (tests inject a fake)."""

    def __init__(self, engine: QueryEngine, *, host: str = "127.0.0.1",
                 port: int = 0, max_inflight: int = 8, max_queue: int = 32,
                 rate_per_client: float = 100.0, burst: float = None,
                 default_deadline_s: float = 5.0, ingest=None,
                 max_frame_bytes: int = MAX_FRAME_BYTES,
                 max_clients: int = 65536, clock=time.monotonic):
        self.engine = engine
        self.ingest = ingest
        self.host, self.port = host, port
        self.max_inflight = int(max_inflight)
        self.max_queue = int(max_queue)
        self.default_deadline_s = float(default_deadline_s)
        self.max_frame_bytes = int(max_frame_bytes)
        self.admission = AdmissionController(
            rate_per_client, burst, clock=clock, max_clients=max_clients)
        self._inflight_sem = threading.Semaphore(self.max_inflight)
        self._queue_lock = threading.Lock()
        self._waiting = 0
        self._sock = None
        self._accept_thread = None
        self._conn_threads = set()
        self._threads_lock = threading.Lock()
        self._closed = threading.Event()
        self._started = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "FrontEnd":
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((self.host, self.port))
        self._sock.listen(128)
        self.port = self._sock.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="serve-accept", daemon=True)
        self._accept_thread.start()
        self._started = True
        telemetry.emit("serve_started", plane="serve", host=self.host,
                       port=self.port, max_inflight=self.max_inflight,
                       max_queue=self.max_queue)
        return self

    @property
    def address(self) -> tuple:
        return (self.host, self.port)

    def close(self) -> None:
        if self._closed.is_set():
            return
        self._closed.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError as e:
                telemetry.suppressed("serve.close", e)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2.0)
        with self._threads_lock:
            threads = list(self._conn_threads)
        for t in threads:
            t.join(timeout=2.0)

    def __enter__(self) -> "FrontEnd":
        return self if self._started else self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- accept loop --------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, peer = self._sock.accept()
            except OSError:
                return              # socket closed by close()
            try:
                faults.fire("serve.accept", peer=peer[0])
            except faults.InjectedFault as e:
                # blast radius: THIS connection; the listener survives
                telemetry.suppressed("serve.accept", e)
                conn.close()
                continue
            t = threading.Thread(target=self._serve_conn, args=(conn, peer),
                                 name=f"serve-conn-{peer[1]}", daemon=True)
            with self._threads_lock:
                self._conn_threads.add(t)
            t.start()

    def _serve_conn(self, conn: socket.socket, peer) -> None:
        _CONNS.inc()
        try:
            conn.settimeout(30.0)
            head = recv_exact(conn, 4)
            if head is None:
                return
            if head in _HTTP_VERBS:
                self._serve_http(conn, head)
                return
            self._serve_frames(conn, head, peer)
        finally:
            _CONNS.dec()
            try:
                conn.close()
            except OSError as e:
                telemetry.suppressed("serve.close", e)
            with self._threads_lock:
                self._conn_threads.discard(threading.current_thread())

    # -- framed protocol ----------------------------------------------------
    def _serve_frames(self, conn, first_head: bytes, peer) -> None:
        head = first_head
        default_client = f"{peer[0]}:{peer[1]}"
        while not self._closed.is_set():
            try:
                req = self._read_request(conn, head)
            except ProtocolError as e:
                _rejection("unknown", "bad_frame").inc()
                try:
                    send_frame(conn, {"status": 400, "error": str(e)})
                except OSError as oe:
                    telemetry.suppressed("serve.respond", oe)
                if e.recoverable:
                    head = None
                    continue
                return
            if req is None:         # clean EOF (or mid-read disconnect)
                return
            head = None
            try:
                resp = self._handle(req, default_client)
            except faults.InjectedCrash:
                raise               # simulated kill: never absorbed
            except Exception as e:  # noqa: BLE001 — one request's blast radius
                _ERRORS.inc()
                resp = {"status": 500, "error": f"{type(e).__name__}: {e}"}
            resp["id"] = req.get("id")
            try:
                with telemetry.span("serve/respond", cat="serve",
                                    request=resp["id"]):
                    send_frame(conn, resp)
            except OSError as e:    # client went away mid-response
                telemetry.suppressed("serve.respond", e)
                return

    def _read_request(self, conn, head):
        """One request frame; ``head`` carries 4 pre-read bytes (protocol
        sniffing) for the first frame on a connection."""
        if head is None:
            return recv_frame(conn, self.max_frame_bytes)
        n = struct.unpack(">I", head)[0]
        if n == 0 or n > self.max_frame_bytes:
            raise ProtocolError(f"bad frame length {n}", recoverable=False)
        body = recv_exact(conn, n)
        if body is None:
            raise ProtocolError("truncated frame body", recoverable=False)
        try:
            obj = json.loads(body.decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise ProtocolError(f"invalid JSON: {e}", recoverable=True) from e
        if not isinstance(obj, dict):
            raise ProtocolError("request must be a JSON object",
                                recoverable=True)
        return obj

    # -- request ladder: admit -> queue -> execute ---------------------------
    def _handle(self, req: dict, default_client: str) -> dict:
        route = req.get("route")
        if not isinstance(route, str) or route not in ROUTES:
            _req_counter("unknown").inc()
            _rejection("unknown", "bad_route").inc()
            return {"status": 404, "error": f"unknown route {route!r}"}
        _req_counter(route).inc()
        client = str(req.get("client") or default_client)
        if route == "ping":         # liveness probe: skips the ladder
            return {"status": 200, "pong": True}
        if not self.admission.admit(client):
            _rejection(route, "admission").inc()
            return {"status": 429, "error": "rate limit exceeded",
                    "reason": "admission"}
        rid = req.get("id")
        deadline_s = float(req.get("deadline_ms",
                                   self.default_deadline_s * 1e3)) / 1e3
        deadline = time.monotonic() + deadline_s
        with telemetry.span("serve/queue_wait", cat="serve",
                            request=rid) as wait:
            with self._queue_lock:
                if self._waiting >= self.max_queue:
                    _shed_counter(route, "queue_full").inc()
                    wait.set(outcome="queue_full")
                    return {"status": 503, "error": "server overloaded",
                            "reason": "queue_full"}
                self._waiting += 1
                _QUEUED.inc()
            try:
                got = self._inflight_sem.acquire(
                    timeout=max(0.0, deadline - time.monotonic()))
            finally:
                with self._queue_lock:
                    self._waiting -= 1
                    _QUEUED.dec()
            wait.set(outcome="admitted" if got else "deadline")
        if not got:
            _shed_counter(route, "deadline").inc()
            return {"status": 504, "error": "deadline exceeded in queue",
                    "reason": "deadline"}
        _INFLIGHT.inc()
        t0 = time.perf_counter()
        try:
            with telemetry.span("serve/request", cat="serve", route=route,
                                client=client, request=rid):
                faults.fire("serve.handle", route=route, client=client)
                resp = self._dispatch(route, req)
            _latency_hist(route).observe(time.perf_counter() - t0)
            return resp
        finally:
            # BaseException-safe: even an InjectedCrash in a handler thread
            # restores the gauge and frees the slot (no leaked capacity)
            _INFLIGHT.dec()
            self._inflight_sem.release()

    # -- routes -------------------------------------------------------------
    def _dispatch(self, route: str, req: dict) -> dict:
        if route == "query":
            return self._route_query(req)
        if route == "standing.register":
            return self._route_standing_register(req)
        if route == "standing.refresh":
            return self._route_standing_refresh(req)
        if route == "ingest":
            return self._route_ingest(req)
        raise AssertionError(route)

    @staticmethod
    def _parse_query(req: dict, *, engine_mode: str = None) -> Query:
        terms = req.get("terms")
        if (not isinstance(terms, list) or not terms
                or not all(isinstance(t, (list, tuple)) and len(t) == 2
                           and all(isinstance(x, str) for x in t)
                           for t in terms)):
            raise ValueError("terms must be a non-empty list of "
                             "[field, term] string pairs")
        return Query(terms=tuple((f, t) for f, t in terms),
                     mode=engine_mode or "count",
                     name=str(req.get("name", "")))

    def _route_query(self, req: dict) -> dict:
        mode = req.get("mode", "count")
        if mode not in ("count", "ids", "copy"):
            return {"status": 400, "error": f"unknown mode {mode!r}"}
        path = req.get("path", "auto")
        try:
            # ids/copy both need materialized rows: engine mode "copy"
            q = self._parse_query(
                req, engine_mode="count" if mode == "count" else "copy")
            res = self.engine.execute(q, path=path)
        except ValueError as e:
            return {"status": 400, "error": str(e)}
        out = result_payload(res, mode)
        out["status"] = 200
        return out

    def _route_standing_register(self, req: dict) -> dict:
        mode = req.get("mode", "count")
        if mode not in ("count", "ids", "copy"):
            return {"status": 400, "error": f"unknown mode {mode!r}"}
        try:
            q = self._parse_query(
                req, engine_mode="count" if mode == "count" else "copy")
            sq = self.engine.register_standing(
                q, name=req.get("name") or None)
        except ValueError as e:
            return {"status": 400, "error": str(e)}
        return {"status": 200, "name": sq.name}

    def _route_standing_refresh(self, req: dict) -> dict:
        name = req.get("name")
        registry = self.engine._standing
        sq = registry.get(str(name)) if registry is not None else None
        if sq is None:
            return {"status": 400,
                    "error": f"no standing query named {name!r}"}
        res = sq.refresh()
        # representation follows the registered engine mode: a count-mode
        # standing view has no rows to ship, copy-mode views can answer in
        # whatever representation the client asked for
        mode = ("count" if sq.query.mode == "count"
                else req.get("mode", "copy"))
        out = result_payload(res, mode)
        out.update(status=200, name=sq.name)
        return out

    def _route_ingest(self, req: dict) -> dict:
        if self.ingest is None:
            return {"status": 400, "error": "no ingest sink configured"}
        records = req.get("records")
        if not isinstance(records, list) or not records:
            return {"status": 400,
                    "error": "records must be a non-empty list of objects"}
        try:
            batch = self._records_to_batch(records)
        except (TypeError, ValueError, KeyError) as e:
            return {"status": 400, "error": f"bad records: {e}"}
        appended = self.ingest(batch)
        return {"status": 200, "appended": int(appended)}

    @staticmethod
    def _records_to_batch(records: list) -> RecordBatch:
        """JSON rows -> RecordBatch: int fields ``timestamp``/``status``,
        every other string field becomes an encoded text column.  All rows
        must agree on the text field set (one batch, one schema)."""
        fields = sorted(k for k, v in records[0].items()
                        if isinstance(v, str))
        if not fields:
            raise ValueError("rows need at least one string field")
        cols = {
            "timestamp": np.asarray(
                [int(r.get("timestamp", i)) for i, r in enumerate(records)],
                np.int64),
            "status": np.asarray([int(r.get("status", 0)) for r in records],
                                 np.int32),
        }
        for f in fields:
            cols[f] = encode_texts([str(r[f]) for r in records])
        return RecordBatch(cols)

    # -- minimal HTTP (operators + scrapers) --------------------------------
    def _serve_http(self, conn, head: bytes) -> None:
        data = bytearray(head)
        while b"\r\n\r\n" not in data and len(data) < 8192:
            chunk = conn.recv(4096)
            if not chunk:
                return
            data += chunk
        line = bytes(data).split(b"\r\n", 1)[0].decode("latin-1")
        parts = line.split()
        target = parts[1] if len(parts) >= 2 else "/"
        if target == "/metrics":
            _req_counter("metrics").inc()
            body = telemetry.prometheus_text().encode()
            ctype = "text/plain; version=0.0.4"
            status = "200 OK"
        elif target == "/healthz":
            _req_counter("healthz").inc()
            body = json.dumps({
                "status": "ok",
                "inflight": _INFLIGHT.value,
                "queued": self._waiting,
                "connections": _CONNS.value,
                "segments": len(self.engine.store.segments),
                "clients": self.admission.num_clients,
            }).encode()
            ctype = "application/json"
            status = "200 OK"
        else:
            _rejection("unknown", "bad_route").inc()
            body, ctype, status = b"not found\n", "text/plain", "404 Not Found"
        try:
            conn.sendall(
                f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
                f"Content-Length: {len(body)}\r\n"
                f"Connection: close\r\n\r\n".encode() + body)
        except OSError as e:
            telemetry.suppressed("serve.respond", e)


# -- client -------------------------------------------------------------------
class ServeClient:
    """Minimal blocking client for the framed protocol (tests, benches,
    the CI smoke driver).  One socket, sequential request/response."""

    def __init__(self, host: str, port: int, *, client_id: str = None,
                 timeout: float = 10.0):
        self.client_id = client_id
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._seq = 0

    def request(self, route: str, **params) -> dict:
        self._seq += 1
        req = {"route": route, "id": self._seq, **params}
        if self.client_id is not None and "client" not in params:
            req["client"] = self.client_id
        send_frame(self._sock, req)
        resp = recv_frame(self._sock)
        if resp is None:
            raise ConnectionError("server closed the connection")
        return resp

    def query(self, terms, *, mode: str = "count", **params) -> dict:
        return self.request("query", terms=[list(t) for t in terms],
                            mode=mode, **params)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self) -> "ServeClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def http_get(host: str, port: int, path: str, *,
             timeout: float = 10.0) -> tuple:
    """Plain-socket HTTP GET -> (status_code, body_bytes).  Used by tests
    and the CI smoke step for /metrics and /healthz (no client library)."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                  f"Connection: close\r\n\r\n".encode())
        data = bytearray()
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
    head, _, body = bytes(data).partition(b"\r\n\r\n")
    status = int(head.split(b"\r\n", 1)[0].split()[1])
    return status, body
