"""Open-loop load generator: sends each request of a schedule over the
front end's framed wire protocol at its due time, and records when it was
due, sent and answered.  It runs as a process of its own and imports no
JAX, so it never competes for the chip and never waits on the server's
interpreter.

    python -m chipbench.loadgen <plan.json> <out.json>

The plan holds ``host``, ``port``, ``lead_s`` (time to open the
connections), ``pool`` (connections opened up front), ``timeout_s``
(client-side wait per request) and ``requests``: [offset s, client id,
name, terms, mode].  On stdout it prints ``ready <t0>``, t0 on the
system's monotonic clock, once its connections are open; the out file
gets one row per request: [due, sent, done, status, count, ids], times on
that clock, ``status`` an HTTP-like code or ``timeout``/``lost``/
``conn_error``.
"""
from __future__ import annotations

import asyncio
import json
import struct
import sys
import time


async def _request(pool, plan, row, req, t_due, out):
    while True:
        try:
            reader, writer = pool.get_nowait()
            break
        except asyncio.QueueEmpty:
            try:
                reader, writer = await asyncio.open_connection(
                    plan["host"], plan["port"])
                out["opened"] += 1
                break
            except OSError:
                row[3] = "conn_error"
                return
    body = json.dumps(req).encode()
    row[1] = time.monotonic()
    try:
        writer.write(struct.pack(">I", len(body)) + body)
        head = await asyncio.wait_for(reader.readexactly(4),
                                      plan["timeout_s"])
        n = struct.unpack(">I", head)[0]
        resp = json.loads(await asyncio.wait_for(reader.readexactly(n),
                                                 plan["timeout_s"]))
    except asyncio.TimeoutError:
        row[2], row[3] = time.monotonic(), "timeout"
        writer.close()
        return
    except (OSError, asyncio.IncompleteReadError):
        row[2], row[3] = time.monotonic(), "conn_error"
        writer.close()
        return
    row[2] = time.monotonic()
    row[3] = resp.get("status")
    row[4] = resp.get("count")
    row[5] = resp.get("ids")
    pool.put_nowait((reader, writer))


async def _lag(out: dict, t0: float) -> None:
    """Record each wake-up of the loop more than 50 ms late."""
    while True:
        t = time.monotonic()
        await asyncio.sleep(0.005)
        late = time.monotonic() - t - 0.005
        if late > 0.05:
            out["stalls"].append((round(t - t0, 3), round(late, 3)))


async def _main(plan: dict) -> dict:
    pool = asyncio.Queue()
    for _ in range(plan["pool"]):
        pool.put_nowait(await asyncio.open_connection(plan["host"],
                                                      plan["port"]))
    t0 = time.monotonic() + plan["lead_s"]
    print(f"ready {t0!r}", flush=True)
    out = {"t0": t0, "opened": 0, "rows": [], "stalls": []}
    lag = asyncio.create_task(_lag(out, t0))
    tasks = []
    for i, (off, client, name, terms, mode) in enumerate(plan["requests"]):
        row = [t0 + off, None, None, "lost", None, None]
        out["rows"].append(row)
        delay = t0 + off - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        req = {"route": "query", "id": i, "client": client, "name": name,
               "terms": terms, "mode": mode}
        tasks.append(asyncio.create_task(
            _request(pool, plan, row, req, t0 + off, out)))
    if tasks:
        await asyncio.wait(tasks, timeout=plan["timeout_s"] + 5.0)
    for t in tasks + [lag]:
        t.cancel()
    while not pool.empty():
        pool.get_nowait()[1].close()
    return out


def main(argv) -> int:
    with open(argv[0]) as f:
        plan = json.load(f)
    out = asyncio.run(_main(plan))
    out["cpu_s"] = time.process_time()
    out["modules_jax"] = sorted(m for m in sys.modules
                                if m == "jax" or m.startswith("jax."))
    with open(argv[1], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
