"""Load generator: the client side of a run, in a process of its own.

    python bench/loadgen.py      (spec as one JSON line on stdin)

Started by the harness; never imports JAX, so its Python runs on its own
interpreter lock and not on the engine thread's.  It reads the spec
(``host``, ``port``, ``mix``, ``seed``, ``vocab``, ``clients``,
``grace``), draws its requests from ``traffic.py``, prints ``ready``,
waits for ``go`` on stdin, then drives ``POST /v1/completions`` over SSE
from ``clients`` clients in a closed loop, each sending its next request
when the last one ends, until ``stop`` arrives on stdin.

Every time is this process's ``time.monotonic()``, the clock the harness
shares.  After ``stop`` nothing new is sent, and a request still
streaming is cut once it has its first token (its served tokens so far
are kept).  Nothing waits past ``grace`` seconds: a request that has not
answered by then has failed.  The last stdout line is one JSON object:
``t0`` and one record per request.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from traffic import Traffic  # noqa: E402


async def _request(host: str, port: int, req, rec: dict) -> None:
    body = json.dumps({"tokens": req.prompt, "max_new_tokens": req.max_new,
                       "temperature": 0.0, "stream": True}).encode()
    rec["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        status = (await reader.readline()).split()
        code = int(status[1]) if len(status) > 1 else 0
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if code != 200:
            rec["status"] = f"http {code}"
            return
        while True:
            line = await reader.readline()
            if not line:
                rec["status"] = "eof"
                return
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            ev = json.loads(line[6:])
            if "token" in ev:
                rec["times"].append(now)
                rec["tokens"].append(ev["token"])
            elif ev.get("done"):
                rec["status"] = "ok"
                return
            else:
                rec["status"] = "error"
                return
    finally:
        writer.close()


async def _drive(spec: dict, traffic: Traffic) -> list[dict]:
    host, port = spec["host"], spec["port"]
    records: list[dict] = []
    tasks: list[asyncio.Task] = []
    nxt = iter(range(1 << 40))

    def start() -> asyncio.Task:
        i = next(nxt)
        rec = {"i": i, "sent": None, "status": "pending", "times": [],
               "tokens": []}
        records.append(rec)
        t = asyncio.ensure_future(_request(host, port, traffic.request(i),
                                           rec))
        t.rec = rec
        tasks.append(t)
        return t

    async def client():
        while True:
            t = start()
            try:    # shielded: stopping the client leaves it running
                await asyncio.shield(t)
            except asyncio.CancelledError:
                raise
            except Exception as e:   # a failed request; the next one
                t.rec["status"] = f"error {type(e).__name__}: {e}"[:200]
                await asyncio.sleep(0.1)

    clients = [asyncio.ensure_future(client())
               for _ in range(spec["clients"])]
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    end = time.monotonic()
    # stop the clients from sending; their requests run on below
    for c in clients:
        c.cancel()
    await asyncio.gather(*clients, return_exceptions=True)
    if line.strip() != "stop":
        raise RuntimeError(f"expected 'stop' on stdin, got {line!r}")

    # streams are cut at their first token; nothing waits past the grace
    deadline = end + spec["grace"]
    while True:
        live = [t for t in tasks if not t.done()]
        for t in live:
            if t.rec["times"]:
                t.cancel()
                t.rec["status"] = "cut"
        live = [t for t in live if not t.cancelled()]
        if not live or time.monotonic() > deadline:
            break
        await asyncio.sleep(0.01)
    for t in tasks:
        if not t.done():
            t.cancel()
            if t.rec["status"] == "pending":
                t.rec["status"] = "timeout"
    results = await asyncio.gather(*tasks, return_exceptions=True)
    for t, r in zip(tasks, results):
        if (isinstance(r, BaseException)
                and not isinstance(r, asyncio.CancelledError)
                and t.rec["status"] == "pending"):
            t.rec["status"] = f"error {type(r).__name__}: {r}"[:200]
    return records


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    traffic = Traffic(spec["mix"], spec["seed"], spec["vocab"])
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    t0 = time.monotonic()
    records = asyncio.run(_drive(spec, traffic))
    for r in records:        # times as offsets from t0, to keep the line short
        r["times"] = [round(x - t0, 6) for x in r["times"]]
        if r["sent"] is not None:
            r["sent"] = round(r["sent"] - t0, 6)
    print(json.dumps({"t0": t0, "records": records}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
