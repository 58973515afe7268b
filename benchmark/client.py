#!/usr/bin/env python3
"""The load generator: one process, one thread, no JAX.

The serve driver (which holds the chip) starts this as a child, writes one
JSON plan to its stdin and reads one JSON report from its stdout.  Every
request is `POST /v1/completions` with `stream: true`; each streamed token
is stamped on arrival with CLOCK_MONOTONIC, which parent and child share.
All times in the report are seconds from the window's opening (`t0`).

plan:   {host, port, t0, mode: "open"|"closed", end_s, clients, requests:
         [{id, prompt, max_tokens[, due]}]}
report: {results: [{id, due, sent, status, stamps: [...], done, error}]}

open:   each request is sent at t0 + due, whatever the system does; the run
        ends when every request has answered.
closed: `clients` callers each send their next request from the list when
        the last one completes; at t0 + end_s whatever is still in flight
        is abandoned (its tokens so far are reported, `done` false).
"""
from __future__ import annotations

import asyncio
import json
import sys
import time


async def _one(plan: dict, req: dict, res: dict):
    """Send one request and stamp its streamed tokens into `res`."""
    t0 = plan["t0"]
    res["sent"] = time.monotonic() - t0
    writer = None
    try:
        reader, writer = await asyncio.open_connection(plan["host"],
                                                       plan["port"])
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "temperature": 0.0, "stream": True}).encode()
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                      f"Content-Type: application/json\r\n"
                      f"X-Request-Id: {req['id']}\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      f"Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        res["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass                                   # response headers
        if res["status"] != 200:
            res["error"] = (await reader.read(2048)).decode(
                "utf-8", "replace")[:300]
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue                           # chunk sizes, blank lines
            now = time.monotonic() - t0
            data = line[6:].strip()
            if data == b"[DONE]":
                res["done"] = True
                break
            ev = json.loads(data)
            if "error" in ev:
                res["error"] = json.dumps(ev["error"])[:300]
                continue
            n = len(ev["choices"][0]["token_ids"])
            res["stamps"].extend([now] * n)
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        res["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        if writer is not None:
            writer.close()


def _result(req: dict) -> dict:
    return {"id": req["id"], "due": req.get("due"), "sent": None,
            "status": 0, "stamps": [], "done": False, "error": None}


async def _open(plan: dict) -> list:
    async def timed(req, res):
        delay = plan["t0"] + req["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        await _one(plan, req, res)

    results = [_result(r) for r in plan["requests"]]
    await asyncio.gather(*(timed(q, r)
                           for q, r in zip(plan["requests"], results)))
    return results


async def _closed(plan: dict) -> list:
    results, nxt = [], iter(plan["requests"])

    async def caller():
        for req in nxt:
            res = _result(req)
            results.append(res)
            await _one(plan, req, res)

    tasks = [asyncio.ensure_future(caller()) for _ in range(plan["clients"])]
    await asyncio.wait(
        tasks, timeout=plan["t0"] + plan["end_s"] - time.monotonic())
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return results


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    run = _open if plan["mode"] == "open" else _closed
    results = asyncio.run(run(plan))
    json.dump({"results": results}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
