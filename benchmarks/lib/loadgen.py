#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax.

Speaks HTTP to the gateway as users do (``POST /v1/generate`` with
``stream: true``, server-sent events back) from one asyncio thread.
Reads one JSON job from stdin:

    {"host", "port", "start_at" (time.monotonic() of the lead-in's
     start), "stop_at" (no new request is sent after it; closed-loop
     streams still open are dropped then), "deadline" (give up),
     "requests": [{"id", "client", "due_s", "prompt", "max_new_tokens",
                   "measured"}, ...]}

and writes one JSON object to stdout: ``{"records": [...]}``, one record
per request sent, with the instants (``time.monotonic()``, the same
clock as the parent's on one machine) at which it was due, was sent,
and at which each token event arrived.

Open loop (``client`` null): each request is sent at ``start_at +
due_s`` whatever the server does, and times from the instant it was
due. Closed loop: each client sends its next request the moment the
last one ended; its requests are due when the last one ended.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Any, Dict, List


async def one_request(job: Dict[str, Any], req: Dict[str, Any],
                      due_t: float, records: List[Dict[str, Any]]) -> None:
    """Sends one request and fills its record in place, so that a
    stream dropped when the window closes keeps the tokens it got."""
    record: Dict[str, Any] = {
        "id": req["id"], "client": req["client"],
        "measured": req["measured"], "prompt_tokens": len(req["prompt"]),
        "max_new_tokens": req["max_new_tokens"], "due_t": due_t,
        "send_t": None, "token_times": [], "token_counts": [],
        "tokens": 0, "outcome": None, "end_t": None, "error": None,
    }
    records.append(record)
    body = json.dumps({"prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "stream": True}).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: {job['host']}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(
            job["host"], job["port"])
        record["send_t"] = time.monotonic()
        writer.write(head + body)
        await writer.drain()
        status = await reader.readline()
        record["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if record["status"] != 200:
            payload = await reader.read()
            record["outcome"] = "http_error"
            record["error"] = payload[-200:].decode("latin-1")
            return
        event = None
        while True:
            line = await reader.readline()
            if not line:
                break
            now = time.monotonic()
            line = line.strip()
            if line.startswith(b"event:"):
                event = line[6:].strip()
            elif line.startswith(b"data:"):
                data = json.loads(line[5:])
                if event == b"token":
                    record["token_times"].append(now)
                    record["token_counts"].append(len(data["token_ids"]))
                    record["tokens"] += len(data["token_ids"])
                elif event == b"done":
                    record["outcome"] = data.get("outcome")
                    record["finish_reason"] = data.get("finish_reason")
                    usage = data.get("usage") or {}
                    record["completion_tokens"] = usage.get(
                        "completion_tokens")
                    break
        if record["outcome"] is None:
            record["outcome"] = "stream_closed"
    except asyncio.CancelledError:
        record["outcome"] = "dropped_at_stop"
        raise
    except Exception as exc:  # a refused or reset connection is a result
        record["outcome"] = "client_error"
        record["error"] = repr(exc)[:200]
    finally:
        record["end_t"] = time.monotonic()
        if writer is not None:
            writer.close()


async def sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def open_loop(job: Dict[str, Any], records: List[Dict[str, Any]]):
    async def fire(req):
        due_t = job["start_at"] + req["due_s"]
        await sleep_until(due_t)
        await one_request(job, req, due_t, records)

    await asyncio.gather(*(fire(r) for r in job["requests"]))


async def closed_loop(job: Dict[str, Any], records: List[Dict[str, Any]]):
    by_client: Dict[int, List[Dict[str, Any]]] = {}
    for req in job["requests"]:
        by_client.setdefault(req["client"], []).append(req)

    async def client(reqs):
        await sleep_until(job["start_at"])
        for req in reqs:
            if time.monotonic() >= job["stop_at"]:
                return
            try:
                await asyncio.wait_for(
                    one_request(job, req, time.monotonic(), records),
                    timeout=max(0.0, job["stop_at"] - time.monotonic()))
            except asyncio.TimeoutError:
                return  # still streaming when the window closed

    await asyncio.gather(*(client(r) for r in by_client.values()))


async def run(job: Dict[str, Any]) -> Dict[str, Any]:
    records: List[Dict[str, Any]] = []
    closed = any(r["client"] is not None for r in job["requests"])
    work = closed_loop(job, records) if closed else open_loop(job, records)
    timed_out = False
    try:
        await asyncio.wait_for(
            work, timeout=max(0.0, job["deadline"] - time.monotonic()))
    except asyncio.TimeoutError:
        timed_out = True
    return {"records": records, "timed_out": timed_out,
            "sent": len(records)}


def main() -> int:
    job = json.load(sys.stdin)
    result = asyncio.run(run(job))
    json.dump(result, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
