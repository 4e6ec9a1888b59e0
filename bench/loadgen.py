"""Load generator: streams completions from the server over HTTP and
records when each token event arrived.  Runs as its own process, so the
engine's host threads do not share an interpreter lock with it, and
imports neither JAX nor the program.

The plan comes on standard input as one JSON object:

  host, port        the server
  t0                the window's opening, on ``time.monotonic()`` (the
                    same clock in every process of the machine)
  start             closed loop: when the clients start, at or before t0,
                    so that the engine's seats are full when the window
                    opens (default t0)
  seconds           the window's length
  tail_s            how long in-flight streams are read after the close
  loop              "open": each request is sent at t0 + its "due";
                    "closed": ``concurrency`` clients each send the next
                    request as soon as their last one ended, until the
                    window closes
  requests          [{"prompt": ids, "max_tokens": k, "due"?: s}]

Requests are greedy (temperature 0) and streamed.  At the end it
writes one JSON object to standard output: per request the time it was
due and sent, the HTTP status, the engine's request id, each token
event as [time, tokens], the token ids, and whether it finished.  Its
own lateness against the schedule is in "late_s".  Streams still open
when the tail ends are closed, which makes the server abort them.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time

CUT = "cut at the end of the tail"


async def _stream(host, port, req, rec):
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0, "stream": True}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\n"
                     b"Host: bench\r\nContent-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        await writer.drain()
        rec["sent"] = time.monotonic()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        length = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":")[1])
        if rec["status"] != 200:
            rec["error"] = (await reader.readexactly(length)).decode(
                "utf-8", "replace")[:200] if length else ""
            return
        while True:
            line = await reader.readline()
            if not line:
                rec["error"] = "stream ended without [DONE]"
                return
            if not line.startswith(b"data: "):
                continue
            if line.startswith(b"data: [DONE]"):
                rec["done"] = True
                return
            t = time.monotonic()
            ev = json.loads(line[6:])
            if "error" in ev:
                rec["error"] = str(ev["error"])[:200]
                return
            rec["rid"] = ev["id"]
            toks = ev["choices"][0]["token_ids"]
            if toks:
                rec["events"].append([t, len(toks)])
                rec["tokens"].extend(toks)
    finally:
        writer.close()


def _record(i, req, due):
    return {"i": i, "due": due, "sent": None, "status": None, "rid": None,
            "events": [], "tokens": [], "done": False, "error": None,
            "max_tokens": req["max_tokens"],
            "prompt_len": len(req["prompt"])}


async def _run(plan):
    host, port, t0 = plan["host"], plan["port"], plan["t0"]
    close = t0 + plan["seconds"]
    end = close + plan["tail_s"]
    reqs = plan["requests"]
    records, tasks = [], []

    async def one(i, req, due):
        rec = _record(i, req, due)
        records.append(rec)
        try:
            await _stream(host, port, req, rec)
        except asyncio.CancelledError:
            rec["error"] = rec["error"] or CUT
            raise
        except (OSError, ValueError, IndexError,
                asyncio.IncompleteReadError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]

    if plan["loop"] == "open":
        async def at(i, req):
            due = t0 + req["due"]
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            await one(i, req, due)

        tasks = [asyncio.ensure_future(at(i, r)) for i, r in enumerate(reqs)]
    else:
        nxt = iter(enumerate(reqs))

        start = plan.get("start", t0)

        async def client():
            await asyncio.sleep(max(0.0, start - time.monotonic()))
            for i, req in nxt:
                if time.monotonic() >= close:
                    return
                await one(i, req, time.monotonic())

        tasks = [asyncio.ensure_future(client())
                 for _ in range(plan["concurrency"])]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, end - time.monotonic()))
    for t in pending:
        t.cancel()
    for err in await asyncio.gather(*tasks, return_exceptions=True):
        if err is not None and not isinstance(err, asyncio.CancelledError):
            raise err
    return records


def main():
    plan = json.loads(sys.stdin.read())
    records = asyncio.run(_run(plan))
    late = sorted(r["sent"] - r["due"] for r in records
                  if r["sent"] is not None)
    out = {"records": sorted(records, key=lambda r: r["i"]),
           "late_s": {"n": len(late),
                      "p50": late[len(late) // 2] if late else None,
                      "max": late[-1] if late else None}}
    sys.stdout.write(json.dumps(out))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
