"""Load generator: a child process that never imports JAX.

    python bench/loadgen.py  < plan.json  > records.json

Reads one JSON plan on standard input, drives the server's
``POST /v1/generate`` SSE endpoint on 127.0.0.1 and writes one JSON object
of per-request records on standard output. Every time is
``time.monotonic()``, the clock the server reads on the same host, so the
parent can put these records beside its own.

The loop is open: every request is sent when it is due (``t0 + due``),
whether or not earlier ones have finished, as independent users send.
Whatever is still open at ``deadline`` is left unfinished in its record.

A record's latencies count from its due time, not from when it was sent, so
a late generator shows as latency and as ``lag`` (sent - due).
"""
from __future__ import annotations

import asyncio
import json
import re
import sys
import time

_TOKEN = re.compile(rb'"token": (\d+)')


async def sse_request(port: int, body: bytes, rec: dict) -> None:
    """POST one request and read its SSE stream to the end, stamping token
    events as they arrive. Token frames are found by C-speed scans of each
    received segment; only the final ``done`` frame is JSON-decoded."""
    rec["sent"] = time.monotonic()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    except OSError as e:
        rec["error"] = f"connect: {e}"
        return
    try:
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        rec["status"] = int(head.split(b" ")[1])
        if rec["status"] != 200:
            rec["error"] = (await reader.read()).decode(errors="replace")
            return
        buf = bytearray()
        while rec["finish_reason"] is None:
            chunk = await reader.read(65536)
            if not chunk:
                rec["error"] = "stream closed before done"
                return
            t = time.monotonic()
            buf += chunk
            i = buf.rfind(b"\n\n")
            if i < 0:
                continue
            complete = bytes(buf[:i + 2])
            del buf[:i + 2]
            toks = _TOKEN.findall(complete)
            if toks:
                if rec["t_first"] is None:
                    rec["t_first"] = t
                rec["t_last"] = t
                rec["tokens"].extend(int(x) for x in toks)
                rec["token_times"].extend([t] * len(toks))
            for name in (b"event: done", b"event: error"):
                j = complete.find(name)
                if j >= 0:
                    frame = complete[j:complete.index(b"\n\n", j)]
                    d = json.loads(frame.partition(b"data: ")[2])
                    rec["finish_reason"] = d["finish_reason"]
                    rec["t_done"] = t
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        writer.close()


def new_record(**kw) -> dict:
    rec = {"sent": None, "status": None, "t_first": None, "t_last": None,
           "t_done": None, "finish_reason": None, "tokens": [],
           "token_times": [], "error": None}
    rec.update(kw)
    return rec


async def run_open(plan: dict, recs: list) -> None:
    port, t0 = plan["port"], plan["t0"]

    async def one(req):
        due = t0 + req["due"]
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        rec = new_record(id=req["id"], due=due, prompt_len=len(req["prompt"]),
                         max_new_tokens=req["max_new_tokens"])
        recs.append(rec)
        body = json.dumps({"prompt": req["prompt"],
                           "max_new_tokens": req["max_new_tokens"]}).encode()
        await sse_request(port, body, rec)
        rec["prompt"] = req["prompt"]

    await asyncio.gather(*(one(r) for r in plan["requests"]))


async def run_until(plan: dict) -> list:
    """The plan's loop, cut at ``deadline``: a request still open then is
    left without its ``finish_reason``."""
    recs: list = []
    task = asyncio.ensure_future(run_open(plan, recs))
    try:
        await asyncio.wait_for(task, plan["deadline"] - time.monotonic())
    except asyncio.TimeoutError:
        pass
    return recs


def main() -> int:
    plan = json.load(sys.stdin)
    recs = asyncio.run(run_until(plan))
    json.dump({"records": recs}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
