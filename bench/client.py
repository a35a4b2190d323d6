"""Streaming clients: every request is ``POST /v1/completions`` with
``stream: true`` to the gateway over loopback, its tokens read as
server-sent events. All clients share one asyncio loop on one thread, so
the load takes one thread of the server's process.

The loop is open: each request is sent at its due time whether or not
earlier ones have finished. Times are ``time.perf_counter()`` seconds,
the clock the server stamps its own request timings with.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Dict, List, Optional

from loadgen import Request

#: how long requests still in flight when the window closes may take
DRAIN_S = 120.0


@dataclasses.dataclass
class Result:
    request: Request
    due: Optional[float] = None        # scheduled send time (open loop)
    sent: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    summary: Dict = dataclasses.field(default_factory=dict)
    error: Optional[str] = None

    @property
    def complete(self) -> bool:
        return (self.error is None and
                len(self.tokens) == self.request.max_tokens)


async def _stream(port: int, res: Result) -> None:
    req = res.request
    body = json.dumps({"prompt": req.prompt, "max_tokens": req.max_tokens,
                       "stream": True}).encode()
    res.sent = time.perf_counter()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            res.error = status.decode("latin-1").strip()
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            line = await reader.readline()
            if not line:
                res.error = res.error or "connection closed mid-stream"
                return
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                return
            chunk = json.loads(data)
            text = chunk["choices"][0]["text"]
            if text:
                res.token_times.append(time.perf_counter())
                res.tokens.append(int(text))
            if "ralm" in chunk:
                res.summary = chunk["ralm"]
    finally:
        writer.close()


async def _one(port: int, res: Result) -> None:
    try:
        await _stream(port, res)
    except (OSError, ValueError, KeyError) as e:
        res.error = f"{type(e).__name__}: {e}"


async def _open_loop(port: int, requests: List[Request], t0: float
                     ) -> List[Result]:
    loop = asyncio.get_running_loop()
    base = loop.time() - (time.perf_counter() - t0)
    results = [Result(r, due=t0 + r.due_s) for r in requests]

    async def at(res: Result):
        delay = base + res.request.due_s - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        await _one(port, res)

    tasks = [asyncio.ensure_future(at(r)) for r in results]
    await asyncio.wait(tasks, timeout=requests[-1].due_s + DRAIN_S)
    for t in tasks:
        t.cancel()
    for r in results:
        if not r.complete and r.error is None:
            r.error = "not answered within the drain"
    return results


def run(port: int, requests: List[Request], t0: float) -> List[Result]:
    """Send each request at ``t0`` plus its due time, then wait for the
    requests still in flight. Returns one ``Result`` per request."""
    return asyncio.run(_open_loop(port, requests, t0))
