"""Load generator: one asyncio loop, one TCP connection a request, SSE read
token by token on the harness's own clock.

The server (runtime/asgi.py) answers HTTP/1.1 with `connection: close` and
no chunking, so a response is read to the end of the stream. Times are
`time.monotonic()` of this process; `t0` is the window's start.
"""

from __future__ import annotations

import asyncio
import json
import time
import urllib.parse
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Record:
    index: int
    prompt_len: int
    max_new_tokens: int
    due: float  # absolute monotonic time the request was due
    sent: float = 0.0
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    done: float = 0.0
    error: Optional[str] = None
    cut: bool = False  # closed loop: still in flight when the window closed
    prompt: Optional[list] = None
    server_ttft_s: Optional[float] = None

    @property
    def finished(self) -> bool:
        return self.done > 0.0 and self.error is None


async def http_json(url: str, body: Optional[dict] = None, method: Optional[str] = None, timeout: float = 600.0) -> dict:
    """A small JSON call on the same plain client (GET without a body)."""
    parsed = urllib.parse.urlparse(url)
    data = json.dumps(body).encode() if body is not None else b""
    verb = method or ("POST" if body is not None else "GET")

    async def call() -> dict:
        reader, writer = await asyncio.open_connection(parsed.hostname, parsed.port)
        try:
            path = parsed.path or "/"
            if parsed.query:
                path += "?" + parsed.query
            writer.write(
                f"{verb} {path} HTTP/1.1\r\nhost: {parsed.hostname}\r\ncontent-type: application/json\r\n"
                f"content-length: {len(data)}\r\nconnection: close\r\n\r\n".encode() + data
            )
            await writer.drain()
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ", 2)[1])
            raw = await reader.read()
            payload = json.loads(raw) if raw.strip() else {}
            if status != 200:
                raise RuntimeError(f"{verb} {url}: HTTP {status}: {str(payload)[:300]}")
            return payload
        finally:
            writer.close()

    return await asyncio.wait_for(call(), timeout)


async def generate(host: str, port: int, prompt: list, rec: Record) -> None:
    """POST /v1/generate, streamed, greedy, no EOS. Fills `rec`; never raises."""
    body = json.dumps(
        {"prompt": prompt, "max_new_tokens": rec.max_new_tokens, "stream": True, "temperature": 0.0},
        separators=(",", ":"),
    ).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port, limit=1 << 20)
        rec.sent = time.monotonic()
        writer.write(
            f"POST /v1/generate HTTP/1.1\r\nhost: {host}\r\ncontent-type: application/json\r\n"
            f"content-length: {len(body)}\r\nconnection: close\r\n\r\n".encode() + body
        )
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        if status != 200:
            rest = await reader.read()
            raise RuntimeError(f"HTTP {status}: {rest[:300]!r}")
        event = b""
        while True:
            line = await reader.readline()
            if not line:
                raise RuntimeError("stream ended without a done event")
            if line.startswith(b"event: "):
                event = line[7:].strip()
            elif line.startswith(b"data: "):
                if event == b"token":
                    rec.token_times.append(time.monotonic())
                    rec.tokens.append(json.loads(line[6:])["token"])
                elif event == b"done":
                    payload = json.loads(line[6:])
                    rec.error = payload.get("error")
                    rec.server_ttft_s = payload.get("ttft_s")
                    rec.done = time.monotonic()
                    return
    except asyncio.CancelledError:
        rec.cut = True
        raise
    except Exception as exc:  # noqa: BLE001 — a failed request is counted, by name
        rec.error = f"{type(exc).__name__}: {exc}"
        rec.done = time.monotonic()
    finally:
        if writer is not None:
            writer.close()


async def run_open(url: str, requests: list, t0: float, window_s: float, drain_s: float) -> list:
    """Send each request at its due time whatever the server does; after the
    window wait up to `drain_s` for what is in flight."""
    parsed = urllib.parse.urlparse(url)
    records, tasks = [], []
    for req in requests:
        due = t0 + req.due_s
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = Record(req.index, len(req.prompt), req.max_new_tokens, due, prompt=req.prompt)
        records.append(rec)
        tasks.append(asyncio.create_task(generate(parsed.hostname, parsed.port, req.prompt, rec)))
    rest = t0 + window_s - time.monotonic()
    if rest > 0:
        await asyncio.sleep(rest)
    if tasks:
        _done, pending = await asyncio.wait(tasks, timeout=max(0.0, drain_s))
        for task in pending:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec.cut and rec.error is None:
            rec.error = f"not finished {drain_s:.0f} s after the window closed"
    return records


async def run_closed(url: str, clients: int, next_request, t0: float, window_s: float, drain_s: float) -> list:
    """`clients` callers, each sending its next request when the last one
    ended. At the close of the window, requests in flight get `drain_s` more
    and are then cut: they are counted as cut, not as attempted."""
    parsed = urllib.parse.urlparse(url)
    records: list = []
    close_at = t0 + window_s

    async def client() -> None:
        while time.monotonic() < close_at:
            req = next_request()
            rec = Record(req.index, len(req.prompt), req.max_new_tokens, time.monotonic(), prompt=req.prompt)
            records.append(rec)
            await generate(parsed.hostname, parsed.port, req.prompt, rec)

    tasks = [asyncio.create_task(client()) for _ in range(clients)]
    await asyncio.sleep(max(0.0, close_at - time.monotonic()))
    _done, pending = await asyncio.wait(tasks, timeout=max(0.0, drain_s))
    for task in pending:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return records
