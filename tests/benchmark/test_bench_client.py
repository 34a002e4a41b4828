"""The load generator against a small SSE server of the test's own: an open
loop sends on its schedule whatever the server does and times each request
from when it was due; a closed loop sends the next when the last ended."""

import asyncio
import json
import statistics
import time

import pytest

from . import _paths  # noqa: F401
from benchlib import client, stats
from benchlib.traffic import Request


async def sse_server(token_gap_s: float, fail_index: int = -1):
    """Answers POST /v1/generate like the service: one `token` event a token,
    then `done`. The request whose first prompt id is `fail_index` is cut."""
    seen = []

    async def handle(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        length = int([l for l in head.split(b"\r\n") if l.lower().startswith(b"content-length")][0].split(b":")[1])
        body = json.loads(await reader.readexactly(length))
        seen.append((time.monotonic(), body))
        writer.write(b"HTTP/1.1 200 OK\r\ncontent-type: text/event-stream\r\nconnection: close\r\n\r\n")
        for i in range(body["max_new_tokens"]):
            await asyncio.sleep(token_gap_s)
            if body["prompt"][0] == fail_index and i == 2:
                writer.close()
                return
            writer.write(f"event: token\nid: {i}\ndata: {json.dumps({'token': 7, 'i': i})}\n\n".encode())
            await writer.drain()
        writer.write(f"event: done\ndata: {json.dumps({'tokens': [], 'ttft_s': 0.01})}\n\n".encode())
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    return server, f"http://127.0.0.1:{port}", seen


def test_open_loop_keeps_its_schedule_and_times_from_the_due_time():
    async def drive():
        server, url, seen = await sse_server(0.02, fail_index=3)
        reqs = [Request(i, 0.1 * i, [i, 1, 2], 5) for i in range(6)]
        t0 = time.monotonic()
        records = await client.run_open(url, reqs, t0, window_s=1.0, drain_s=5.0)
        server.close()
        return t0, records, seen

    t0, records, seen = asyncio.run(drive())
    assert len(records) == 6 and [b["stream"] for _t, b in seen] == [True] * 6
    assert all(b["temperature"] == 0.0 and "eos_token_id" not in b for _t, b in seen)
    for i, rec in enumerate(records):
        assert rec.due == pytest.approx(t0 + 0.1 * i)
        assert 0 <= rec.sent - rec.due < 0.05
    good = [r for r in records if r.index != 3]
    assert all(r.finished and len(r.tokens) == 5 and r.server_ttft_s == 0.01 for r in good)
    assert records[3].error and not records[3].finished and len(records[3].tokens) == 2
    nums = stats.window_numbers(records, t0, 1.0, give_up_s=5.0)
    assert len(nums["ttft_ms"]) == 6 and len(nums["late_ms"]) == 6
    # the failed one counts as the longest: the whole time the harness waited for it
    assert max(nums["ttft_ms"]) == pytest.approx((t0 + 1.0 + 5.0 - records[3].due) * 1e3)
    assert sorted(nums["ttft_ms"])[0] == pytest.approx(20, abs=15)
    assert len(nums["itl_ms"]) == 5 * 4 + 1 and statistics.median(nums["itl_ms"]) == pytest.approx(20, abs=8)
    assert nums["tokens_in_window"] == 5 * 5 + 2


def test_what_is_not_finished_when_the_harness_gives_up_has_failed():
    async def drive():
        server, url, _seen = await sse_server(0.2)
        t0 = time.monotonic()
        records = await client.run_open(url, [Request(0, 0.0, [1], 50)], t0, window_s=0.3, drain_s=0.3)
        server.close()
        return records

    (rec,) = asyncio.run(drive())
    assert not rec.finished and "not finished" in rec.error and 1 <= len(rec.tokens) <= 4


def test_closed_loop_sends_the_next_when_the_last_ended_and_cuts_at_the_close():
    async def drive():
        server, url, seen = await sse_server(0.01)
        counter = iter(range(10_000))
        t0 = time.monotonic()
        records = await client.run_closed(
            url, 3, lambda: Request(next(counter), 0.0, [1, 2], 10), t0, window_s=0.6, drain_s=0.0
        )
        server.close()
        return t0, records, seen

    t0, records, seen = asyncio.run(drive())
    finished = [r for r in records if r.finished]
    cut = [r for r in records if r.cut]
    assert len(cut) <= 3 and len(finished) + len(cut) == len(records)
    assert 3 * 3 <= len(finished) <= 3 * 7  # ~0.1 s a request, three callers, 0.6 s
    # never more than three in flight: each caller's sends do not overlap
    times = sorted([(r.sent, 1) for r in records] + [(r.done, -1) for r in finished])
    depth = peak = 0
    for _t, step in times:
        depth += step
        peak = max(peak, depth)
    assert peak == 3
    nums = stats.window_numbers(records, t0, 0.6, give_up_s=0.0)
    assert nums["tokens_in_window"] >= 10 * len(finished)


def test_percentiles_are_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 90) == 90 and stats.percentile(values, 99) == 99 and stats.percentile(values, 100) == 100
    assert stats.percentile([5.0], 99) == 5.0 and statistics.median([1, 3, 2, 10]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)
