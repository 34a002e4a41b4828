"""The hand-off from the engine thread to the streams' consumers that wait on
an event loop (`serving/engine.py` `_StreamWakes`): inside an emit block (16
rows of a decode step, a chunk's first token, a speculative group) the waiters
are held and woken as the block ends, ONE `call_soon_threadsafe` a consumer
loop; everywhere else at once. Every token still reaches its stream once and
in order, a request that ends inside a block wakes its consumer at the block's
end, a closed loop is skipped, and `/v1/stats` `loop.stream_wakeups` counts
the calls. The synchronous path (`wait_new`, `result()`) is the condition's,
as before. CPU, `tiny`."""

import asyncio
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, PAGE, PAGES_PER_SLOT, CHUNK = 20, 16, 4, 32


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(params, cfg, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    kwargs = dict(max_slots=SLOTS, num_pages=4 * SLOTS + 1, page_size=PAGE, pages_per_slot=PAGES_PER_SLOT, prefill_chunk=CHUNK)
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


def _iterate(engine) -> None:
    engine._admit()
    engine._prefill_one()
    engine._decode_step()


class _LoopThread:
    """An event loop on a thread of its own, as the container's API thread is."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()

    def run(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def close(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
        assert not self.thread.is_alive()
        self.loop.close()


@pytest.fixture()
def loops():
    made = []

    def make(n: int) -> list:
        made.extend(_LoopThread() for _ in range(n))
        return made[-n:]

    yield make
    for lt in made:
        if not lt.loop.is_closed():
            lt.close()


async def _stream(req, timeout=30.0):
    """What `stream_sse` does with the buffer: a cursor, `wait_new_async`
    until done. Returns every token seen, in order, and when `done` was seen."""
    seen, offset = [], 0
    while True:
        tokens, done = await req.wait_new_async(offset, timeout=timeout)
        seen.extend(tokens)
        offset += len(tokens)
        if done:
            return seen, time.time()


def _prompt(i: int) -> list:
    return [(7 * i + j) % 250 + 1 for j in range(3 + i % 5)]


def _parked(requests, timeout: float = 20.0) -> None:
    """Every consumer of a request not done waits on its event."""
    deadline = time.monotonic() + timeout
    while not all(req.done or req._async_waiters for req in requests):
        assert time.monotonic() < deadline, "a consumer never parked"
        time.sleep(0.0005)


# -- the hand-off alone, with loops that record what they are asked ------------------


class _FakeLoop:
    def __init__(self, closed: bool = False):
        self.calls = []
        self.closed = closed

    def call_soon_threadsafe(self, callback, *args):
        if self.closed:
            raise RuntimeError("Event loop is closed")
        self.calls.append((threading.get_ident(), callback, args))
        callback(*args)


class _Event:
    def __init__(self):
        self.is_set = False

    def set(self):
        self.is_set = True


def test_inside_a_block_waiters_are_held_and_woken_once_a_loop_at_its_end():
    from modal_tpu.serving.engine import _StreamWakes

    wakes, one, two = _StreamWakes(), _FakeLoop(), _FakeLoop()
    events = [_Event() for _ in range(5)]
    with wakes:
        wakes.wake([(one, events[0]), (two, events[1])])
        wakes.wake([(one, events[2])])
        wakes.wake([(one, events[3])])
        assert not any(e.is_set for e in events) and one.calls == two.calls == []
        # another thread's wake is not the block's: at once
        other = threading.Thread(target=wakes.wake, args=([(two, events[4])],))
        other.start()
        other.join(10)
        assert events[4].is_set and len(two.calls) == 1 and wakes.calls == 1
    assert all(e.is_set for e in events)
    assert len(one.calls) == 1 and len(two.calls) == 2 and wakes.calls == 3
    assert one.calls[0][2] == ([events[0], events[2], events[3]],)  # one callback sets the loop's three events
    # outside a block: one call a waiter
    later = [_Event(), _Event()]
    wakes.wake([(one, later[0]), (one, later[1])])
    assert all(e.is_set for e in later) and len(one.calls) == 3 and wakes.calls == 5


def test_a_block_that_raises_still_wakes_what_it_held():
    from modal_tpu.serving.engine import _StreamWakes

    wakes, loop, event = _StreamWakes(), _FakeLoop(), _Event()
    with pytest.raises(ZeroDivisionError):
        with wakes:
            wakes.wake([(loop, event)])
            1 / 0
    assert event.is_set and wakes.calls == 1
    after = _Event()
    wakes.wake([(loop, after)])  # the block is over: at once
    assert after.is_set and wakes.calls == 2


def test_a_closed_loop_is_skipped_and_not_counted_in_a_block_or_out_of_one():
    from modal_tpu.serving.engine import _StreamWakes

    wakes, gone, live = _StreamWakes(), _FakeLoop(closed=True), _FakeLoop()
    events = [_Event() for _ in range(4)]
    with wakes:
        wakes.wake([(gone, events[0]), (live, events[1])])
    wakes.wake([(gone, events[2]), (live, events[3])])
    assert [e.is_set for e in events] == [False, True, False, True]
    assert wakes.calls == 2 and len(live.calls) == 2


def test_a_request_alone_wakes_its_consumer_at_once_from_another_thread(loops):
    from modal_tpu.serving.engine import GenRequest

    (lt,) = loops(1)
    req = GenRequest([1, 2], max_new_tokens=3)
    consumer = lt.run(_stream(req, timeout=None))
    for tok in (5, 6, 7):
        _parked([req])
        req._append(tok)
    _parked([req])
    req._finish()
    seen, _at = consumer.result(10)
    assert seen == [5, 6, 7] == req.tokens and req._wakes.calls == 4


# -- a live engine -------------------------------------------------------------------


@pytest.mark.parametrize("case", ["one_loop", "two_loops", "speculative"])
def test_every_stream_receives_exactly_its_tokens_in_order_once(tiny_model, loops, case):
    params, cfg = tiny_model
    extra = dict(draft=(params, cfg), spec_k=2, max_slots=6) if case == "speculative" else {}
    engine = _engine(params, cfg, **extra)
    consumer_loops = loops(2 if case == "two_loops" else 1)
    n = 8 if case == "speculative" else SLOTS + 6  # more requests than slots: some wait for one
    engine.start()
    try:
        requests = [engine.submit(_prompt(i), max_new_tokens=4 + i % 9) for i in range(n)]
        consumers = [consumer_loops[i % len(consumer_loops)].run(_stream(req)) for i, req in enumerate(requests)]
        seen = [c.result(120)[0] for c in consumers]
    finally:
        engine.stop()
    for req, got in zip(requests, seen):
        assert got == req.tokens == req.result(0) and len(got) == req.max_new_tokens and req.error is None
    stats = engine.stats()
    assert stats["tokens_generated"] == sum(req.max_new_tokens for req in requests)
    # at most one call a block a loop, never one a token: batched steps make fewer
    assert 0 < stats["loop"]["stream_wakeups"] < stats["tokens_generated"]


@pytest.mark.parametrize("n_loops", [1, 2])
def test_stream_wakeups_is_one_a_loop_a_block_that_appended_plus_one_a_waiter_woken_at_once(tiny_model, loops, n_loops):
    """The loop driven by hand, every consumer parked before each iteration:
    the calls are counted from what the emits were given (the rows of each
    step read, the first tokens), never from how the engine wakes."""
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    consumer_loops = loops(n_loops)
    requests = [engine.submit(_prompt(i), max_new_tokens=30 + i % 5) for i in range(SLOTS + 4)]
    loop_of = {req.id: consumer_loops[i % n_loops] for i, req in enumerate(requests)}
    consumers = [loop_of[req.id].run(_stream(req)) for req in requests]
    steps, firsts = [], []
    collect_step, emit_first = engine._collect_step, engine._emit_first
    engine._collect_step = lambda step: (steps.append(list(step.slots)), collect_step(step))[1]
    engine._emit_first = lambda idx, slot, tok: (firsts.append(slot.request.id), emit_first(idx, slot, tok))[1]
    while not all(req.done for req in requests):
        _parked(requests)
        _iterate(engine)
    for c in consumers:
        c.result(30)
    stats = engine.stats()
    assert stats["loop"]["tokens_discarded"] == 0  # every row read appended to its stream
    blocks = [rows[lo : lo + 16] for rows in steps for lo in range(0, len(rows), 16)]
    assert max(len(rows) for rows in steps) == SLOTS and any(len(b) == 16 for b in blocks)
    by_hand = sum(len({loop_of[s.request.id] for _i, s in block}) for block in blocks) + len(firsts)
    assert len(firsts) == len(requests) and stats["loop"]["stream_wakeups"] == by_hand
    assert by_hand < stats["tokens_generated"] / 2
    # three requests still queued when the engine stops: each waiter is woken at once, one call each
    queued = [engine.submit(_prompt(40 + i), max_new_tokens=4) for i in range(3)]
    stopped = [consumer_loops[0].run(_stream(req)) for req in queued]
    _parked(queued)
    engine.stop()
    for c, req in zip(stopped, queued):
        assert c.result(10)[0] == [] and req.error == "engine stopped"
    assert engine.stats()["loop"]["stream_wakeups"] == by_hand + 3


@pytest.mark.parametrize("ends", ["first_token", "decode_block", "stop"])
def test_a_request_that_ends_wakes_a_consumer_that_waits_for_done_within_a_second(tiny_model, loops, ends):
    """A consumer with no timeout (as `wait_done`): only the wake of the
    request's end lets it go on. It ends in the emit of its first token, in a
    block of a decode step beside streams that go on, or queued behind four
    slots when the engine stops (a wake at once, from the stopping thread)."""
    params, cfg = tiny_model
    engine = _engine(params, cfg, max_slots=4).start()
    (lt,) = loops(1)
    try:
        companions = [engine.submit(_prompt(i), max_new_tokens=56) for i in range(4 if ends == "stop" else 3)]
        with_them = [lt.run(_stream(req)) for req in companions]
        target = engine.submit(_prompt(30), max_new_tokens={"first_token": 1, "decode_block": 5, "stop": 56}[ends])
        waits = lt.run(asyncio.wait_for(_stream(target, timeout=None), 30))
        if ends == "stop":
            _parked([target])
            engine.stop()
        _seen, seen_at = waits.result(40)
        assert target.done and 0 <= seen_at - target.finished_at < 1.0
        if ends == "stop":
            assert target.error == "engine stopped" and target.tokens == []
        else:
            assert target.error is None and len(target.tokens) == target.max_new_tokens
        for c, req in zip(with_them, companions):
            _got, seen_at = c.result(60)
            assert 0 <= seen_at - req.finished_at < 1.0
    finally:
        engine.stop()


def test_a_consumer_whose_loop_closed_leaves_the_engine_serving_the_others(tiny_model, loops):
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    gone, live = loops(2)
    requests = [engine.submit(_prompt(i), max_new_tokens=8) for i in range(6)]

    async def parked_then_cancelled(req):
        task = asyncio.ensure_future(_stream(req))
        while not req._async_waiters:
            await asyncio.sleep(0.001)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    gone.run(parked_then_cancelled(requests[0])).result(10)
    gone.close()  # the consumer is gone with its loop; its waiter stays registered
    assert requests[0]._async_waiters and gone.loop.is_closed()
    others = [live.run(_stream(req)) for req in requests[1:]]
    engine.start()
    try:
        for c, req in zip(others, requests[1:]):
            assert c.result(60)[0] == req.tokens and len(req.tokens) == 8
        assert requests[0].result(60) == requests[0].tokens and len(requests[0].tokens) == 8
    finally:
        engine.stop()


@pytest.mark.parametrize("way", ["wait_new", "result"])
def test_the_synchronous_consumers_behave_as_before(tiny_model, loops, way):
    """Beside asyncio consumers of the same requests, threads that read with
    `wait_new` or block in `result()` get every token from the condition."""
    from modal_tpu.serving.engine import EngineStopped, GenRequest

    params, cfg = tiny_model
    engine = _engine(params, cfg, max_slots=4).start()
    (lt,) = loops(1)
    got = {}

    def read(req):
        if way == "result":
            got[req.id] = req.result(timeout=60)
            return
        seen, offset, done = [], 0, False
        while not done:
            tokens, done = req.wait_new(offset, timeout=30)
            seen.extend(tokens)
            offset += len(tokens)
        got[req.id] = seen

    try:
        requests = [engine.submit(_prompt(i), max_new_tokens=5 + i) for i in range(6)]
        async_side = [lt.run(_stream(req)) for req in requests]
        threads = [threading.Thread(target=read, args=(req,)) for req in requests]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        for req, c in zip(requests, async_side):
            assert got[req.id] == c.result(30)[0] == req.tokens and len(req.tokens) == req.max_new_tokens
    finally:
        engine.stop()
    # a request that ended in error: `wait_new` returns done, `result()` raises
    failed = GenRequest([1], 2)
    failed._finish(error="engine stopped")
    assert failed.wait_new(0, timeout=1) == ([], True)
    with pytest.raises(EngineStopped):
        failed.result(timeout=1)


def test_stream_wakeups_per_token_reads_through_the_benchmark_s_file_on_a_live_engine(tiny_model, loops):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import run as bench_run
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))
    params, cfg = tiny_model
    engine = _engine(params, cfg).start()
    (lt,) = loops(1)
    try:
        warm = engine.submit([1, 2, 3], max_new_tokens=3)
        lt.run(_stream(warm)).result(60)
        start = engine.stats()
        requests = [engine.submit(_prompt(i), max_new_tokens=12) for i in range(SLOTS)]
        for c in [lt.run(_stream(req)) for req in requests]:
            c.result(120)
        end = engine.stats()
    finally:
        engine.stop()
    value = bench_run.read_layer_metric("stream_wakeups_per_token", {"stats_start": start, "stats_end": end})
    woke = end["loop"]["stream_wakeups"] - start["loop"]["stream_wakeups"]
    assert value == pytest.approx(woke / (end["tokens_generated"] - start["tokens_generated"]))
    # twenty streams on one loop: a step of up to twenty rows wakes the loop once or twice
    assert 0 < value < 0.5
