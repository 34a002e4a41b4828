"""The engine loop's own account of its time and work (ISSUE 26): the phases
partition the engine thread's time, the `/v1/stats` counters are exact against
a hand count, and none of it makes `modal_tpu.serving` import jax. CPU, `tiny`.
Since ISSUE 39 each phase also says how long the thread was off the CPU in it
and how long the device was dry in it, a host phase over 50 ms is a stall, and
`/v1/stats` `host` has the threads' CPU and the collector's pauses.

The profiler's half of the same phases (the `engine.*` annotations in a
trace) is in tests/test_trace_clock_check.py; the per-layer metrics that read
these counters are in tests/benchmark/test_bench_loop_metrics.py."""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, PAGES, PAGE, PAGES_PER_SLOT, CHUNK = 4, 25, 16, 8, 32


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(params, cfg, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    kwargs = dict(max_slots=SLOTS, num_pages=PAGES, page_size=PAGE, pages_per_slot=PAGES_PER_SLOT, prefill_chunk=CHUNK)
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


def _iterate(engine) -> None:
    """One iteration of the loop on the caller's thread (the engine is not
    started), so that a test decides what happens between two."""
    engine._admit()
    engine._prefill_one()
    engine._decode_step()


def _counters(engine) -> dict:
    keys = ("prompt_tokens_prefilled", "prefill_chunks", "prefill_bucket_tokens", "requests_admitted")
    stats = engine.stats()
    return {key: stats[key] for key in keys}


def test_the_phases_partition_the_engine_thread_s_time(tiny_model, tmp_path):
    from modal_tpu.observability import tracing
    from modal_tpu.observability.catalog import ENGINE_PHASES

    params, cfg = tiny_model
    trace_dir = str(tmp_path / "traces")
    tracing.configure(trace_dir)
    write_seconds_before = tracing.span_write_seconds()
    engine = _engine(params, cfg)
    started = time.perf_counter()
    engine.start()
    try:
        # mixed: more requests than slots, prompts of one and of two chunks, one sampled
        requests = [
            engine.submit(list(range(1 + i, 1 + i + n)), max_new_tokens=m, temperature=t, seed=i)
            for i, (n, m, t) in enumerate([(5, 9, 0.0), (40, 6, 0.0), (33, 12, 0.7), (12, 4, 0.0), (50, 7, 0.0), (7, 10, 0.0)])
        ]
        for req in requests:
            req.result(timeout=120)
        time.sleep(0.4)  # the loop has nothing to do: `wait_work`
        mid = engine.stats()["loop"]
    finally:
        engine.stop()
    wall = time.perf_counter() - started
    stats = engine.stats()
    loop = stats["loop"]
    phases = loop["phase_seconds"]
    assert set(phases) == set(ENGINE_PHASES) and all(seconds > 0 for seconds in phases.values())  # every phase ran
    assert sum(phases.values()) == pytest.approx(loop["seconds"], rel=0.01)
    assert loop["seconds"] == pytest.approx(wall, rel=0.02)
    for read in (mid, loop):  # in every read, exactly: host + sync + wait = seconds
        sync_seconds = read["phase_seconds"]["prefill_sync"] + read["phase_seconds"]["decode_sync"]
        assert read["host_seconds"] + sync_seconds + read["wait_seconds"] == pytest.approx(read["seconds"], rel=1e-12)
        assert read["work_seconds"] + read["wait_seconds"] == read["seconds"]
        assert read["host_seconds"] + sync_seconds == pytest.approx(read["work_seconds"], rel=1e-12)
    assert loop["wait_seconds"] == phases["wait_work"] >= 0.3
    off_cpu = loop["phase_off_cpu_seconds"]
    assert set(off_cpu) == set(phases) and all(0 <= off_cpu[name] <= phases[name] for name in phases)
    assert off_cpu["wait_work"] >= 0.3 * 0.9  # blocked on the condition: off the CPU
    starved = loop["phase_starved_seconds"]
    assert set(starved) == set(phases) - {"wait_work"} and loop["starved_seconds"] == pytest.approx(sum(starved.values()))
    assert all(0 <= starved[name] <= phases[name] for name in starved)
    assert stats["requests_admitted"] == 6 and 0 < stats["prefill_chunks"] <= loop["iterations"]
    # six requests over four slots: two waited for a slot, and the wait is counted
    waits = [req.admitted_at - req.created_at for req in requests]
    assert stats["queue_wait_seconds"] == pytest.approx(sum(waits), abs=1e-6) and max(waits) > 0
    # what writing the request spans cost is counted, on every thread of the process
    assert len(tracing.read_spans(trace_dir)) > 6 * 4
    assert stats["tracing"] == {"span_write_seconds": tracing.span_write_seconds()}
    assert 0 < stats["tracing"]["span_write_seconds"] - write_seconds_before < loop["host_seconds"]


def test_a_process_with_no_sink_and_no_tap_pays_nothing_to_write_a_span(tmp_path, monkeypatch):
    from modal_tpu.observability import tracing

    class NoLock:
        def __enter__(self):
            raise AssertionError("`_write` took the sink's lock with no sink set")

        def __exit__(self, *exc):
            return False

    real_lock = tracing._sink_lock
    monkeypatch.setattr(tracing, "_sink_file", None)
    monkeypatch.setattr(tracing, "_sink_dir", None)
    monkeypatch.setattr(tracing, "_span_taps", [])
    monkeypatch.setattr(tracing, "_sink_lock", NoLock())
    before = tracing.span_write_seconds()
    parent = tracing.SpanContext(tracing.new_trace_id(), tracing.new_span_id())
    tracing.record_span("serving.decode", start=1.0, end=2.0, parent=parent)
    seen = []
    tracing.add_span_tap(seen.append)  # a tap alone sees the span; its time is not the sink's
    tracing.record_span("serving.decode", start=2.0, end=3.0, parent=parent)
    assert len(seen) == 1 and tracing.span_write_seconds() == before
    monkeypatch.setattr(tracing, "_sink_lock", real_lock)
    tracing.configure(str(tmp_path / "sink"))
    tracing.record_span("serving.decode", start=3.0, end=4.0, parent=parent)
    assert len(seen) == 2 and tracing.span_write_seconds() > before
    assert len(tracing.read_spans(str(tmp_path / "sink"))) == 1


def test_the_counters_against_a_hand_count_with_a_prefix_cache_hit(tiny_model):
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    prompt = list(range(100, 140))  # 40 tokens: a chunk of 32 (bucket 32) and one of 8 (bucket 16)
    first = engine.submit(prompt, max_new_tokens=3)
    while not first.done:
        _iterate(engine)
    assert _counters(engine) == {
        "prompt_tokens_prefilled": 40, "prefill_chunks": 2, "prefill_bucket_tokens": 48, "requests_admitted": 1,
    }
    # the same prompt again: the cache covers 39 of its 40 tokens (two full pages, seven tokens of
    # the third; the last token must still be computed): ONE token in a bucket of 16
    second = engine.submit(prompt, max_new_tokens=3)
    while not second.done:
        _iterate(engine)
    assert second.tokens == first.tokens and engine.stats()["prefix_cache_hits"] == 1
    assert _counters(engine) == {
        "prompt_tokens_prefilled": 41, "prefill_chunks": 3, "prefill_bucket_tokens": 64, "requests_admitted": 2,
    }
    engine.stop()


def test_the_counters_against_a_hand_count_with_one_forced_preemption(tiny_model):
    params, cfg = tiny_model
    engine = _engine(params, cfg, prefix_cache=False)
    older = engine.submit(list(range(1, 21)), max_new_tokens=8)  # 20 tokens: bucket 32
    _iterate(engine)  # admitted, its chunk launched alone: its first token in flight
    younger = engine.submit(list(range(30, 40)), max_new_tokens=8)  # 10 tokens: bucket 16
    _iterate(engine)  # the younger admitted, its chunk launched inside the older's first step
    _iterate(engine)  # both decode: that step is in flight
    assert len(older.tokens) == 2 and len(younger.tokens) == 1 and engine.stats()["loop"]["drains"] == 0
    assert engine._preempt_youngest() and younger.preemptions == 1 and older.preemptions == 0
    # the step in flight was read first: its token is part of what the younger prefills again
    assert len(older.tokens) == 3 and len(younger.tokens) == 2 and engine.stats()["loop"]["drains"] == 1
    time.sleep(0.01)  # requeued: the wait until it is admitted again is queue wait too
    while not (older.done and younger.done):
        _iterate(engine)
    # re-admitted, the younger prefills its prompt and the two tokens it had: 12 tokens, bucket 16
    assert _counters(engine) == {
        "prompt_tokens_prefilled": 20 + 10 + 12, "prefill_chunks": 3, "prefill_bucket_tokens": 32 + 16 + 16,
        "requests_admitted": 3,
    }
    stats = engine.stats()
    assert stats["preemptions"] == 1 and stats["tokens_generated"] == 16
    assert stats["queue_wait_seconds"] >= 0.01
    assert len(older.tokens) == len(younger.tokens) == 8
    engine.stop()


def test_a_steady_iteration_launches_step_n_plus_one_before_it_reads_step_n(tiny_model):
    """ISSUE 35: the order of the phases, who a sync reads, and the three
    counters of `/v1/stats` `loop` that say how the overlap fared."""
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    phases = []
    switch = engine._phase
    engine._phase = lambda name, **attrs: (phases.append((name, attrs)), switch(name, **attrs))[1]
    one = engine.submit(list(range(1, 8)), max_new_tokens=12)
    two = engine.submit(list(range(20, 60)), max_new_tokens=12)  # two chunks
    launched_before_read = []
    while not (one.done and two.done):
        before = engine._flying
        del phases[:]
        _iterate(engine)
        names = [name for name, _attrs in phases]
        if before is not None and engine._flying is not None and not {"prefill_prep", "prefill_sync"} & set(names):
            # a steady iteration: nothing to admit, prefill or read of a chunk, one step in flight on entry
            assert names == ["decode_prep", "decode_dispatch", "decode_sync", "emit"]
            # the sync reads the step BEFORE the one just launched
            assert dict(phases)["decode_sync"]["batch"] == len(before.slots)
            launched_before_read.append(engine._flying is not before)
    assert len(launched_before_read) >= 8 and all(launched_before_read)
    loop = engine.stats()["loop"]
    steps = engine.stats()["steps"]
    # `one` decodes alone for two steps, then both for nine, then `two` alone for two: 13 steps,
    # every one but the first launched while its predecessor's tokens were unread
    assert (steps, loop["steps_overlapped"], loop["drains"], loop["tokens_discarded"]) == (13, 12, 0, 0)
    assert loop["decode_rows"] == 2 * 1 + 9 * 2 + 2 * 1  # counted at each launch, where the device computes them
    engine.stop()


def test_every_sixteenth_step_is_read_before_anything_else_is_launched(tiny_model):
    """`QUIESCE_STEPS` (serving/engine.py says why it is there): at the head
    of the iteration after steps 16, 32, ... the step in flight is read, so
    the device's queue is empty while the host admits and prepares."""
    from modal_tpu.serving.engine import QUIESCE_STEPS

    params, cfg = tiny_model
    engine = _engine(params, cfg)
    req = engine.submit(list(range(1, 8)), max_new_tokens=40)
    empty_after_admit = []
    while not req.done:
        engine._admit()
        if engine._flying is None and engine.step_count:
            empty_after_admit.append(engine.step_count)
        engine._prefill_one()
        engine._decode_step()
    assert QUIESCE_STEPS == 16 and empty_after_admit == [16, 32]
    stats = engine.stats()
    # 39 steps; the first and the one after each periodic drain overlap nothing
    assert (stats["steps"], stats["loop"]["steps_overlapped"], stats["loop"]["drains"]) == (39, 36, 2)
    engine.stop()


def test_a_chunk_s_first_token_is_read_behind_the_launch_of_the_step_it_joins(tiny_model):
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    phases = []
    switch = engine._phase
    engine._phase = lambda name, **attrs: (phases.append(name), switch(name, **attrs))[1]
    older = engine.submit(list(range(1, 8)), max_new_tokens=9)
    _iterate(engine)
    _iterate(engine)
    del phases[:]
    joiner = engine.submit(list(range(30, 41)), max_new_tokens=9)
    _iterate(engine)  # the joiner's whole prompt is one chunk, launched inside the older's step
    assert phases == [
        "admit", "prefill_prep",  # the chunk is made ready; nothing is launched
        "decode_prep", "decode_dispatch", "emit",  # the older's step carries the chunk; the chunk's counters
        "decode_sync", "emit",  # the older's step before it; the joiner's first token is not read
    ]
    assert len(joiner.tokens) == 0 and [s.request for _i, s in engine._flying.slots] == [older]
    del phases[:]
    _iterate(engine)  # both decode in the step launched here
    assert phases == [
        "decode_prep", "decode_dispatch",  # the step both are in, the joiner on the device's own first token
        "decode_sync", "emit",  # the older's step before it
        "prefill_sync", "emit",  # only now the joiner's first token
    ]
    assert len(joiner.tokens) == 1 and [s.request for _i, s in engine._flying.slots] == [older, joiner]
    while not (older.done and joiner.done):
        _iterate(engine)
    alone = _engine(params, cfg)
    want = alone.submit(list(range(30, 41)), max_new_tokens=9)
    while not want.done:
        _iterate(alone)
    assert joiner.tokens == want.tokens
    engine.stop()
    alone.stop()


DECODE_OVERLAP_PCT = dict(num="loop.steps_overlapped", den="steps", scale=100.0)  # the per-layer metric ISSUE 35 names


def test_decode_overlap_pct_reads_near_100_from_a_live_engine_and_nothing_from_a_parent_s_stats(tiny_model):
    """The ratio a later `benchmark` PR can declare (PERF.md, open questions:
    this PR could not add the entry), through the reader the benchmark has."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from readers import stats_delta_ratio
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))
    params, cfg = tiny_model
    engine = _engine(params, cfg).start()
    try:
        engine.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
        ctx = {"stats_start": engine.stats()}
        requests = [engine.submit(list(range(60 + i, 100 + i)), max_new_tokens=40) for i in range(3)]
        for req in requests:
            req.result(timeout=120)
        ctx["stats_end"] = engine.stats()
    finally:
        engine.stop()
    value = stats_delta_ratio.read(ctx, **DECODE_OVERLAP_PCT)
    steps = ctx["stats_end"]["steps"] - ctx["stats_start"]["steps"]
    # all but the first after a wait for work and the one after each periodic drain (every 16th step)
    assert 39 <= steps <= 45 and 100 * (steps - 2 - steps // 16) / steps <= value <= 100 * (steps - 1 - steps // 16) / steps
    parent = {"steps": 3, "loop": {"iterations": 5}}  # the parent commit's `/v1/stats` lacks the counter
    assert stats_delta_ratio.read({"stats_start": parent, "stats_end": {"steps": 9, "loop": {"iterations": 11}}}, **DECODE_OVERLAP_PCT) is None
    assert stats_delta_ratio.read({"stats_start": ctx["stats_end"], "stats_end": ctx["stats_end"]}, **DECODE_OVERLAP_PCT) is None


def test_the_span_knobs_are_read_once_when_the_engine_is_built(tiny_model, monkeypatch):
    from modal_tpu.serving import engine as engine_mod

    params, cfg = tiny_model
    monkeypatch.setenv(engine_mod.SPANS_ENV, "0")
    monkeypatch.setenv(engine_mod.SPAN_TOKENS_ENV, "3")
    engine = _engine(params, cfg)
    assert engine.spans_on is False and engine.span_mark_tokens == 3
    monkeypatch.setenv(engine_mod.SPANS_ENV, "1")  # a later change is for the next engine
    req = engine.submit([1, 2, 3], max_new_tokens=2)
    assert req.root_span is None and engine.spans_on is False
    engine.stop()
    assert _engine(params, cfg).spans_on is True


def test_importing_the_serving_package_still_leaves_jax_out():
    code = (
        "import sys, modal_tpu.serving, modal_tpu.serving.engine, modal_tpu.observability.catalog\n"
        "from modal_tpu.serving import llm_service\n"
        "assert 'jax' not in sys.modules, 'importing modal_tpu.serving imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


class _NoAnnotation:
    def __init__(self, name, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Clock:
    """`perf_counter` and `thread_time` where the test puts them."""

    def __init__(self):
        self.wall = self.cpu = 0.0

    def perf_counter(self):
        return self.wall

    def thread_time(self):
        return self.cpu


class _Program:
    """An output of a launched program: `is_ready()` answers from a script,
    one answer a check, and a check past the script fails the test."""

    def __init__(self, *answers):
        self.answers = list(answers)

    def is_ready(self):
        return self.answers.pop(0)


@pytest.fixture()
def clock(monkeypatch):
    from modal_tpu.serving import engine as engine_mod

    fake = _Clock()
    monkeypatch.setattr(engine_mod, "time", fake)
    return fake


def test_a_phase_s_seconds_off_the_cpu_are_its_wall_time_less_the_thread_s_cpu_time(monkeypatch):
    from modal_tpu.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "CPU_SAMPLE", 1)  # every phase measured
    phases = engine_mod._LoopPhases(_NoAnnotation)
    phases.switch("admit")
    until = time.perf_counter() + 0.03
    while time.perf_counter() < until:  # on the CPU
        pass
    phases.switch("emit")
    time.sleep(0.05)  # off it
    phases.switch("decode_prep")
    phases.close()
    table = phases.snapshot()
    seconds, off_cpu = table["phase_seconds"], table["phase_off_cpu_seconds"]
    assert all(0 <= off_cpu[name] <= seconds[name] for name in seconds)
    assert seconds["emit"] >= 0.05 and off_cpu["emit"] >= 0.045
    assert seconds["admit"] >= 0.03 and off_cpu["admit"] < seconds["admit"] / 2  # a shared machine may take a little


def test_the_cpu_clock_is_read_for_one_phase_in_cpu_sample_and_the_others_take_its_share(clock, monkeypatch):
    """`thread_time` is a system call (6 us on the chip's host): read at the
    start and the end of the first phase and of every CPU_SAMPLE-th after it,
    and a phase's off-CPU seconds are its seconds times its measured share."""
    from modal_tpu.serving import engine as engine_mod

    reads = []
    thread_time = clock.thread_time
    clock.thread_time = lambda: (reads.append(clock.wall), thread_time())[1]
    phases = engine_mod._LoopPhases(_NoAnnotation)
    monkeypatch.setattr(engine_mod, "CPU_SAMPLE", 13)  # 29 by default
    for i in range(27):  # 10 ms each; emit is a quarter on the CPU, decode_prep all of it
        phases.switch("emit" if i % 2 == 0 else "decode_prep")
        clock.wall += 0.01
        clock.cpu += 0.0025 if i % 2 == 0 else 0.01
    phases.close()
    # phases 0 (emit), 13 (decode_prep) and 26 (emit) are measured, at their start and their end
    assert reads == pytest.approx([0.0, 0.01, 0.13, 0.14, 0.26, 0.27])
    table = phases.snapshot()
    assert table["phase_seconds"]["emit"] == pytest.approx(0.14) and table["phase_seconds"]["decode_prep"] == pytest.approx(0.13)
    assert table["phase_off_cpu_seconds"]["emit"] == pytest.approx(0.14 * 0.75)
    assert table["phase_off_cpu_seconds"]["decode_prep"] == pytest.approx(0.0, abs=1e-12) and table["phase_off_cpu_seconds"]["admit"] == 0.0


def test_the_device_is_dry_from_the_first_check_that_finds_it_finished_to_the_next_dispatch(clock):
    from modal_tpu.serving.engine import _LoopPhases

    phases = _LoopPhases(_NoAnnotation)
    step = _Program(False, False, True)  # running at the sync's check and emit's first; done at the next
    for wall, name in ((0.0, "decode_prep"), (1.0, "decode_dispatch")):
        clock.wall = wall
        phases.switch(name)
    phases.launched(step)
    for wall, name in ((2.0, "decode_sync"), (3.0, "emit")):
        clock.wall = wall
        phases.switch(name)
    clock.wall = 4.0
    phases.check()  # emit's 16th token: done, and nothing asks it again
    for wall, name in ((6.0, "admit"), (7.0, "prefill_prep"), (9.0, "prefill_dispatch")):
        clock.wall = wall
        phases.switch(name)
    clock.wall = 10.0
    phases.launched(_Program(True))  # a chunk that ends before the host looks
    phases.switch("emit")
    for wall, name in ((12.0, "wait_work"), (20.0, "admit"), (21.0, "decode_prep")):
        clock.wall = wall
        phases.switch(name)  # nothing in flight: the device idles, and is not starved
    clock.wall = 22.0
    phases.close()
    assert step.answers == []
    starved = phases.snapshot()["phase_starved_seconds"]
    assert "wait_work" not in starved
    assert starved == {
        "admit": 1.0, "prefill_prep": 2.0, "prefill_dispatch": 0.0, "prefill_sync": 0.0,
        "decode_prep": 0.0, "decode_dispatch": 0.0, "decode_sync": 0.0, "emit": 2.0 + 2.0,
    }


def test_a_host_phase_over_50_ms_is_a_stall_and_a_sync_or_a_wait_never_is(clock):
    from modal_tpu.serving.engine import STALL_SECONDS, _LoopPhases

    phases = _LoopPhases(_NoAnnotation)
    for name, lasts in (
        ("emit", 0.06), ("decode_sync", 0.2), ("decode_dispatch", 0.04), ("prefill_sync", 1.0), ("admit", 0.051),
        ("wait_work", 2.0), ("decode_prep", 0.01),
    ):
        phases.switch(name)
        clock.wall += lasts
    phases.close()
    table = phases.snapshot()
    assert STALL_SECONDS == 0.05
    assert table["stalls"] == {
        "admit": 1, "prefill_prep": 0, "prefill_dispatch": 0, "decode_prep": 0, "decode_dispatch": 0, "emit": 1,
    }
    assert table["stall_seconds"] == pytest.approx(0.06 + 0.051)


def test_a_stall_over_half_a_second_writes_one_line_that_says_where_it_went(clock, monkeypatch):
    import types

    from modal_tpu.serving import engine as engine_mod

    lines = []
    monkeypatch.setattr(engine_mod, "logger", types.SimpleNamespace(warning=lines.append))
    collector = types.SimpleNamespace(seconds=4.0)
    monkeypatch.setattr(engine_mod, "GC_PAUSES", collector)
    phases = engine_mod._LoopPhases(_NoAnnotation)
    phases.switch("emit")
    clock.wall, clock.cpu, collector.seconds = 0.6, 0.1, 4.3
    phases.switch("decode_prep")  # emit: 0.6 s, 0.1 of it on the CPU, 0.3 s of the collector's pauses
    clock.wall, clock.cpu = 0.9, 0.35
    phases.switch("decode_sync")  # a stall, not long enough for a line
    clock.wall = 2.0
    phases.switch("admit")  # a long sync: neither
    clock.wall, clock.cpu = 2.8, 0.55
    phases.switch("decode_prep")  # admit: 0.8 s; the last reading was at emit's end, 1.4 s before it began
    assert lines == [
        "serving engine stalled in emit: 0.600 s; since a reading 0.000 s before it began, 0.500 s off the CPU "
        "and 0.300 s of the collector's pauses",
        "serving engine stalled in admit: 0.800 s; since a reading 1.400 s before it began, 1.750 s off the CPU "
        "and 0.000 s of the collector's pauses",
    ]
    assert phases.snapshot()["stalls"]["decode_prep"] == 1


@pytest.fixture()
def served(tiny_model):
    """The serving ASGI app behind the real AsgiHttpServer on a loop thread
    of its own, as a container serves it; the collector's hook installed."""
    import asyncio
    import threading

    from modal_tpu.observability.device_telemetry import install_gc_hooks
    from modal_tpu.runtime.asgi import AsgiHttpServer
    from modal_tpu.serving.api import serving_asgi_app

    install_gc_hooks()
    params, cfg = tiny_model
    engine = _engine(params, cfg).start()
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    server = AsgiHttpServer(serving_asgi_app(engine))
    asyncio.run_coroutine_threadsafe(server.start(), loop).result(30)
    try:
        yield server.port, thread
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()


def _http(port: int, method: str, path: str, body: dict | None = None) -> bytes:
    import json
    import socket

    payload = json.dumps(body).encode() if body is not None else b""
    with socket.create_connection(("127.0.0.1", port), timeout=120) as sock:
        sock.sendall(f"{method} {path} HTTP/1.1\r\nhost: t\r\ncontent-length: {len(payload)}\r\n\r\n".encode() + payload)
        chunks = []
        while data := sock.recv(65536):
            chunks.append(data)
    return b"".join(chunks).split(b"\r\n\r\n", 1)[1]


def test_v1_stats_carries_the_api_thread_s_cpu_and_the_collector_s_pauses(served):
    import gc
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import run as bench_run
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))
    port, api_thread = served
    start = json.loads(_http(port, "GET", "/v1/stats"))
    for i in range(4):
        raw = _http(port, "POST", "/v1/generate", {"prompt": [3 + i, 1, 4, 1, 5], "max_new_tokens": 12, "stream": True})
        assert raw.count(b"event: token") == 12
    gc.collect()
    end = json.loads(_http(port, "GET", "/v1/stats"))
    threads = end["host"]["threads"]
    assert set(threads) == {"api_cpu_seconds", "engine_cpu_seconds", "process_cpu_seconds"}
    for key in threads:  # the API thread served four streams, the engine computed them
        assert threads[key] > start["host"]["threads"][key], key
    assert threads["api_cpu_seconds"] < threads["process_cpu_seconds"]
    from modal_tpu.observability.device_telemetry import _threads

    assert _threads["api"] == api_thread.native_id
    gc_end, gc_start = end["host"]["gc"], start["host"]["gc"]
    assert gc_end["pause_seconds"] > gc_start["pause_seconds"] and gc_end["collections"][2] >= gc_start["collections"][2] + 1
    # the seven per-layer metrics of ISSUE 39 read this window through the benchmark's own files
    ctx = {"stats_start": start, "stats_end": end}
    read = {name: bench_run.read_layer_metric(name, ctx) for name in (
        "loop_emit_off_cpu_ms", "api_cpu_us_per_token", "loop_starved_pct", "loop_starved_emit_pct",
        "loop_stall_pct", "gc_pause_pct", "decode_rows_per_step",
    )}
    assert all(value is not None for value in read.values()), read
    assert read["api_cpu_us_per_token"] > 0 and read["gc_pause_pct"] > 0 and 0 <= read["loop_starved_pct"] <= 100
    assert read["decode_rows_per_step"] == pytest.approx(
        (end["loop"]["decode_rows"] - start["loop"]["decode_rows"]) / (end["steps"] - start["steps"])
    ) and 1 <= read["decode_rows_per_step"] <= SLOTS
