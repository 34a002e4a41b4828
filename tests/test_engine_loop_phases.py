"""The engine loop's own account of its time and work (ISSUE 26): the phases
partition the engine thread's time, the `/v1/stats` counters are exact against
a hand count, and none of it makes `modal_tpu.serving` import jax. CPU, `tiny`.

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
    _iterate(engine)  # admitted, prefilled, one decode step
    younger = engine.submit(list(range(30, 40)), max_new_tokens=8)  # 10 tokens: bucket 16
    _iterate(engine)  # the younger: admitted, prefilled, and both decode
    assert len(older.tokens) == 3 and len(younger.tokens) == 2
    assert engine._preempt_youngest() and younger.preemptions == 1 and older.preemptions == 0
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
