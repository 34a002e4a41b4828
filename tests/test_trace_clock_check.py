"""The profiler's half of the engine loop's phases (ISSUE 26): a session
taken here on the CPU around the `tiny` engine holds an `engine.*` event
for every phase that ran, and `tools/trace_clock_check.py`, which reads
them beside the device's runs of the decode step, is checked on a table
made by hand."""

import glob
import importlib.util
import os
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location("trace_clock_check", os.path.join(REPO, "tools", "trace_clock_check.py"))
clock_tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(clock_tool)

MS = 1_000_000  # nanoseconds


def phase(name, start_ms, end_ms, **stats):
    return ["engine." + name, int(start_ms * MS), int(end_ms * MS), stats]


def test_the_clocks_are_checked_where_cause_and_effect_cross_between_the_planes():
    runs = [(0 * MS, 50 * MS), (90 * MS, 140 * MS)]  # two decode steps on the device
    phases = [
        phase("decode_dispatch", -1.0, 0.5, batch=3),
        phase("decode_sync", 0.5, 50.2, batch=3),  # ends 0.2 ms after the step it waited for
        phase("emit", 50.2, 53.0, tokens=3),
        phase("decode_prep", 82.0, 88.0),
        phase("decode_dispatch", 88.0, 91.0, batch=4),
        phase("decode_sync", 91.0, 139.9, batch=4),  # ends 0.1 ms BEFORE its step: the host first
        phase("emit", 139.9, 150.0, tokens=4),
    ]
    clock = clock_tool.clock_check(phases, runs)
    assert clock["steps"] == 2 and clock["unpaired"] == 0
    # the device started 1 and 2 ms after its dispatch did; the first sync ended 0.2 ms after its step,
    # the second 0.1 ms BEFORE it: an effect ahead of its cause, so the clocks are off by at least that
    assert clock["launch_ms"] == {"median": pytest.approx(1.5), "least": pytest.approx(1.0), "most": pytest.approx(2.0)}
    assert clock["sync_ms"] == {"median": pytest.approx(0.05), "least": pytest.approx(-0.1), "most": pytest.approx(0.2)}
    assert clock["host_first_share"] == 0.5
    # a sync whose step started before the trace did has no run to be paired with: counted, not guessed
    clock = clock_tool.clock_check(phases, runs[1:])
    assert clock["steps"] == 1 and clock["unpaired"] == 1 and clock["sync_ms"]["median"] == pytest.approx(-0.1)
    assert clock_tool.clock_check([], runs) == {"steps": 0, "unpaired": 0}


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    cfg = get_config("tiny")
    engine = ServingEngine(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=4, num_pages=25, page_size=16, pages_per_slot=8, prefill_chunk=32
    ).start()
    engine.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)  # compiled before the session
    yield engine
    engine.stop()


def test_a_profiler_session_holds_an_event_for_every_phase_that_ran(tiny_engine, tmp_path):
    """The session `benchmark/benchlib/incontainer.py` takes in the
    container, taken here on the CPU: host tracer at level 1, Python tracer
    off."""
    import jax

    from modal_tpu.observability.catalog import ENGINE_PHASES

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        first = tiny_engine.submit(list(range(10, 50)), max_new_tokens=5)
        first.result(timeout=120)
        time.sleep(0.05)  # the loop waits for work inside the session
        second = tiny_engine.submit([7, 8, 9], max_new_tokens=4)
        second.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found, runs = clock_tool.load(path)
    assert runs == []  # no device plane on the CPU: nothing to pair, said and not guessed
    assert clock_tool.clock_check(found, runs)["steps"] == 0
    assert {name for name, _s, _e, _stats in found} == {"engine." + name for name in ENGINE_PHASES}
    by_phase: dict = {}
    for name, _start, _end, stats in found:
        by_phase.setdefault(name[len("engine."):], []).append(stats)
    for name in ("admit", "prefill_prep", "prefill_dispatch", "prefill_sync"):
        assert {stats["request_id"] for stats in by_phase[name]} == {first.id, second.id}, name
    assert [s["chunk_tokens"] for s in by_phase["prefill_prep"]] == [32, 8, 3]
    assert [s["bucket"] for s in by_phase["prefill_prep"]] == [32, 16, 16] and by_phase["prefill_prep"][1]["offset"] == 32
    assert all(stats["batch"] == 1 for stats in by_phase["decode_dispatch"] + by_phase["decode_sync"])
    assert len(by_phase["decode_sync"]) == 4 + 3  # a step a token after the first
    assert {s.get("request_id") for s in by_phase["emit"]} == {first.id, second.id, None}
    # they partition the thread's time: each starts where the last ended (microseconds apart: the
    # tracer's own work between two; now and then another thread takes the interpreter there)
    seams = sorted(b[1] - a[2] for a, b in zip(found, found[1:]))
    assert seams[0] >= 0 and seams[len(seams) // 2] < 50_000 and sum(seams) < 0.02 * (found[-1][2] - found[0][1])
