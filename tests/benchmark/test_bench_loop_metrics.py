"""The per-layer metrics that read the engine loop's own counters
(`/v1/stats` `loop`, PR 26): the reader of two counters on what a program
without them leaves it (nothing, never a 0), and each metric's own file over
two reads of a live `tiny` engine."""

import json
import os

import pytest

from . import _paths
from readers import stats_delta_ratio

LOOP_METRICS = (
    "loop_host_pct", "loop_emit_pct", "loop_prep_pct", "span_write_pct", "prefill_fill_pct", "queue_wait_mean_ms",
    "loop_emit_ms", "loop_admit_ms", "loop_prefill_launch_ms", "loop_decode_launch_ms", "prefill_chunks_per_iter",
    "decode_overlap_pct",
)
with open(os.path.join(_paths.REPO_ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def args(metric):
    with open(os.path.join(_paths.BENCH_DIR, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "stats_delta_ratio"
    return spec["args"]


def test_the_reader_of_two_counters_returns_nothing_not_zero_on_missing_input():
    # a program without the counters (the parent commit), at either end of the window; a divisor that did not move
    old, new = {"steps": 4}, {"steps": 9, "loop": {"host_seconds": 1.0, "work_seconds": 4.0}}
    ratio = dict(num="loop.host_seconds", den="loop.work_seconds", scale=100.0)
    assert stats_delta_ratio.read({"stats_start": old, "stats_end": old}, **ratio) is None
    assert stats_delta_ratio.read({"stats_start": old, "stats_end": new}, **ratio) is None
    assert stats_delta_ratio.read({"stats_start": None, "stats_end": new}, **ratio) is None
    assert stats_delta_ratio.read({"stats_start": new, "stats_end": new}, **ratio) is None
    later = {"loop": {"host_seconds": 1.5, "work_seconds": 14.0}}
    assert stats_delta_ratio.read({"stats_start": new, "stats_end": later}, **ratio) == pytest.approx(5.0)


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """`/v1/stats` of a live engine before and after one request of 40
    prompt tokens (chunks of 32 and 8 in buckets of 32 and 16) and 6 output
    tokens (5 decode steps), with a span sink set."""
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.observability import tracing
    from modal_tpu.serving.engine import ServingEngine

    tracing.configure(str(tmp_path_factory.mktemp("spans")))
    cfg = get_config("tiny")
    engine = ServingEngine(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=4, num_pages=25, page_size=16, pages_per_slot=8, prefill_chunk=32
    ).start()
    try:
        engine.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
        ctx = {"stats_start": engine.stats()}
        engine.submit(list(range(60, 100)), max_new_tokens=6).result(timeout=120)
        ctx["stats_end"] = engine.stats()
    finally:
        engine.stop()
    return ctx


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_a_loop_metric_s_own_file_reads_the_live_engine_and_is_silent_on_a_program_without_the_counter(window, metric):
    value = stats_delta_ratio.read(window, **args(metric))
    assert value is not None and value > 0, metric
    parent = {"steps": 3, "tokens_generated": 9}  # what the parent commit's `/v1/stats` has of these paths
    assert stats_delta_ratio.read({"stats_start": parent, "stats_end": parent}, **args(metric)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_the_decode_overlap_and_a_parent_s_stats_leave_it_silent(cell):
    """`decode_overlap_pct` names no cells and moves `serve_tokens_per_s`,
    so every cell lists it, those that later PRs add too; the program
    before PR 35 counts `steps` and no `loop.steps_overlapped`: the metric
    is then left out of the line, never a 0."""
    import run as bench_run

    loaded = bench_run.load_cell(_paths.REPO_ROOT, cell)
    entry = loaded["per_layer"]["decode_overlap_pct"]
    assert "workloads" not in entry and entry["moves"] in loaded["end_to_end"] and entry["layer"] == "engine loop"
    assert args("decode_overlap_pct") == {"num": "loop.steps_overlapped", "den": "steps", "scale": 100.0}
    parent = {"stats_start": {"steps": 100, "loop": {"iterations": 90}}, "stats_end": {"steps": 1994, "loop": {"iterations": 1902}}}
    assert bench_run.read_layer_metric("decode_overlap_pct", parent) is None
    change = {"stats_start": {"steps": 100, "loop": {"steps_overlapped": 90}}, "stats_end": {"steps": 1994, "loop": {"steps_overlapped": 1864}}}
    assert bench_run.read_layer_metric("decode_overlap_pct", change) == pytest.approx(100.0 * 1774 / 1894)


def test_the_loop_metrics_against_a_hand_count(window):
    read = {m: stats_delta_ratio.read(window, **args(m)) for m in LOOP_METRICS}
    assert read["prefill_fill_pct"] == pytest.approx(100 * 40 / 48)
    grew = {k: window["stats_end"]["loop"][k] - window["stats_start"]["loop"][k] for k in ("iterations", "work_seconds", "host_seconds")}
    assert read["prefill_chunks_per_iter"] == pytest.approx(2 / grew["iterations"]) and 0 < read["prefill_chunks_per_iter"] <= 1
    # the two parts are of the whole, and the whole is under the time the loop had work
    assert read["loop_emit_pct"] + read["loop_prep_pct"] < read["loop_host_pct"] < 100
    assert read["loop_host_pct"] == pytest.approx(100 * grew["host_seconds"] / grew["work_seconds"])
    # a share and a time a call are the same seconds over two divisors
    assert read["loop_emit_ms"] * grew["iterations"] / 1000 == pytest.approx(read["loop_emit_pct"] * grew["work_seconds"] / 100)
    steps = window["stats_end"]["steps"] - window["stats_start"]["steps"]
    assert steps == 5
    assert (read["loop_prefill_launch_ms"] * 2 + read["loop_decode_launch_ms"] * steps) / 1000 == pytest.approx(
        read["loop_prep_pct"] * grew["work_seconds"] / 100
    )
    assert read["span_write_pct"] < read["loop_host_pct"]
    # of the 5 decode steps the first finds nothing in flight; none can be counted twice
    assert 0 < read["decode_overlap_pct"] <= 100 * 4 / 5
    # a phase missing at one end of the window silences the metric that sums it, and no other
    window = dict(window, stats_start={"loop": {"work_seconds": 0.0, "phase_seconds": {"emit": 0.0}}})
    assert stats_delta_ratio.read(window, **args("loop_prep_pct")) is None and stats_delta_ratio.read(window, **args("loop_emit_pct")) > 0
