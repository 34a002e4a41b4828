"""`stream_wakeups_per_token`: the engine's cross-thread wake-ups of the
streams' event loops (`/v1/stats` `loop.stream_wakeups`) over the tokens it
generated. Its file has its `BENCHMARK.json` entry key for key and is listed
in every cell; it reads its number from two recorded reads of `/v1/stats`,
and is left out of the line (nothing, never a 0) where the program lacks the
key, as the parent does. The same read on a live engine is in
tests/test_engine_stream_wakeups.py."""

import json
import os

from . import _paths
from readers import stats_delta_ratio

import run as bench_run

NAME = "stream_wakeups_per_token"

with open(os.path.join(_paths.REPO_ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def stats(steps: int, tokens: int, wakeups: int) -> dict:
    return {"steps": steps, "tokens_generated": tokens, "loop": {"iterations": steps, "stream_wakeups": wakeups}}


# two reads 45 s apart at the scale of `laguna-xs.2.reasoning-saturated`: 1,750 steps of 113 rows,
# each 8 blocks of 16 rows and a chunk's first token about every third step
RECORDED = {"stats_start": stats(1000, 110000, 9000), "stats_end": stats(2750, 308000, 9000 + 1750 * 8 + 600)}


def test_the_file_has_its_entry_key_for_key_and_is_listed_for_every_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    with open(os.path.join(_paths.BENCH_DIR, "layer_metrics", NAME + ".json")) as f:
        spec = json.load(f)
    assert {k: v for k, v in spec.items() if k not in ("reader", "args", "description")} == entry
    assert entry == {
        "name": NAME, "unit": "calls/token", "better": "lower", "source": "program_counter",
        "layer": "serving API", "moves": "serve_tokens_per_s",
    }
    assert spec["reader"] == "stats_delta_ratio" and spec["args"] == {"num": "loop.stream_wakeups", "den": "tokens_generated"}
    for cell in (w["name"] for w in BENCH["workloads"]):
        assert NAME in bench_run.load_cell(_paths.REPO_ROOT, cell)["per_layer"], cell


def test_it_reads_its_number_from_two_recorded_reads_of_v1_stats():
    want = (1750 * 8 + 600) / 198000
    assert bench_run.read_layer_metric(NAME, RECORDED) == want
    assert stats_delta_ratio.read(RECORDED, "loop.stream_wakeups", "tokens_generated") == want


def test_it_is_silent_on_a_parent_s_stats_that_lack_the_key_and_on_a_window_with_no_tokens():
    def parent(read: dict) -> dict:
        return {"steps": read["steps"], "tokens_generated": read["tokens_generated"], "loop": {"iterations": read["steps"]}}

    ctx = {"stats_start": parent(RECORDED["stats_start"]), "stats_end": parent(RECORDED["stats_end"])}
    assert bench_run.read_layer_metric(NAME, ctx) is None
    idle = {"stats_start": RECORDED["stats_end"], "stats_end": RECORDED["stats_end"]}
    assert bench_run.read_layer_metric(NAME, idle) is None
