"""`prefill_attended_pct` (PR 27): how much of a slot's span the prefill
chunks' attention walked, from two counters of `/v1/stats`. Data only: the
metric's own file names the reader the benchmark has."""

import json
import os

import pytest

from . import _paths
from readers import stats_delta_ratio

import run


def spec():
    with open(os.path.join(_paths.BENCH_DIR, "layer_metrics", "prefill_attended_pct.json")) as f:
        return json.load(f)


def test_prefill_attended_pct_is_declared_as_its_file_says_and_names_no_cells():
    with open(os.path.join(_paths.REPO_ROOT, "BENCHMARK.json")) as f:
        declared = [m for m in json.load(f)["per_layer"] if m["name"] == "prefill_attended_pct"]
    assert declared == [{k: v for k, v in spec().items() if k not in ("reader", "args")}]
    assert declared[0] == {
        "name": "prefill_attended_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "jitted steps", "moves": "serve_tokens_per_s",
    }
    # so every cell that reports `serve_tokens_per_s` reports it
    for cell in ("mistral-7b.chat-steady", "yi-1.5-6b.longdoc-saturated", "mistral-7b.chat-saturated"):
        assert "prefill_attended_pct" in run.load_cell(_paths.REPO_ROOT, cell)["per_layer"]


def test_prefill_attended_pct_reads_a_ratio_from_two_snapshots_through_the_harness():
    # three chunks in the window: live prefixes of 128, 256 and 600 positions on rows of 8,192
    start = {"prefill_kv_attended": 5120, "prefill_kv_span": 81920, "steps": 40}
    end = {"prefill_kv_attended": 5120 + 512 + 512 + 1024, "prefill_kv_span": 81920 + 3 * 8192, "steps": 90}
    ctx = {"stats_start": start, "stats_end": end}
    assert spec()["reader"] == "stats_delta_ratio"
    assert run.read_layer_metric("prefill_attended_pct", ctx) == pytest.approx(100 * 2048 / 24576)
    assert stats_delta_ratio.read(ctx, **spec()["args"]) == run.read_layer_metric("prefill_attended_pct", ctx)


@pytest.mark.parametrize("missing", ["both ends", "the start", "the end", "no chunk in the window"])
def test_prefill_attended_pct_is_silent_where_the_counters_are_missing_or_did_not_move(missing):
    parent = {"steps": 3, "prefill_chunks": 2, "prefill_bucket_tokens": 48}  # the parent commit's `/v1/stats`
    ours = {**parent, "prefill_kv_attended": 1024, "prefill_kv_span": 16384}
    ctx = {
        "both ends": {"stats_start": parent, "stats_end": parent},
        "the start": {"stats_start": parent, "stats_end": ours},
        "the end": {"stats_start": ours, "stats_end": None},
        "no chunk in the window": {"stats_start": ours, "stats_end": ours},
    }[missing]
    assert run.read_layer_metric("prefill_attended_pct", ctx) is None
