"""The FLOP and byte counts against hand-worked values, for both
configurations, and the benchmark's data files against each other."""

import json
import os
import re

import pytest

from . import _paths
from kernels import counts

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def config(name):
    with open(os.path.join(_paths.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


MISTRAL, YI = config("mistral-7b-v0.3-serve-1chip"), config("yi-1.5-6b-serve-1chip")

# by hand. Mistral: attention 4096*4096*2 + 2*4096*1024 = 41,943,040; FFN 3*4096*14336 = 176,160,768;
# layer 218,103,808. Yi: 4096*4096*2 + 2*4096*512 = 37,748,736; 3*4096*11008 = 135,266,304; layer 173,015,040.
HAND = {
    "mistral": dict(cfg=MISTRAL, layer=218_103_808, params=16 * 218_103_808 + 2 * 4096 * 32768 + 33 * 4096,
                    kv_token=2 * 8 * 128 * 2 * 16, head=2 * 4096 * 32768),
    "yi": dict(cfg=YI, layer=173_015_040, params=16 * 173_015_040 + 2 * 4096 * 64000 + 33 * 4096,
               kv_token=2 * 4 * 128 * 2 * 16, head=2 * 4096 * 64000),
}


@pytest.mark.parametrize("model", sorted(HAND))
def test_parameters_and_cache_bytes_match_the_hand_count(model):
    h = HAND[model]
    assert counts.layer_matmul_params(h["cfg"]) == h["layer"]
    assert counts.param_count(h["cfg"]) == h["params"]
    assert counts.kv_bytes_per_token(h["cfg"]) == h["kv_token"]
    assert counts.head_flops(h["cfg"]) == h["head"]


def test_the_sizes_the_configurations_state():
    assert counts.param_count(MISTRAL) == pytest.approx(3.76e9, rel=0.005)
    assert counts.param_count(YI) == pytest.approx(3.29e9, rel=0.005)
    assert counts.kv_bytes_per_token(MISTRAL) == 64 * 1024 and counts.kv_bytes_per_token(YI) == 32 * 1024
    # the pools the engine geometry gives: pages of 16 tokens
    assert MISTRAL["engine"]["num_pages"] * 16 * 64 * 1024 == 3 * 2**30
    assert YI["engine"]["num_pages"] * 16 * 32 * 1024 == 3 * 2**30


@pytest.mark.parametrize("model", sorted(HAND))
def test_flops_of_a_prompt_and_of_a_generated_token(model):
    h = HAND[model]
    cfg, per_token = h["cfg"], 2 * h["layer"] * 16
    assert counts.token_matmul_flops(cfg) == per_token
    # 3 prompt tokens attend 1 + 2 + 3 = 6 keys: 4 flops x 32 heads x 128 x 16 layers a key
    assert counts.attention_flops(cfg, 0, 3) == 4 * 32 * 128 * 16 * 6
    assert counts.prefill_flops(cfg, 3) == 3 * per_token + 4 * 32 * 128 * 16 * 6 + h["head"]
    # a token generated at position 1000 attends 1001 keys
    assert counts.decode_flops(cfg, 1000) == per_token + 4 * 32 * 128 * 16 * 1001 + h["head"]


def test_bytes_a_decode_step_must_read():
    # Mistral, 32 slots with 450 live tokens each: weights (layers + head + norms) in bf16,
    # 32 embedding rows, 14,400 tokens of 64 KiB
    weights = (16 * 218_103_808 + 4096 * 32768 + 33 * 4096) * 2
    assert counts.decode_step_bytes(MISTRAL, 32, 32 * 450) == weights + 32 * 4096 * 2 + 32 * 450 * 65536
    assert counts.paged_decode_kernel_bytes(YI, 24, 24 * 3000) == 24 * 3000 * 2048 + 2 * 24 * 32 * 128 * 2


def test_reduced_names_no_width_and_every_changed_key_is_listed():
    for cfg, published in ((MISTRAL, {"num_hidden_layers": 32, "max_position_embeddings": 32768}), (YI, {"num_hidden_layers": 32})):
        assert cfg["published"] == published and sorted(cfg["reduced"]) == sorted(published)
        for key in cfg["reduced"]:
            assert not re.search(r"(_dim|_rank|_size)$", key) and "head" not in key
        assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128 == cfg["derived"]["head_dim"]


def bench():
    with open(os.path.join(_paths.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract_s_shape():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]] + [w["name"] for w in b["workloads"]] + [c["name"] for c in b["configs"]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"])) and os.path.isfile(os.path.join(_paths.REPO_ROOT, c["file"]))
        assert config(c["name"])["reduced"] == c["reduced"] and config(c["name"])["source"] == c["source"]
    assert any("mfu" in m["name"] for m in b["per_layer"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 4)


def test_every_cell_finds_its_files_and_reports_what_it_must():
    import run

    b = bench()
    for w in b["workloads"]:
        cell = run.load_cell(_paths.REPO_ROOT, w["name"])
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        assert cell["traffic"]["loop"] in ("open", "closed")
        for name, metric in cell["per_layer"].items():
            assert metric["moves"] in cell["end_to_end"], (w["name"], name)


def test_a_later_serving_cell_is_one_traffic_file_and_one_entry_of_workloads(tmp_path):
    """As PR 26 added `mistral-7b.chat-saturated`; rehearsed on a cell the benchmark does not have: nothing that is there is edited."""
    import shutil

    import run

    shutil.copytree(os.path.join(_paths.BENCH_DIR, "configs"), tmp_path / "benchmark" / "configs")
    shutil.copytree(os.path.join(_paths.BENCH_DIR, "traffic"), tmp_path / "benchmark" / "traffic")
    with open(tmp_path / "benchmark" / "traffic" / "chat-steady.json") as f:
        mix = json.load(f)
    mix.update(loop="closed", closed={"clients": 128, "pool": 128}, drain_s=0)
    (tmp_path / "benchmark" / "traffic" / "chat-crowded.json").write_text(json.dumps(mix))
    b = bench()
    before = json.dumps({k: v for k, v in b.items() if k != "workloads"}, sort_keys=True)
    b["workloads"].append({"name": "mistral-7b.chat-crowded", "config": "mistral-7b-v0.3-serve-1chip",
                           "traffic": "chat-crowded", "chips": 1, "why": "the chat mix, closed loop of 128"})
    assert json.dumps({k: v for k, v in b.items() if k != "workloads"}, sort_keys=True) == before
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = run.load_cell(str(tmp_path), "mistral-7b.chat-crowded")
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    universal = {m["name"] for m in b["per_layer"] if "workloads" not in m and m["moves"] in ("serve_tokens_per_s", "setup_s")}
    assert set(cell["per_layer"]) == universal and {"serve_mfu_pct", "decode_step_ms", "kv_pages_high_water_pct"} <= universal
    assert cell["traffic"]["closed"]["clients"] == 128 and run.traffic.build(cell["traffic"], 1, 45, 32768).loop == "closed"
    # the cells that are there still report what they did
    assert set(run.load_cell(str(tmp_path), "mistral-7b.chat-steady")["end_to_end"]) == {"serve_tokens_per_s", "ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert set(run.load_cell(str(tmp_path), "yi-1.5-6b.longdoc-saturated")["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}


def test_each_per_layer_metric_is_a_file_of_its_own_that_names_a_reader():
    b = bench()
    files = sorted(f[:-5] for f in os.listdir(os.path.join(_paths.BENCH_DIR, "layer_metrics")))
    assert files == sorted(m["name"] for m in b["per_layer"])
    for m in b["per_layer"]:
        with open(os.path.join(_paths.BENCH_DIR, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
        assert os.path.isfile(os.path.join(_paths.BENCH_DIR, "readers", spec["reader"] + ".py"))
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_peaks_table_has_the_v5e_row_with_its_source():
    with open(os.path.join(_paths.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in row["source"]
