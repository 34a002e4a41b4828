"""The MiMo-V2-Flash configuration's own files: its counts at hand-reckoned
sizes, its names and entries, and the catalog row it is cut from. (That
admitting it changed no byte of a file the benchmark had is
test_bench_accepted.py's, for every configuration at once.) The plain
reference against the program (logits, weights, the shares of the experts)
is in tests/test_serving_two_pools_reference.py."""

import json
import os
import re

import pytest

from . import _paths
from benchlib import reference_mimo_v2
from kernels import counts_mimo_v2 as counts

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL, CONFIG = "mimo-v2-flash.mixed-saturated", "mimo-v2-flash-serve-1chip-ep16"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


REAL = load(_paths.BENCH_DIR, "configs", CONFIG + ".json")


def test_the_reference_imports_nothing_of_the_program():
    with open(reference_mimo_v2.__file__) as f:
        assert "modal_tpu" not in f.read().replace("the program", "")


# -- the counts, by hand -------------------------------------------------------------
# attention: q 4096 x 64 x 192 = 50,331,648; k+v a KV head 4096 x 320 = 1,310,720; o 64 x 128 x 4096 = 33,554,432
FULL_ATTN, SWA_ATTN = 50_331_648 + 4 * 1_310_720 + 33_554_432, 50_331_648 + 8 * 1_310_720 + 33_554_432
EXPERT, DENSE, ROUTER = 3 * 4096 * 2048, 3 * 4096 * 16384, 4096 * 256


def test_the_parameters_the_issue_reckons():
    assert (FULL_ATTN, SWA_ATTN, EXPERT, DENSE) == (89_128_960, 94_371_840, 25_165_824, 201_326_592)
    assert counts.attention_params(REAL, 0) == FULL_ATTN and counts.attention_params(REAL, 1) == SWA_ATTN
    assert counts.expert_params(REAL) == EXPERT
    # a token under even routing meets 8 x 16 / 256 = half an expert a layer
    by_hand = FULL_ATTN + DENSE + 5 * (SWA_ATTN + ROUTER + EXPERT / 2) + (FULL_ATTN + ROUTER + EXPERT / 2)
    assert counts.token_matmul_params(REAL) == by_hand
    assert REAL["derived"]["parameters_held"] == pytest.approx(3.43e9, rel=0.002)


def test_a_window_layer_counts_the_window_and_a_full_layer_the_context():
    assert counts.keys_seen(REAL, 1, 5000) == 128 and counts.keys_seen(REAL, 1, 50) == 51 and counts.keys_seen(REAL, 0, 5000) == 5001
    per_key = 2 * 64 * (192 + 128)  # QK^T over 192, PV over 128
    assert counts.attention_flops(REAL, 5000, 1) == per_key * (2 * 5001 + 5 * 128)
    # three prompt tokens: 1 + 2 + 3 keys in every layer (all inside the window)
    assert counts.attention_flops(REAL, 0, 3) == per_key * 6 * 7
    head = 2 * 4096 * 19072
    assert counts.decode_flops(REAL, 5000) == 2 * counts.token_matmul_params(REAL) + per_key * (2 * 5001 + 5 * 128) + head
    assert counts.prefill_flops(REAL, 3) == 3 * 2 * counts.token_matmul_params(REAL) + per_key * 42 + head


@pytest.mark.parametrize("slots,live", [(1, 1000), (100, 100_000)])
def test_a_decode_step_s_bytes_at_one_slot_and_at_a_hundred(slots, live):
    hit = 16 * (1 - (1 - 8 / 256) ** slots)  # 0.5 of 16 experts at one slot, 15.3 at a hundred
    assert hit == pytest.approx(0.5 if slots == 1 else 15.33, rel=0.002)
    fixed = 2 * FULL_ATTN + 5 * SWA_ATTN + DENSE + 6 * (ROUTER + 256) + 7 * 2 * 4096 + 4096 + 4096 * 19072
    window_positions = slots * min(live / slots, 128)
    kv = live * 2 * 4 * 320 * 2 + window_positions * 5 * 8 * 320 * 2
    want = 2 * (fixed + 6 * hit * EXPERT) + slots * 4096 * 2 + kv
    assert counts.decode_step_bytes(REAL, slots, live) == pytest.approx(want, rel=1e-12)
    # the kernels: one layer's live keys and values, the queries in and the outputs out
    q_and_out = slots * 64 * 320 * 2
    assert counts.full_decode_kernel_bytes(REAL, slots, live) == live * 4 * 320 * 2 + q_and_out
    assert counts.swa_decode_kernel_bytes(REAL, slots, live) == window_positions * 8 * 320 * 2 + q_and_out
    assert counts.full_decode_kernel_flops(REAL, slots, live) == 2 * 64 * 320 * live
    assert counts.swa_decode_kernel_flops(REAL, slots, live) == 2 * 64 * 320 * window_positions


# -- names, entries, and what was there ------------------------------------------------


def test_the_cell_s_files_and_names_are_found_before_anything_boots():
    import run

    cell = run.load_cell(_paths.REPO_ROOT, CELL)  # check_names: reference, counts, bytes_fn / flops_fn, calls_key
    assert cell["reference"] == os.path.join(_paths.BENCH_DIR, "benchlib", "reference_mimo_v2.py")
    new = {"moe_tokens_per_expert", "moe_local_share_pct", "kv_window_pages_high_water_pct",
           "full_decode_attention_roofline", "swa_decode_attention_roofline"}
    assert new <= set(cell["per_layer"]) and "paged_decode_roofline" not in cell["per_layer"]
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    bench = cell["bench"]
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        assert NAME.match(entry["name"]) and len(entry.get("why", "x")) <= 200
    for name in new:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = load(_paths.BENCH_DIR, "layer_metrics", name + ".json")
        assert {k: v for k, v in spec.items() if k not in ("reader", "args")} == entry and entry["workloads"] == [CELL]
    assert REAL["full_attention_layers"] == 2 and REAL["swa_attention_layers"] == 5


def test_the_configuration_is_the_catalog_row_cut_as_its_file_says():
    conf = next(c for c in load(_paths.REPO_ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == REAL["reduced"] == ["num_hidden_layers", "max_position_embeddings", "n_routed_experts_held", "vocab_size"]
    assert conf["source"] == REAL["source"]
    # no width is cut: these are the published ones
    widths = dict(hidden_size=4096, num_attention_heads=64, num_key_value_heads=4, swa_num_key_value_heads=8, head_dim=192,
                  v_head_dim=128, sliding_window=128, intermediate_size=16384, moe_intermediate_size=2048,
                  n_routed_experts=256, num_experts_per_tok=8)
    assert {k: REAL[k] for k in widths} == widths
    assert len(REAL["hybrid_layer_pattern"]) == len(REAL["moe_layer_freq"]) == 48  # kept whole; the first 7 run
    assert REAL["engine"]["window_num_pages"] > 0  # the keyword the parent's llm_service refuses at once
    # the program takes every key the file maps, and builds the cut
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config

    cfg = get_config(incontainer.service_arguments(REAL, 1)["model"])
    assert (cfg.n_layers, cfg.attn_pattern, cfg.ffn_pattern) == (7, (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1))
    assert cfg.experts_held == (0, 16) and cfg.vocab_size == 19072 and cfg.param_count() == REAL["derived"]["parameters_held"]
