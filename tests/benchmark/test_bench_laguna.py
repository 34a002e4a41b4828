"""The Laguna-XS.2 configuration's own files: its counts at hand-reckoned
sizes and against what the program computes, its names and entries, the
catalog row it is cut from, the reference as the harness's child, the whole
run on the CPU at `tiny-laguna`. (That admitting it changed no entry the
benchmark had is test_bench_accepted.py's, for every configuration at once.)
The plain reference against the program (logits, weights, the shares of the
experts) is in tests/test_serving_two_pools_reference.py."""

import json
import os
import re
import subprocess
import sys

import pytest

from . import _paths
from ._drive import alter_a_token, skip_the_chip_look
import run as bench_run
from benchlib import reference_laguna
from kernels import counts_laguna as counts

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL, CONFIG = "laguna-xs.2.reasoning-saturated", "laguna-xs.2-serve-1chip"
LAGUNAROOT = os.path.join(_paths.FIXTURES, "lagunaroot")


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


REAL = load(_paths.BENCH_DIR, "configs", CONFIG + ".json")
TINY = load(LAGUNAROOT, "benchmark", "configs", "tiny-laguna.json")


def test_the_reference_imports_nothing_of_the_program_and_carries_the_three_readings():
    with open(reference_laguna.__file__) as f:
        text = f.read()
    assert "modal_tpu" not in text.replace("the program", "")
    # the three readings the catalog row does not settle: word for word in the configuration and in the reference's head
    head = " ".join(text.split('"""')[1].split())
    readings = [a for a in REAL["assumed"] if a[:3] in ("(1)", "(2)", "(3)")]
    assert len(readings) == 3
    for reading in readings:
        assert " ".join(reading.split()) in head, reading[:40]


# -- the counts, by hand -------------------------------------------------------------
# q, o at the layer's head count: 2048 x H x 128 each; the gate, a scalar a head: 2048 x H; k + v: 2 x 2048 x 8 x 128
FULL_ATTN, SWA_ATTN = 2 * 2048 * 48 * 128 + 2048 * 48 + 2 * 2048 * 8 * 128, 2 * 2048 * 64 * 128 + 2048 * 64 + 2 * 2048 * 8 * 128
EXPERT, SHARED, DENSE, ROUTER = 3 * 2048 * 512, 3 * 2048 * 512, 3 * 2048 * 8192, 2048 * 256


def test_the_parameters_the_issue_reckons():
    assert (FULL_ATTN, SWA_ATTN, EXPERT, DENSE, ROUTER) == (29_458_432, 37_879_808, 3_145_728, 50_331_648, 524_288)
    assert counts.attention_params(REAL, 48) == FULL_ATTN and counts.attention_params(REAL, 64) == SWA_ATTN
    assert counts.expert_params(REAL) == EXPERT and counts.shared_params(REAL) == SHARED
    # a token meets its 8 routed experts and the shared one, the router whole, the gate's projection
    by_hand = FULL_ATTN + DENSE + 3 * (SWA_ATTN + ROUTER + 9 * EXPERT) + (FULL_ATTN + ROUTER + 9 * EXPERT)
    assert counts.token_matmul_params(REAL) == by_hand
    # held: 256 experts a layer, the vocabulary whole: 3.87 B = 7.74 GB
    held = 2 * FULL_ATTN + 3 * SWA_ATTN + DENSE + 4 * (ROUTER + SHARED + 256 * EXPERT) + 2 * 2048 * 100_352 + 11 * 2048
    assert REAL["derived"]["parameters_held"] == held == 3_869_857_792


def test_a_sliding_layer_counts_the_window_and_each_layer_its_own_heads():
    assert counts.keys_seen(REAL, "sliding_attention", 5000) == 512 and counts.keys_seen(REAL, "sliding_attention", 50) == 51
    assert counts.keys_seen(REAL, "full_attention", 5000) == 5001
    full, swa = 4 * 48 * 128, 4 * 64 * 128  # QK^T and PV over 128, 2 flops a multiply-add
    assert counts.attention_flops(REAL, 5000, 1) == 2 * full * 5001 + 3 * swa * 512
    assert counts.attention_flops(REAL, 0, 3) == (2 * full + 3 * swa) * 6  # 1 + 2 + 3 keys, inside the window
    head = 2 * 2048 * 100_352
    assert counts.decode_flops(REAL, 5000) == 2 * counts.token_matmul_params(REAL) + 2 * full * 5001 + 3 * swa * 512 + head
    assert counts.prefill_flops(REAL, 3) == 3 * 2 * counts.token_matmul_params(REAL) + (2 * full + 3 * swa) * 6 + head


@pytest.mark.parametrize("slots,live", [(1, 1000), (100, 60_000), (128, 200_000)])
def test_a_decode_step_s_bytes_read_the_experts_its_rows_touch_and_never_more_than_all(slots, live):
    touched = 256 * (1 - (1 - 8 / 256) ** slots)  # 8 of 256 at one slot, 245 at a hundred
    assert touched == pytest.approx({1: 8.0, 100: 245.3, 128: 251.6}[slots], rel=0.002) and touched <= 256
    assert counts.experts_touched(REAL, slots) == pytest.approx(touched, rel=1e-12) and counts.experts_touched(REAL, 10**6) == 256
    fixed = 2 * FULL_ATTN + 3 * SWA_ATTN + DENSE + 4 * (ROUTER + SHARED) + 5 * 2 * 2048 + 2048 + 2048 * 100_352
    window_positions = slots * min(live / slots, 512)
    kv = (2 * live + 3 * window_positions) * 2 * 8 * 128 * 2
    want = 2 * (fixed + 4 * touched * EXPERT) + slots * 2048 * 2 + kv
    assert counts.decode_step_bytes(REAL, slots, live) == pytest.approx(want, rel=1e-12)
    # the kernels: one layer's live keys and values, the queries in and the outputs out, at the layer's heads
    assert counts.full_decode_kernel_bytes(REAL, slots, live) == live * 4096 + 2 * slots * 48 * 128 * 2
    assert counts.swa_decode_kernel_bytes(REAL, slots, live) == window_positions * 4096 + 2 * slots * 64 * 128 * 2
    assert counts.full_decode_kernel_flops(REAL, slots, live) == 4 * 48 * 128 * live
    assert counts.swa_decode_kernel_flops(REAL, slots, live) == 4 * 64 * 128 * window_positions


# one layer of each kind alone: (attention, FFN, query heads) as the published lists name them
ONE_LAYER = {
    "full-gqa6-dense": ("full_attention", "dense", 12),
    "sliding-gqa8-experts": ("sliding_attention", "sparse", 16),
    "full-gqa6-experts": ("full_attention", "sparse", 12),
}


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
@pytest.mark.parametrize("kind", sorted(ONE_LAYER))
def test_the_counts_keep_the_rule_needed_is_no_more_than_computed(kind, program):
    """`counts_laguna` at `tiny-laguna` against XLA's own count of the
    program's jitted step on the CPU, a layer kind at a time (XLA counts a
    scanned group's body once, so a stack would read short): what the model
    needs can only fall short of what is computed (padding to a tile, the
    span-wide gather path, idle rows), so a share of a peak worked out from it
    cannot pass 100."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config, init_params

    attn, ffn, heads = ONE_LAYER[kind]
    one = {**TINY, "num_hidden_layers": 1, "layer_types": [attn], "mlp_layer_types": [ffn], "num_attention_heads_per_layer": [heads]}
    cfg = get_config("tiny-laguna", n_layers=1, attn_pattern=(attn,), ffn_pattern=(ffn,), n_heads_per_layer=(heads,))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    slots, context, chunk = 8, 40, 32
    cache = jax.eval_shape(lambda: paged_kv.PagedKVCache.create(cfg, slots, 64, 4, 16, window_num_pages=40))
    scalar, ids = jax.ShapeDtypeStruct((), jnp.int32), lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
    if program == "paged_decode_step":
        lowered = paged_kv.paged_decode_step.lower(params, cfg, ids(slots), cache, jax.ShapeDtypeStruct((slots,), jnp.bool_))
        needed = slots * counts.decode_flops(one, context)
    else:
        lowered = paged_kv.paged_prefill.lower(params, cfg, ids(chunk), scalar, cache, scalar, scalar)
        needed = counts.prefill_flops(one, chunk)
    computed = lowered.compile().cost_analysis()["flops"]
    assert 0 < needed <= computed, (needed, computed)
    assert computed < 4 * needed, (needed, computed)  # and the program is not several times the model


# -- names, entries, and what was there ------------------------------------------------

NEW_METRICS = {
    "moe_rows_per_expert", "moe_experts_touched_pct", "kv_window512_pages_high_water_pct",
    "full_gqa6_decode_attention_roofline", "swa512_decode_attention_roofline",
}


def test_the_cell_s_files_and_names_are_found_before_anything_boots():
    cell = bench_run.load_cell(_paths.REPO_ROOT, CELL)  # check_names: reference, counts, bytes_fn / flops_fn, calls_key
    assert cell["reference"] == os.path.join(_paths.BENCH_DIR, "benchlib", "reference_laguna.py")
    assert NEW_METRICS <= set(cell["per_layer"])
    # another configuration's metrics are not this cell's, and the universal ones are
    assert not {"paged_decode_roofline", "moe_tokens_per_expert", "full_decode_attention_roofline"} & set(cell["per_layer"])
    assert {"serve_mfu_pct", "decode_step_hbm_pct", "device_idle_pct", "loop_host_pct", "prefill_attended_pct"} <= set(cell["per_layer"])
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    bench = cell["bench"]
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        assert NAME.match(entry["name"]) and len(entry.get("why", "x")) <= 200
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = load(_paths.BENCH_DIR, "layer_metrics", name + ".json")
        assert {k: v for k, v in spec.items() if k not in ("reader", "args")} == entry and entry["workloads"] == [CELL]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (REAL["full_attention_layers"], REAL["swa_attention_layers"]) == (2, 3)
    spec = cell["traffic"]
    assert spec["loop"] == "closed" and spec["closed"] == {"clients": 192, "pool": 192} and spec["drain_s"] == 0
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 2048}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 1024}


def test_the_configuration_is_the_catalog_row_cut_as_its_file_says():
    bench = load(_paths.REPO_ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == REAL["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert conf["source"] == REAL["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert REAL["published"] == {"num_hidden_layers": 40, "max_position_embeddings": 262144}
    # no width is cut, every expert is held and the vocabulary is whole: these are the published ones
    widths = dict(hidden_size=2048, num_attention_heads=48, num_key_value_heads=8, head_dim=128, sliding_window=512,
                  intermediate_size=8192, moe_intermediate_size=512, shared_expert_intermediate_size=512,
                  num_experts=256, num_experts_per_tok=8, vocab_size=100352, moe_routed_scaling_factor=2.5)
    assert {k: REAL[k] for k in widths} == widths
    assert len(REAL["layer_types"]) == len(REAL["mlp_layer_types"]) == len(REAL["num_attention_heads_per_layer"]) == 40  # kept whole; the first 5 run
    assert REAL["rope_parameters"]["full_attention"]["attention_factor"] == 1.4158883083359672
    assert REAL["engine"]["max_waiting"] > 192 - 128  # no caller is refused; and the keyword an llm_service before this PR refuses at once
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reasoning-saturated", 1)
    # the program takes every key the file maps, and builds the cut: the preset's own
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config
    from modal_tpu.models.paged_kv import default_window_num_pages

    arguments = incontainer.service_arguments(REAL, 1)
    cfg = get_config(arguments["model"])
    assert cfg == get_config({"name": "laguna-xs.2", "n_layers": 5, "max_seq_len": 8192})
    assert cfg.experts_held == (0, 256) and cfg.param_count() == REAL["derived"]["parameters_held"]
    # the window pool no admission pattern exhausts: 128 slots x 33 pages, a chunk's 16 more, the scratch page
    assert arguments["window_num_pages"] == default_window_num_pages(cfg, 128, 16, 256) == 4241


def test_the_tiny_fixture_is_the_same_description_and_maps_the_same_keys():
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config

    assert TINY["program_keys"] == REAL["program_keys"] and set(TINY["rope_parameters"]) == set(REAL["rope_parameters"])
    assert get_config(incontainer.service_arguments(TINY, 1)["model"]) == get_config("tiny-laguna")


# -- the reference as the harness's child, and the whole run on the CPU ------------------


@pytest.mark.parametrize("control", ["", "fp8"])
def test_the_reference_child_runs_at_tiny_and_the_control_reads_apart(tmp_path, control):
    """`python reference_laguna.py job.json out.json`, the contract of
    benchmark/README.md, on streams the reference itself would serve (greedy
    from its own float32 logits): gaps of zero, and under the control the
    float8 pass's choices lie far from the reference's best."""
    import numpy as np

    ref = reference_laguna.Reference(TINY, 9, pad_to=64)
    rng = np.random.default_rng(4)
    requests = []
    for index, n_prompt in enumerate((7, 19)):
        tokens = [int(t) for t in rng.integers(0, 512, size=n_prompt)]
        served = []
        for _ in range(6):
            served.append(int(np.argmax(ref.logits(tokens + served, [len(tokens) + len(served) - 1])[0])))
        requests.append({"index": index, "prompt": tokens, "tokens": served})
    job = {"config": TINY, "seed": 9, "control": control, "require_platform": "cpu", "pad_to": 64, "requests": requests}
    job_path, out_path = tmp_path / "job.json", tmp_path / "out.json"
    job_path.write_text(json.dumps(job))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _paths.BENCH_DIR}
    proc = subprocess.run([sys.executable, reference_laguna.__file__, str(job_path), str(out_path)], env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(out_path.read_text())
    assert out["tokens_compared"] == 12 and out["requests_compared"] == 2 and out["platform"] == "cpu"
    assert out["logit_gap_max"] == 0.0 and out["logit_gap_mean"] == 0.0
    if control:
        assert out["control_logit_gap_max"] > 0.01 and out["control_logit_gap_mean"] > 0.001
    else:
        assert "control_logit_gap_max" not in out


@pytest.fixture
def bench_env(supervisor, tmp_path, monkeypatch):
    for key in ("MODAL_TPU_STATE_DIR", "JAX_COMPILATION_CACHE_DIR", "PYTHONPATH"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jit_cache"))
    skip_the_chip_look(bench_run, monkeypatch.setattr)
    return str(tmp_path / "bench_state")


@pytest.mark.parametrize("case", ["sound", "token_altered"])
def test_the_whole_run_on_the_cpu_at_tiny_laguna(bench_env, case, monkeypatch):
    """App -> llm_service -> container -> POST /v1/generate -> the
    configuration's own reference in a child, with `tiny-laguna` behind the
    published keys: a sound run reads correct, one whose program alters a
    token does not."""
    argv = ["--workload", "tiny-laguna.closed", "--seed", str(2**31 + 19), "--seconds", "4", "--trace", "0", "--boot-timeout", "120"]
    if case == "token_altered":
        import modal_tpu.serving

        real = modal_tpu.serving.llm_service
        monkeypatch.setattr(modal_tpu.serving, "llm_service", lambda *a, **kw: alter_a_token(real(*a, **kw)))
    children, run_child = [], bench_run.run_child
    monkeypatch.setattr(bench_run, "run_child", lambda argv, timeout_s, env=None: children.append(argv[0]) or run_child(argv, timeout_s, env))
    line = bench_run.measure(bench_run.parse(argv), root=LAGUNAROOT, state_root=bench_env)
    assert children == [os.path.join(_paths.BENCH_DIR, "benchlib", "reference_laguna.py")]
    assert line["attempted"] >= 5 and line["failed"] == 0 and set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["compared"]["bad_streams"] == {"value": 0, "limit": 0}
    gap = line["compared"]["logit_gap_max"]
    if case == "sound":
        assert line["correct"] is True and gap["value"] <= gap["limit"] and line["reference"]["tokens_compared"] > 20
    else:
        assert line["correct"] is False and gap["value"] > 5 * gap["limit"]
