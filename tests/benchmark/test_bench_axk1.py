"""The A.X-K1 configuration's own files: its counts at hand-reckoned sizes
and against what the program computes, its names and entries, the catalog row
it is cut from, the reference as the harness's child, the whole run on the
CPU at `tiny-axk1`. (That admitting it changed no entry the benchmark had is
test_bench_accepted.py's, for every configuration at once.) The plain
reference against the program (logits, weights, the shares of the experts,
the group rule, the absorbed form) is in
tests/test_serving_two_pools_reference.py."""

import json
import os
import re
import subprocess
import sys

import pytest

from . import _paths
from ._drive import alter_a_token, skip_the_chip_look
import run as bench_run
from benchlib import reference_axk1
from kernels import counts_axk1 as counts

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL, CONFIG = "a.x-k1.longctx-reasoning-saturated", "a.x-k1-serve-1chip-ep16"
AXK1ROOT = os.path.join(_paths.FIXTURES, "axk1root")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


REAL = load(_paths.BENCH_DIR, "configs", CONFIG + ".json")
TINY = load(AXK1ROOT, "benchmark", "configs", "tiny-axk1.json")


def test_the_reference_imports_nothing_of_the_program_and_carries_the_four_readings():
    with open(reference_axk1.__file__) as f:
        text = f.read()
    assert "modal_tpu" not in text.replace("the program", "")
    # the four readings the catalog row does not settle: word for word in the configuration and in the reference's head
    head = " ".join(text.split('"""')[1].split())
    readings = [a for a in REAL["assumed"] if a[:3] in ("(1)", "(2)", "(3)", "(4)")]
    assert len(readings) == 4
    for reading in readings:
        assert " ".join(reading.split()) in head, reading[:40]


# -- the counts, by hand -------------------------------------------------------------
# W_DQ 7168 x 1536, W_UQ 1536 x 64 x 192, W_DKV 7168 x 576, kv_b_proj 512 x 64 x 256, W_O 8192 x 7168
ATTN = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
EXPERT, DENSE, ROUTER = 3 * 7168 * 2048, 3 * 7168 * 18432, 7168 * 192


def test_the_parameters_the_issue_reckons():
    assert (ATTN, EXPERT, DENSE, ROUTER) == (101_122_048, 44_040_192, 396_361_728, 1_376_256)
    assert counts.attention_params(REAL) == ATTN and counts.expert_params(REAL) == counts.shared_params(REAL) == EXPERT
    # a token under even routing meets 8 x 12 / 192 = half a held expert a layer, the shared one and the router whole
    by_hand = 7 * ATTN + DENSE + 6 * (ROUTER + EXPERT + EXPERT / 2)
    assert counts.token_matmul_params(REAL) == by_hand
    # held: 12 experts and the shared one a layer, 20,480 rows of embedding and head, every norm: 4.84 B = 9.68 GB
    held = 7 * ATTN + DENSE + 6 * (ROUTER + 13 * EXPERT) + 2 * 20480 * 7168 + 7 * (2 * 7168 + 1536 + 512) + 7168
    assert REAL["derived"]["parameters_held"] == held == 4_841_331_712
    whole = 61 * ATTN + DENSE + 60 * (ROUTER + 193 * EXPERT) + 2 * 163_840 * 7168 + 61 * (2 * 7168 + 1536 + 512) + 7168
    assert REAL["derived"]["parameters_whole"] == whole and whole == pytest.approx(519.0e9, rel=1e-4)  # the card's number
    assert REAL["derived"]["kv_bytes_per_token"] == 7 * 576 * 2 == 8064 == 7 * counts.kv_bytes_per_token(REAL)
    assert REAL["derived"]["softmax_scale"] == pytest.approx(reference_axk1.rotary_rule(reference_axk1.model_shapes(REAL))[2], rel=1e-5)


def test_decode_counts_the_absorbed_form_and_prefill_the_plain_one():
    absorbed, plain = 2 * 64 * (576 + 512), 2 * 64 * (192 + 128)  # operations a (query, cached row) pair a layer
    assert (absorbed, plain) == (139_264, 40_960)
    head = 2 * 7168 * 20480
    assert counts.decode_flops(REAL, 5000) == 2 * counts.token_matmul_params(REAL) + 7 * absorbed * 5001 + head
    assert counts.prefill_flops(REAL, 3) == 3 * 2 * counts.token_matmul_params(REAL) + 7 * plain * 6 + head
    # the kernel: a row of 1,152 B meets 64 heads: 121 operations a byte, under the v5e's ridge of 240
    assert counts.mla_decode_kernel_flops(REAL, 50, 175_000) == absorbed * 175_000
    assert counts.mla_decode_kernel_bytes(REAL, 50, 175_000) == 175_000 * 1152 + 50 * 64 * (576 + 512) * 2
    assert 120 < absorbed / 1152 < 122


@pytest.mark.parametrize("slots,live", [(1, 1000), (50, 175_000), (64, 400_000)])
def test_a_decode_step_s_bytes_read_the_held_experts_its_rows_touch_and_never_more_than_twelve(slots, live):
    hit = 12 * (1 - (1 - 8 / 192) ** slots)
    assert hit == pytest.approx({1: 0.5, 50: 10.57, 64: 11.21}[slots], rel=0.002) and hit <= 12
    assert counts.experts_hit(REAL, slots) == pytest.approx(hit, rel=1e-12) and counts.experts_hit(REAL, 10**6) == 12
    fixed = 7 * ATTN + DENSE + 6 * (ROUTER + EXPERT) + 7 * (2 * 7168 + 1536 + 512) + 7168 + 7168 * 20480
    want = 2 * (fixed + 6 * hit * EXPERT) + slots * 7168 * 2 + live * 8064
    assert counts.decode_step_bytes(REAL, slots, live) == pytest.approx(want, rel=1e-12)


ONE_LAYER = {"latent-dense": 1, "latent-grouped-experts": 0}  # first_k_dense_replace of a model of one layer


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
@pytest.mark.parametrize("kind", sorted(ONE_LAYER))
def test_the_counts_keep_the_rule_needed_is_no_more_than_computed(kind, program):
    """`counts_axk1` at `tiny-axk1` against XLA's own count of the program's
    jitted step on the CPU, a layer kind at a time (XLA counts a scanned
    group's body once): what the model needs can only fall short of what is
    computed (every held expert over every row, a prefix block's keys and
    values rebuilt a chunk, the span-wide gather path), so a share of a peak
    worked out from it cannot pass 100."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config, init_params

    one = {**TINY, "num_hidden_layers": 1, "first_k_dense_replace": ONE_LAYER[kind]}
    cfg = get_config("tiny-axk1", n_layers=1, ffn_pattern=(1 - ONE_LAYER[kind],))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    slots, context, chunk = 8, 40, 32
    cache = jax.eval_shape(lambda: paged_kv.PagedKVCache.create(cfg, slots, 64, 4, 16))
    scalar, ids = jax.ShapeDtypeStruct((), jnp.int32), lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
    if program == "paged_decode_step":
        lowered = paged_kv.paged_decode_step.lower(params, cfg, ids(slots), cache, jax.ShapeDtypeStruct((slots,), jnp.bool_))
        needed = slots * counts.decode_flops(one, context)
    else:
        lowered = paged_kv.paged_prefill.lower(params, cfg, ids(chunk), scalar, cache, scalar, scalar)
        needed = counts.prefill_flops(one, chunk)
    computed = lowered.compile().cost_analysis()["flops"]
    assert 0 < needed <= computed, (needed, computed)
    assert computed < 6 * needed, (needed, computed)  # and the program is not many times the model


# -- names, entries, and what was there ------------------------------------------------

NEW_METRICS = {"mla_decode_attention_roofline", "kv_latent_bytes_per_token", "moe_held_share_pct", "moe_rows_per_held_expert"}


def test_the_cell_s_files_and_names_are_found_before_anything_boots():
    cell = bench_run.load_cell(_paths.REPO_ROOT, CELL)  # check_names: reference, counts, bytes_fn / flops_fn, calls_key
    assert cell["reference"] == os.path.join(_paths.BENCH_DIR, "benchlib", "reference_axk1.py")
    assert NEW_METRICS <= set(cell["per_layer"])
    # another configuration's metrics are not this cell's, and the universal ones are
    assert not {"paged_decode_roofline", "moe_tokens_per_expert", "full_decode_attention_roofline", "kv_window_pages_high_water_pct"} & set(cell["per_layer"])
    assert {"serve_mfu_pct", "decode_step_hbm_pct", "device_idle_pct", "loop_host_pct", "prefill_attended_pct", "decode_overlap_pct"} <= set(cell["per_layer"])
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}  # above capacity: on neither tail's list
    bench = cell["bench"]
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        assert NAME.match(entry["name"]) and 1 <= len(entry.get("why", "x")) <= 200, entry["name"]
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = load(_paths.BENCH_DIR, "layer_metrics", name + ".json")
        assert {k: v for k, v in spec.items() if k not in ("reader", "args", "description")} == entry and entry["workloads"] == [CELL]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
    roofline = load(_paths.BENCH_DIR, "layer_metrics", "mla_decode_attention_roofline.json")
    assert roofline["args"]["kernel"] == "paged_decode_attention_mla" and REAL[roofline["args"]["calls_key"]] == 7
    assert bench["per_layer"][-4:] == [next(m for m in bench["per_layer"] if m["name"] == n) for n in (
        "mla_decode_attention_roofline", "kv_latent_bytes_per_token", "moe_held_share_pct", "moe_rows_per_held_expert")]
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG and len(bench["workloads"]) == 6
    spec = cell["traffic"]
    assert spec["loop"] == "closed" and spec["closed"] == {"clients": 96, "pool": 96} and spec["drain_s"] == 0
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.5, "min": 1024, "max": 6144}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 640, "sigma": 0.4, "min": 256, "max": 1024}
    assert spec["trace"] == {"start_s": 24.0, "length_s": 6.0}
    # warm-up touches every prefill bucket up to the chunk: 16, 32, 64, 128, 256, and a prompt of more than one chunk
    from modal_tpu.models.paged_kv import prefill_bucket

    chunk = REAL["engine"]["prefill_chunk"]
    assert {prefill_bucket(min(n, chunk), chunk) for n in spec["warmup"]["prompt_tokens"]} == {16, 32, 64, 128, 256}
    assert max(spec["warmup"]["prompt_tokens"]) > chunk


def test_the_configuration_is_the_catalog_row_cut_as_its_file_says():
    bench = load(_paths.REPO_ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == REAL["reduced"] == ["num_hidden_layers", "max_position_embeddings", "n_routed_experts_held", "vocab_size"]
    assert conf["source"] == REAL["source"] == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    assert REAL["published"] == {"num_hidden_layers": 61, "max_position_embeddings": 131072, "n_routed_experts_held": 192, "vocab_size": 163840}
    if os.path.isfile(CATALOG):  # every number of the row under the same key, but the keys the file lists as reduced
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
        assert row["source_url"] == REAL["source"]
        differ = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert differ == {"num_hidden_layers", "max_position_embeddings", "vocab_size"}, differ
    # no width is cut, the router keeps its 192 outputs, its groups and its 8 experts a token: these are the published ones
    widths = dict(hidden_size=7168, num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048, n_routed_experts=192,
                  num_experts_per_tok=8, n_group=8, topk_group=4, n_shared_experts=1, routed_scaling_factor=2.5)
    assert {k: REAL[k] for k in widths} == widths
    assert (REAL["n_routed_experts_held"], REAL["vocab_size"] * 8, REAL["num_hidden_layers"]) == (12, 163840, 1 + 6)
    assert REAL["engine"]["requires"] == ["latent_kv", "router_groups"]  # the keyword an llm_service before this PR refuses at once
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx-reasoning-saturated", 1)
    # the program takes every key the file maps, and builds the cut: the preset's own
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config
    from modal_tpu.serving.service import MECHANISMS

    arguments = incontainer.service_arguments(REAL, 1)
    cfg = get_config(arguments["model"])
    assert cfg == get_config({"name": "a.x-k1", "n_layers": 7, "max_seq_len": 8192, "vocab_size": 20480, "n_experts_held": 12})
    assert cfg.experts_held == (0, 12) and cfg.param_count() == REAL["derived"]["parameters_held"]
    assert get_config("a.x-k1").param_count() == REAL["derived"]["parameters_whole"]
    assert set(arguments["requires"]) <= set(MECHANISMS) and arguments["max_slots"] == 64 and arguments["prefix_cache"] is False
    # 20,480 usable pages of 16 = 327,680 tokens; a slot's row of 512 pages holds the 8,192 positions
    assert (arguments["num_pages"] - 1) * 16 == 327_680 and cfg.max_seq_len == 512 * 16


def test_the_tiny_fixture_is_the_same_description_and_maps_the_same_keys():
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config

    assert TINY["program_keys"] == REAL["program_keys"] and set(TINY["rope_scaling"]) == set(REAL["rope_scaling"])
    assert {k for k in REAL if k not in TINY} <= {"model", "source", "reduced", "published", "derived", "assumed", "deployment", "engine_note"}
    assert get_config(incontainer.service_arguments(TINY, 1)["model"]) == get_config("tiny-axk1")


# -- the reference as the harness's child, and the whole run on the CPU ------------------


@pytest.mark.parametrize("control", ["", "fp8"])
def test_the_reference_child_runs_at_tiny_and_the_control_reads_apart(tmp_path, control):
    """`python reference_axk1.py job.json out.json`, the contract of
    benchmark/README.md, on streams the reference itself would serve (greedy
    from its own float32 logits): gaps of zero, and under the control the
    float8 pass's choices lie far from the reference's best."""
    import numpy as np

    ref = reference_axk1.Reference(TINY, 9, pad_to=64)
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
    proc = subprocess.run([sys.executable, reference_axk1.__file__, str(job_path), str(out_path)], env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(out_path.read_text())
    assert out["tokens_compared"] == 12 and out["requests_compared"] == 2 and out["platform"] == "cpu"
    assert out["logit_gap_max"] == 0.0 and out["logit_gap_mean"] == 0.0
    if control:
        assert out["control_logit_gap_max"] > 0.004 and out["control_logit_gap_mean"] > 0.0004  # 12 tokens: 0.0082 the widest
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
def test_the_whole_run_on_the_cpu_at_tiny_axk1(bench_env, case, monkeypatch):
    """App -> llm_service (with `requires`) -> container -> POST /v1/generate
    -> the configuration's own reference in a child, with `tiny-axk1` behind
    the published keys: a sound run reads correct, one whose program alters a
    token does not."""
    argv = ["--workload", "tiny-axk1.closed", "--seed", str(2**31 + 19), "--seconds", "4", "--trace", "0", "--boot-timeout", "120"]
    if case == "token_altered":
        import modal_tpu.serving

        real = modal_tpu.serving.llm_service
        monkeypatch.setattr(modal_tpu.serving, "llm_service", lambda *a, **kw: alter_a_token(real(*a, **kw)))
    children, run_child = [], bench_run.run_child
    monkeypatch.setattr(bench_run, "run_child", lambda argv, timeout_s, env=None: children.append(argv[0]) or run_child(argv, timeout_s, env))
    line = bench_run.measure(bench_run.parse(argv), root=AXK1ROOT, state_root=bench_env)
    assert children == [os.path.join(_paths.BENCH_DIR, "benchlib", "reference_axk1.py")]
    assert line["attempted"] >= 5 and line["failed"] == 0 and set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["compared"]["bad_streams"] == {"value": 0, "limit": 0}
    gap = line["compared"]["logit_gap_max"]
    if case == "sound":
        assert line["correct"] is True and gap["value"] <= gap["limit"] and line["reference"]["tokens_compared"] > 20
    else:
        assert line["correct"] is False and gap["value"] > 5 * gap["limit"]


def test_a_program_without_the_mechanisms_refuses_the_cell_in_the_harness_process(monkeypatch):
    """What the parent commit does with this cell: `llm_service` is called in
    the harness process, and one that lacks a mechanism the configuration
    requires (or the keyword itself) raises there, before any container is
    asked for."""
    import modal_tpu
    from benchlib import incontainer
    from modal_tpu.serving import service

    monkeypatch.setattr(service, "MECHANISMS", ("window_kv", "routed_experts"))
    with pytest.raises(ValueError, match="has no 'latent_kv', 'router_groups'"):
        incontainer.build_service(modal_tpu.App("bench-refused"), REAL, 1)
