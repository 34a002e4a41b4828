"""The LFM2-8B-A1B configuration's own files: its counts at hand-reckoned
sizes and against what the program computes, its names and entries, the
catalog row it is cut from, the reference as the harness's child, the whole
run on the CPU at `tiny-lfm2`. (That admitting it changed no entry the
benchmark had is test_bench_accepted.py's, for every configuration at once.)
The plain reference against the program through the cache (chunk boundaries,
slots joining, slot reuse, preemption, both decode paths) is in
tests/test_serving_conv_state.py."""

import json
import os
import re
import subprocess
import sys

import pytest

from . import _paths
from ._drive import alter_a_token, skip_the_chip_look
import run as bench_run
from benchlib import reference_lfm2
from kernels import counts_lfm2 as counts

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL, CONFIG = "lfm2-8b-a1b.longgen-saturated", "lfm2-8b-a1b-serve-1chip"
LFM2ROOT = os.path.join(_paths.FIXTURES, "lfm2root")
# the published config.json's keys (the catalog row this configuration is cut from), as the row gives them
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                    "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


REAL = load(_paths.BENCH_DIR, "configs", CONFIG + ".json")
TINY = load(LFM2ROOT, "benchmark", "configs", "tiny-lfm2.json")


def test_the_reference_imports_nothing_of_the_program_and_carries_the_readings():
    with open(reference_lfm2.__file__) as f:
        text = f.read()
    assert "modal_tpu" not in text.replace("the program", "")
    head = " ".join(text.split('"""')[1].split())
    for words in ("the head is untied", "add 1e-6", "[L, hidden_size]", "half-split"):
        assert words in head, words
    assert sum(a[:3] in ("(1)", "(2)", "(3)", "(4)", "(5)") for a in REAL["assumed"]) == 5


# -- the counts, by hand -------------------------------------------------------------
CONV = 3 * 2048 * 2048 + 3 * 2048 + 2048 * 2048  # W_in, the taps, W_out
ATTN = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048  # q, k, v, o (heads of 64: 32 and 8)
EXPERT, DENSE, ROUTER = 3 * 2048 * 1792, 3 * 2048 * 7168, 2048 * 32 + 32


def test_the_sizes_the_issue_reckons():
    assert (CONV, ATTN, EXPERT, DENSE) == (16_783_360, 10_485_760, 11_010_048, 44_040_192)
    assert counts.conv_params(REAL) == CONV and counts.attention_params(REAL) == ATTN and counts.expert_params(REAL) == EXPERT
    # held: 2 dense convolution layers, 9 expert convolution layers, 3 expert attention layers, embedding and head
    norms = 14 * 2 * 2048 + 3 * 2 * 64 + 2048
    held = 2 * (CONV + DENSE) + 9 * (CONV + ROUTER + 32 * EXPERT) + 3 * (ATTN + ROUTER + 32 * EXPERT) + 2 * 65536 * 2048 + norms
    assert counts.param_count(REAL) == REAL["derived"]["parameters_held"] == held == 4_801_295_104
    assert round(2 * held / 1e9, 2) == 9.60  # GB in bfloat16: 56% of the chip before any cache
    whole = counts.param_count(dict(REAL, num_hidden_layers=24))
    assert whole == REAL["derived"]["parameters_whole"] and whole - 65536 * 2048 == REAL["derived"]["parameters_whole_tied"]
    assert round(REAL["derived"]["parameters_whole_tied"] / 1e9, 2) == 8.34  # the published 8.3B
    # 3 attention layers x keys and values x 8 heads of 64 x 2 B; 11 convolution layers x 2 rows x 2048 x 2 B
    assert counts.kv_bytes_per_token(REAL) * counts.attention_layers(REAL) == 3 * 2 * 8 * 64 * 2 == 6144 == REAL["derived"]["kv_bytes_per_token"]
    assert counts.conv_state_bytes_per_slot(REAL) == 11 * 2 * 2048 * 2 == 90_112 == REAL["derived"]["conv_state_bytes_per_slot"]
    assert (REAL["attention_layers"], REAL["conv_layers"]) == (3, 11)


def test_decode_counts_the_attention_layers_only_and_the_state_of_each_slot():
    head = 2 * 2048 * 65536
    per_token = 2 * (2 * (CONV + DENSE) + 9 * (CONV + 2048 * 32 + 4 * EXPERT) + 3 * (ATTN + 2048 * 32 + 4 * EXPERT))
    assert counts.decode_flops(REAL, 5000) == per_token + 3 * 4 * 32 * 64 * 5001 + head
    assert counts.prefill_flops(REAL, 3) == 3 * per_token + 3 * 4 * 32 * 64 * 6 + head
    # the kernel: one layer's live keys and values at 2,048 B a position, 8,192 operations a position
    assert counts.gqa_decode_kernel_bytes(REAL, 192, 300_000) == 300_000 * 2048 + 2 * 192 * 32 * 64 * 2
    assert counts.gqa_decode_kernel_flops(REAL, 192, 300_000) == 8192 * 300_000
    # at 192 rows every one of the 32 experts is touched (1 - (7/8)^192 of them)
    assert counts.experts_touched(REAL, 192) == pytest.approx(32, rel=1e-10) and counts.experts_touched(REAL, 1) == pytest.approx(4)
    fixed = 2048 * 65536 + 2048 + 2 * (CONV + DENSE) + 9 * (CONV + ROUTER + 32 * EXPERT) + 3 * (ATTN + 128 + ROUTER + 32 * EXPERT) + 14 * 2 * 2048
    want = 2 * fixed + 192 * (2048 * 2 + 90_112) + 300_000 * 6144
    assert counts.decode_step_bytes(REAL, 192, 300_000) == pytest.approx(want, rel=1e-9)


# (layer_types, num_dense_layers): a convolution layer beside an attention layer, whose pool the step needs
LAYERS = {
    "conv-dense": (("conv", "full_attention"), 2), "attention-experts": (("full_attention",), 0),
    "conv-experts": (("conv", "full_attention"), 0),
}


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_the_counts_keep_the_rule_needed_is_no_more_than_computed(kind, program):
    """`counts_lfm2` at `tiny-lfm2` against XLA's own count of the program's
    jitted step on the CPU, a layer kind at a time (a group of one layer: XLA
    counts a scanned group's body once): what the model needs can only fall
    short of what is computed (every expert over every row, the span-wide
    gather path), so a share of a peak worked out from it cannot pass 100."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config, init_params

    types, dense = LAYERS[kind]
    one = {**TINY, "num_hidden_layers": len(types), "layer_types": list(types), "num_dense_layers": dense}
    cfg = get_config("tiny-lfm2", n_layers=len(types), attn_pattern=types, ffn_pattern=tuple(int(i >= dense) for i in range(len(types))))
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
    assert computed < 6 * needed, (needed, computed)


# -- names, entries, and the row -------------------------------------------------------

NEW_METRICS = {"kv_hybrid_bytes_per_token", "conv_state_bytes_per_slot", "gqa4_hd64_decode_attention_roofline", "moe_rows_per_expert_all32"}


def test_the_cell_s_files_and_names_are_found_before_anything_boots():
    cell = bench_run.load_cell(_paths.REPO_ROOT, CELL)  # check_names: reference, counts, bytes_fn / flops_fn, calls_key
    assert cell["reference"] == os.path.join(_paths.BENCH_DIR, "benchlib", "reference_lfm2.py")
    assert NEW_METRICS <= set(cell["per_layer"])
    assert not {"paged_decode_roofline", "mla_decode_attention_roofline", "kv_latent_bytes_per_token"} & set(cell["per_layer"])
    assert {"serve_mfu_pct", "decode_step_hbm_pct", "device_idle_pct", "decode_rows_per_step", "prefill_chunk_ms"} <= set(cell["per_layer"])
    assert set(cell["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}  # above capacity: on neither tail's list
    bench = cell["bench"]
    for entry in bench["configs"] + bench["workloads"] + bench["per_layer"]:
        assert NAME.match(entry["name"]) and 1 <= len(entry.get("why", "x")) <= 200, entry["name"]
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = load(_paths.BENCH_DIR, "layer_metrics", name + ".json")
        assert {k: v for k, v in spec.items() if k not in ("reader", "args", "description")} == entry and entry["workloads"] == [CELL]
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
    roofline = load(_paths.BENCH_DIR, "layer_metrics", "gqa4_hd64_decode_attention_roofline.json")
    assert roofline["args"]["kernel"] == "paged_decode_attention_full" and REAL[roofline["args"]["calls_key"]] == 3
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    spec = cell["traffic"]
    assert spec["loop"] == "closed" and spec["closed"] == {"clients": 288, "pool": 288} and spec["drain_s"] == 0
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64, "max": 4096}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert spec["trace"] == {"start_s": 24.0, "length_s": 6.0}
    # warm-up touches every prefill bucket the traffic reaches (a chunk of 256 and any remainder), and a second chunk
    from modal_tpu.models.paged_kv import prefill_bucket

    chunk = REAL["engine"]["prefill_chunk"]
    reached = {prefill_bucket(n, chunk) for n in range(1, chunk + 1)}
    assert {prefill_bucket(min(n, chunk), chunk) for n in spec["warmup"]["prompt_tokens"]} | {prefill_bucket(600 - chunk * 2, chunk)} >= reached
    assert max(spec["warmup"]["prompt_tokens"]) > chunk


def test_the_configuration_is_the_catalog_row_cut_as_its_file_says():
    bench = load(_paths.REPO_ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == REAL["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert conf["source"] == REAL["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert REAL["published"] == {"num_hidden_layers": 24, "max_position_embeddings": 128000}
    # every number of the row under the same key, but the keys the file lists as reduced
    differ = {k for k, v in PUBLISHED.items() if REAL.get(k) != v}
    assert differ == set(REAL["reduced"]) and {k: PUBLISHED[k] for k in differ} == REAL["published"], differ
    widths = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=8, intermediate_size=7168,
                  moe_intermediate_size=1792, num_experts=32, num_experts_per_tok=4, conv_L_cache=3, vocab_size=65536)
    assert {k: REAL[k] for k in widths} == widths
    assert REAL["engine"]["requires"] == ["conv_state", "qk_norm"]  # the keyword an llm_service before this PR refuses at once
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longgen-saturated", 1)
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config
    from modal_tpu.models.paged_kv import packs_heads
    from modal_tpu.serving.service import MECHANISMS

    arguments = incontainer.service_arguments(REAL, 1)
    cfg = get_config(arguments["model"])
    assert cfg == get_config({"name": "lfm2-8b-a1b", "n_layers": 14, "max_seq_len": 8192})
    assert cfg.param_count() == REAL["derived"]["parameters_held"] and get_config("lfm2-8b-a1b").param_count() == REAL["derived"]["parameters_whole"]
    kinds = cfg.layer_kinds
    assert sum(k.conv == 3 for k in kinds) == 11 and [i for i, k in enumerate(kinds) if not k.conv] == [2, 6, 10]
    assert all(packs_heads(cfg, k) for k in kinds if not k.conv) and all(k.qk_norm for k in kinds if not k.conv)
    assert set(arguments["requires"]) <= set(MECHANISMS) and arguments["max_slots"] == 192 and arguments["prefix_cache"] is False
    assert (arguments["num_pages"] - 1) * 16 == 393_216 and cfg.max_seq_len == 512 * 16


def test_the_tiny_fixture_is_the_same_description_and_maps_the_same_keys():
    from benchlib import incontainer
    from modal_tpu.models.llama import get_config

    assert TINY["program_keys"] == REAL["program_keys"]
    assert {k for k in REAL if k not in TINY} <= {"model", "source", "reduced", "published", "derived", "assumed", "deployment", "engine_note", "tie_word_embeddings"}
    assert get_config(incontainer.service_arguments(TINY, 1)["model"]) == get_config("tiny-lfm2")


# -- the reference as the harness's child, and the whole run on the CPU ------------------


@pytest.mark.parametrize("control", ["", "fp8"])
def test_the_reference_child_runs_at_tiny_and_the_control_reads_apart(tmp_path, control):
    """`python reference_lfm2.py job.json out.json` on streams the reference
    itself would serve (greedy from its own float32 logits): gaps of zero, and
    under the control the float8 pass's choices lie far from the reference's
    best: at tiny size the control reads `correct: false` by the fixture's limits."""
    import numpy as np

    ref = reference_lfm2.Reference(TINY, 9, pad_to=64)
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
    proc = subprocess.run([sys.executable, reference_lfm2.__file__, str(job_path), str(out_path)], env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(out_path.read_text())
    assert out["tokens_compared"] == 12 and out["requests_compared"] == 2 and out["platform"] == "cpu"
    assert out["logit_gap_max"] == 0.0 and out["logit_gap_mean"] == 0.0
    if control:
        limits = TINY["correct"]["limits"]
        assert out["control_logit_gap_max"] > limits["logit_gap_max"] or out["control_logit_gap_mean"] > limits["logit_gap_mean"]
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
def test_the_whole_run_on_the_cpu_at_tiny_lfm2(bench_env, case, monkeypatch):
    """App -> llm_service (with `requires`) -> container -> POST /v1/generate
    -> the configuration's own reference in a child, with `tiny-lfm2` behind
    the published keys: a sound run reads correct, one whose program alters a
    token does not."""
    argv = ["--workload", "tiny-lfm2.closed", "--seed", str(2**31 + 23), "--seconds", "4", "--trace", "0", "--boot-timeout", "120"]
    if case == "token_altered":
        import modal_tpu.serving

        real = modal_tpu.serving.llm_service
        monkeypatch.setattr(modal_tpu.serving, "llm_service", lambda *a, **kw: alter_a_token(real(*a, **kw)))
    children, run_child = [], bench_run.run_child
    monkeypatch.setattr(bench_run, "run_child", lambda argv, timeout_s, env=None: children.append(argv[0]) or run_child(argv, timeout_s, env))
    line = bench_run.measure(bench_run.parse(argv), root=LFM2ROOT, state_root=bench_env)
    assert children == [os.path.join(_paths.BENCH_DIR, "benchlib", "reference_lfm2.py")]
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
    requires raises there, before any container is asked for."""
    import modal_tpu
    from benchlib import incontainer
    from modal_tpu.serving import service

    monkeypatch.setattr(service, "MECHANISMS", ("window_kv", "routed_experts", "latent_kv", "router_groups"))
    with pytest.raises(ValueError, match="has no 'conv_state', 'qk_norm'"):
        incontainer.build_service(modal_tpu.App("bench-refused"), REAL, 1)
