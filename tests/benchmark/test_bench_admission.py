"""A configuration of another architecture is admitted as new files and new
entries: it names its own reference (`reference`) and its own counts
(`counts`), a kernel of its own gets a roofline from a `layer_metrics` file,
and nothing that was there is edited. Without the keys everything reads
what it read before (PR 28).

The harness finds everything beside its own run.py, so the admitted tree is
driven as the driver drives a checkout: its own run.py, in a child
(`_drive.py`), with nothing of this checkout's harness on the path."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from . import _accepted, _paths
from .test_bench_faults import TINYROOT
import run as bench_run
from benchlib import reference, trace_reduce
from kernels import counts
from readers import decode_step_hbm, serve_mfu, trace_kernel_roofline

OTHER = os.path.join(_paths.FIXTURES, "other_arch")
with open(os.path.join(_paths.FIXTURES, "recorded_ctx.json")) as f:
    RECORDED = json.load(f)["cells"]
WHOLE_STEP = {"serve_mfu_pct": serve_mfu, "decode_step_hbm_pct": decode_step_hbm, "paged_decode_roofline": trace_kernel_roofline}


def files_of(top: str) -> dict:
    out = {}
    for folder, _dirs, names in os.walk(top):
        for name in names:
            path = os.path.join(folder, name)
            out[os.path.relpath(path, top)] = _accepted.digest(path)
    return out


def in_tree(root: str, *argv, timeout: float = 60) -> dict:
    """`_drive.py` in a child, on the CPU, from the tree at `root`."""
    env = {k: v for k, v in os.environ.items() if k not in ("MODAL_TPU_SERVER_URL", "MODAL_TPU_STATE_DIR", "PYTHONPATH")}
    drive = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_drive.py")
    proc = subprocess.run([sys.executable, drive, root, *argv], cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def copy_the_benchmark(root: str, with_its_tests: bool = False) -> dict:
    """The benchmark's tree as committed, copied to `root`: BENCHMARK.json
    and benchmark/ (and tests/benchmark/, where the record of what was
    accepted is). Returns BENCHMARK.json as it stood."""
    for top in ("benchmark", "tests/benchmark")[: 2 if with_its_tests else 1]:
        shutil.copytree(os.path.join(_paths.REPO_ROOT, top), os.path.join(root, top), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_paths.REPO_ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def lay_another_architecture_over(root: str, bench: dict) -> dict:
    """What a `model_config` PR of another architecture brings, laid over
    the tree at `root`: a configuration, its reference, its counts, a
    kernel's roofline metric, a traffic file, and entries appended to
    `configs`, `workloads` and `per_layer`. Returns the entries."""
    bench_dir = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(OTHER, "benchmark"), bench_dir, dirs_exist_ok=True)
    shutil.copy(os.path.join(TINYROOT, "benchmark", "traffic", "tiny-open.json"), os.path.join(bench_dir, "traffic"))
    with open(os.path.join(OTHER, "entries.json")) as f:
        entries = json.load(f)
    for key, new in entries.items():
        bench[key].extend(new)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=2)
    os.symlink(os.path.join(_paths.REPO_ROOT, "modal_tpu"), os.path.join(root, "modal_tpu"))  # the system under test
    return entries


@pytest.fixture(scope="module")
def admitted(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("admitted"))
    bench = copy_the_benchmark(root)
    before, was = files_of(root), json.loads(json.dumps(bench))
    entries = lay_another_architecture_over(root, bench)
    return {"root": root, "bench_dir": os.path.join(root, "benchmark"), "before": before, "was": was, "bench": bench, "entries": entries}


def test_admitting_another_architecture_changes_no_byte_of_a_file_that_was_there(admitted):
    after = files_of(admitted["root"])
    added = sorted(set(after) - set(admitted["before"]))
    assert added == [
        "benchmark/benchlib/reference_other.py", "benchmark/configs/tiny-other.json", "benchmark/kernels/counts_other.py",
        "benchmark/layer_metrics/other_kernel_roofline.json", "benchmark/traffic/tiny-open.json",
    ]
    changed = [name for name, digest in admitted["before"].items() if after[name] != digest]
    assert changed == ["BENCHMARK.json"]
    # and BENCHMARK.json only GAINED entries: every entry that was there is there, in place, as it was
    was, now = admitted["was"], admitted["bench"]
    assert set(was) == set(now)
    for key, value in was.items():
        if key in admitted["entries"]:
            assert now[key][: len(value)] == value and now[key][len(value):] == admitted["entries"][key]
        else:
            assert now[key] == value


def test_load_cell_finds_the_new_cell_s_files_by_their_names(admitted):
    cell = in_tree(admitted["root"], "load", "tiny-other.open")
    assert cell["reference"] == os.path.join(admitted["bench_dir"], "benchlib", "reference_other.py")
    assert cell["counts_file"] == os.path.join(admitted["bench_dir"], "kernels", "counts_other.py")
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    universal = {m["name"] for m in admitted["was"]["per_layer"] if "workloads" not in m and m["moves"] in cell["end_to_end"]}
    assert set(cell["per_layer"]) == universal | {"other_kernel_roofline"} and "paged_decode_roofline" not in cell["per_layer"]
    # the cells that were there name neither key and get the dense block's files
    old = in_tree(admitted["root"], "load", "mistral-7b.chat-saturated")
    assert old["reference"] == os.path.join(admitted["bench_dir"], "benchlib", "reference.py")
    assert old["counts_file"] == os.path.join(admitted["bench_dir"], "kernels", "counts.py")
    assert "paged_decode_roofline" in old["per_layer"] and "other_kernel_roofline" not in old["per_layer"]


@pytest.mark.parametrize("case", ["sound", "token_altered"])
def test_the_new_cell_s_run_calls_the_reference_its_configuration_names(admitted, tmp_path, case):
    """The whole run on the CPU at `tiny` (the path test_bench_faults.py
    drives), from the admitted tree: the child that decided `correct` was
    the configuration's own file."""
    argv = ["--workload", "tiny-other.open", "--seed", str(2**31 + 13), "--seconds", "4", "--trace", "0", "--boot-timeout", "90"]
    ran = in_tree(admitted["root"], "run", str(tmp_path / "bench_state"), case, *argv, timeout=400)
    line = ran["line"]
    assert ran["children"] == [[os.path.join(admitted["bench_dir"], "benchlib", "reference_other.py"), 300]]
    assert line["attempted"] >= 5 and line["failed"] == 0 and set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    gap = line["compared"]["logit_gap_max"]
    if case == "sound":
        assert line["correct"] is True and gap["value"] <= gap["limit"] and line["reference"]["tokens_compared"] > 20
    else:
        assert line["correct"] is False and gap["value"] > 10 * gap["limit"]


def edit_json(path: str, edit) -> None:
    with open(path) as f:
        data = json.load(f)
    edit(data)
    with open(path, "w") as f:
        json.dump(data, f)


def metric_args(key: str, value: str):
    return lambda spec: spec["args"].__setitem__(key, value)


def list_the_cell(bench: dict) -> None:
    next(m for m in bench["per_layer"] if m["name"] == "paged_decode_roofline")["workloads"].append("tiny-other.open")


@pytest.mark.parametrize("file, edit, names", [
    ("benchmark/configs/tiny-other.json", lambda cfg: cfg.update(reference="benchlib/reference_absent.py"), ["tiny-other.json", "'reference'", "reference_absent.py"]),
    ("benchmark/configs/tiny-other.json", lambda cfg: cfg.update(counts="counts_absent"), ["tiny-other.json", "'counts'", "counts_absent"]),
    ("benchmark/layer_metrics/other_kernel_roofline.json", metric_args("bytes_fn", "absent_bytes"), ["other_kernel_roofline.json", "bytes_fn", "absent_bytes", "counts_other.py"]),
    ("benchmark/layer_metrics/other_kernel_roofline.json", metric_args("calls_key", "absent_calls"), ["other_kernel_roofline.json", "calls_key", "absent_calls", "tiny-other.json"]),
    # a metric that names no function takes its reader's default: the paged kernel's, which the other module lacks
    ("BENCHMARK.json", list_the_cell, ["paged_decode_roofline.json", "bytes_fn", "paged_decode_kernel_bytes", "counts_other.py"]),
], ids=["reference", "counts", "bytes_fn", "calls_key", "default_bytes_fn"])
def test_a_name_that_finds_nothing_ends_the_run_before_it_boots_and_names_the_key(admitted, tmp_path, file, edit, names):
    """Not a fall-back to the default (a cell judged by the wrong reference,
    or a share of a peak from the wrong counts, would read as sound), and
    not an AttributeError out of a reader after the traced window."""
    root = str(tmp_path / "root")
    shutil.copytree(admitted["root"], root, symlinks=True)
    assert "run_failed" not in in_tree(root, "load", "tiny-other.open")
    edit_json(os.path.join(root, file), edit)
    message = in_tree(root, "load", "tiny-other.open")["run_failed"]
    assert all(name in message for name in names), message


# -- the readers: the named counts where a configuration names them, today's numbers where it does not --


def recorded(cell: str) -> dict:
    return json.loads(json.dumps(RECORDED[cell]["ctx"]))


def reader_args(bench_dir: str, metric: str) -> dict:
    with open(os.path.join(bench_dir, "layer_metrics", metric + ".json")) as f:
        return json.load(f).get("args", {})


@pytest.mark.parametrize("metric", sorted(WHOLE_STEP))
@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_without_the_keys_a_reader_gives_the_number_the_parent_s_reader_gave(cell, metric):
    """`recorded_ctx.json`: what the readers were handed in one traced run a
    cell on the chip (my chip runs, PR 28), and what the readers of the
    parent commit made of it. Equal, not approximately: the same floats go
    through the same expressions in the same order."""
    expected = RECORDED[cell]["expected"]
    ctx = recorded(cell)
    assert "counts" not in ctx["config"] and "reference" not in ctx["config"] and "head_dim" not in ctx["config"]
    assert WHOLE_STEP[metric].read(ctx, **reader_args(_paths.BENCH_DIR, metric)) == expected[metric]
    assert bench_run.read_layer_metric(metric, ctx) == expected[metric]  # through the harness's own door too


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_with_the_key_the_whole_step_readers_use_the_named_module_s_numbers(admitted, tmp_path, cell):
    ctx = recorded(cell)
    plain = {name: reader.read(ctx) for name, reader in WHOLE_STEP.items()}
    ctx["config"]["counts"] = "counts_other"
    with open(tmp_path / "asked.json", "w") as f:
        json.dump({"metrics": ["serve_mfu_pct", "decode_step_hbm_pct"], "ctx": ctx}, f)
    named = in_tree(admitted["root"], "read", str(tmp_path / "asked.json"))
    work, cfg = ctx["traced_work"], ctx["config"]
    by_hand = sum(3 * counts.prefill_flops(cfg, p) for p in work["prefilled_prompts"]) + sum(5 * counts.decode_flops(cfg, c) for c in work["decode_contexts"])
    assert named["serve_mfu_pct"] == pytest.approx(100.0 * by_hand / (ctx["trace"]["busy_s"] * 197e12), rel=1e-12)
    assert 2.999 * plain["serve_mfu_pct"] < named["serve_mfu_pct"] < 5.001 * plain["serve_mfu_pct"]
    assert named["decode_step_hbm_pct"] == pytest.approx(7 * plain["decode_step_hbm_pct"], rel=1e-12)


def two_kernels_in_the_decode_step() -> dict:
    """A table in which a run of the decode step calls two Mosaic kernels:
    the paged attention once a layer (2 layers) and another three times."""
    ms, target = 1_000_000, 'custom-call(...), custom_call_target="tpu_custom_call"'
    ops, modules = [], []
    for run in range(4):
        t0 = run * 100 * ms
        modules.append(["jit_paged_decode_step(77)", t0, 60 * ms])
        ops += [[f"%paged_decode_attention.6 = bf16[4]{{0}} {target}", t0 + (1 + 10 * i) * ms, 5 * ms] for i in range(2)]
        ops += [[f"%other_kernel.3 = f32[4]{{0}} {target}", t0 + (30 + 8 * i) * ms, 2 * ms] for i in range(3)]
        ops.append(["%fusion.1 = f32[8]{0} fusion(...)", t0 + 55 * ms, 4 * ms])
    modules.append(["jit_paged_prefill(78)", 400 * ms, 30 * ms])
    ops.append([f"%other_kernel.9 = f32[4]{{0}} {target}", 405 * ms, 20 * ms])
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]}


def test_a_metric_s_file_reads_one_kernel_of_two_against_its_own_counts(admitted, tmp_path):
    trace = trace_reduce.reduce_table(two_kernels_in_the_decode_step())
    assert sorted(trace["kernels"]) == ["jit_paged_decode_step/other_kernel.3", "jit_paged_decode_step/paged_decode_attention.6", "jit_paged_prefill/other_kernel.9"]
    with open(os.path.join(admitted["bench_dir"], "configs", "tiny-other.json")) as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"config": cfg, "peaks": peaks, "chips": 1, "trace": trace, "traced_work": {"mean_active_slots": 3.0, "mean_live_kv_tokens": 500.0}}

    def read_in_tree(ctx_: dict):
        with open(tmp_path / "asked.json", "w") as f:
            json.dump({"metrics": ["other_kernel_roofline"], "ctx": ctx_}, f)
        return in_tree(admitted["root"], "read", str(tmp_path / "asked.json"))["other_kernel_roofline"]

    # its own kernel: 4 runs x 3 calls of 2 ms; a state of 128 x 128 bf16 read and written a slot (memory-bound)
    assert read_in_tree(ctx) == pytest.approx(100.0 * (2 * 3.0 * 128 * 128 * 2 / 819e9) / 2e-3, rel=1e-12)
    args = reader_args(admitted["bench_dir"], "other_kernel_roofline")
    assert args["kernel"] == "other_kernel" and args["calls_key"] == "other_kernel_calls" and cfg["other_kernel_calls"] == 3
    # the same file over a program without that kernel: nothing, never a 0
    only_paged = {**ctx, "trace": {**trace, "kernels": {k: v for k, v in trace["kernels"].items() if "other_kernel.3" not in k}}}
    assert read_in_tree(only_paged) is None
    # with none of the arguments, as before: every Mosaic call of the module against the paged kernel's counts
    plain = {**ctx, "config": {k: v for k, v in cfg.items() if k != "counts"}}
    floor_s = counts.paged_decode_kernel_bytes(cfg, 3.0, 500.0) / 819e9
    assert trace_kernel_roofline.read(plain) == pytest.approx(100.0 * floor_s / ((4 * 2 * 5e-3 + 4 * 3 * 2e-3) / (4 * 2)), rel=1e-12)
    assert trace_kernel_roofline.read(plain, kernel="paged_decode_attention") == pytest.approx(100.0 * floor_s / 5e-3, rel=1e-12)


def test_the_inline_expression_moved_into_counts_is_the_same_expression():
    cfg = RECORDED[sorted(RECORDED)[0]]["ctx"]["config"]
    for live in (0.0, 1364.25, 12001.515625):
        assert counts.paged_decode_kernel_flops(cfg, 3.0, live) == counts.attention_flops(cfg, 0, 1) * live / cfg["num_hidden_layers"]


@pytest.mark.parametrize("head_dim, expect", [(None, 128), (192, 192)])
def test_shapes_take_head_dim_where_the_configuration_gives_it(head_dim, expect):
    cfg = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8, "intermediate_size": 14336,
           "num_hidden_layers": 16, "vocab_size": 32768}
    if head_dim:
        cfg["head_dim"] = head_dim
    assert counts.shapes(cfg)["hd"] == expect
    assert counts.kv_bytes_per_token(cfg) == 2 * 8 * expect * 2 * 16
    assert counts.layer_matmul_params(cfg) == 2 * 4096 * 32 * expect + 2 * 4096 * 8 * expect + 3 * 4096 * 14336


def test_the_reference_child_gets_the_contract_s_job(tmp_path, monkeypatch):
    cell = bench_run.load_cell(TINYROOT, "tiny.open")
    default = os.path.join(_paths.BENCH_DIR, "benchlib", "reference.py")
    assert cell["reference"] == default
    seen = {}

    def child(argv, timeout_s, env=None):
        seen.update(file=argv[0], timeout_s=timeout_s)
        with open(argv[1]) as f:
            assert set(json.load(f)) == {"config", "seed", "control", "require_platform", "pad_to", "requests"}
        with open(argv[2], "w") as f:
            json.dump({"logit_gap_max": 0.0}, f)

    monkeypatch.setattr(bench_run, "run_child", child)
    out = bench_run.run_reference(cell, 3, [], str(tmp_path), "", "cpu")
    assert out == {"logit_gap_max": 0.0} and seen == {"file": default, "timeout_s": 300}


def test_the_padding_rule_and_the_comparison_serve_any_object_with_logits():
    """What a sibling reference takes from benchlib/reference.py: `padded`,
    and `compare`, which touches nothing of its argument but `logits`."""
    import numpy as np

    ids, pos = reference.padded([5, 6, 7], [1, 2], pad_to=1024)
    assert ids.shape == (1024,) and list(ids[:4]) == [5, 6, 7, 0] and pos.shape == (512,) and list(pos[:3]) == [1, 2, 0]
    assert reference.padded(list(range(600)), [0])[0].shape == (1024,)

    class Table:
        def logits(self, tokens, positions, low=False):
            out = np.zeros((len(positions), 8), np.float32)
            for row, p in enumerate(positions):  # the best next token is the last one + 1; float8 would say + 2
                out[row, (tokens[p] + (2 if low else 1)) % 8] = 1.0
            return out

    got = reference.compare(Table(), [{"index": 0, "prompt": [1, 2], "tokens": [3, 4, 6]}], control="fp8")
    assert got["logit_gap_max"] == 1.0 and got["tokens_compared"] == 3 and got["per_request"][0]["gap_max"] == 1.0
    assert got["control_logit_gap_max"] == 1.0 and got["control_logit_gap_mean"] == 1.0
