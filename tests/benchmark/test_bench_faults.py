"""The rest of a run with the look for a chip skipped (CPU, `tiny`, the
whole stack: App -> llm_service -> container -> POST /v1/generate): a sound
run comes out correct; one with the timed path broken underneath, a token
altered where it is produced, and one judged on the lower-precision control
come out NOT correct."""

import json
import os

import pytest

from . import _paths
from ._drive import alter_a_token, skip_the_chip_look
import run as bench_run

TINYROOT = os.path.join(_paths.FIXTURES, "tinyroot")


@pytest.fixture
def bench_env(supervisor, tmp_path, monkeypatch):
    # measure() writes these; monkeypatch puts them back for the next test
    for key in ("MODAL_TPU_STATE_DIR", "JAX_COMPILATION_CACHE_DIR", "PYTHONPATH"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jit_cache"))
    skip_the_chip_look(bench_run, monkeypatch.setattr)
    return str(tmp_path / "bench_state")


@pytest.mark.parametrize("case", ["sound", "token_altered", "control_fp8"])
def test_a_run_without_the_chip_look(bench_env, case, capsys, monkeypatch):
    """A sound run reads correct; one whose program alters a token, and one
    in which the float8 reference stands in the program's place (the
    control), go through the same comparison and read NOT correct."""
    argv = ["--workload", "tiny.open", "--seed", str(2**31 + 11), "--seconds", "4", "--trace", "0", "--boot-timeout", "90"]
    if case == "control_fp8":
        argv += ["--control", "reference_fp8"]
    if case == "token_altered":
        import modal_tpu.serving

        real = modal_tpu.serving.llm_service
        monkeypatch.setattr(modal_tpu.serving, "llm_service", lambda *a, **kw: alter_a_token(real(*a, **kw)))
    line = bench_run.measure(bench_run.parse(argv), root=TINYROOT, state_root=bench_env)
    assert line["attempted"] >= 5 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert line["compared"]["bad_streams"] == {"value": 0, "limit": 0}
    gap, mean = line["compared"]["logit_gap_max"], line["compared"]["logit_gap_mean"]
    if case == "sound":
        assert line["correct"] is True and gap["value"] <= gap["limit"] and mean["value"] <= mean["limit"]
    elif case == "token_altered":
        assert line["correct"] is False and gap["value"] > 10 * gap["limit"]
    else:  # the control fails BOTH numbers, with room, while the program's own gaps stay sound
        assert line["correct"] is False and line["control"] == "reference_fp8"
        assert gap["value"] > 3 * gap["limit"] and mean["value"] > 3 * mean["limit"]
        assert line["reference"]["logit_gap_max"] <= gap["limit"] and line["reference"]["logit_gap_mean"] <= mean["limit"]
    bench_run.emit_mod.emit(line)
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1])["correct"] is (case == "sound")
    assert out.err.strip().splitlines()[-1] == f"correct: {case == 'sound'}"


def test_no_tpu_means_no_result_line(monkeypatch, capsys):
    from modal_tpu.server import worker

    monkeypatch.setattr(worker, "probe_jax_devices", lambda timeout_s=0: (1, "cpu", "cpu"))
    rc = bench_run.main(["--workload", "mistral-7b.chat-steady", "--seed", "1", "--seconds", "5", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_a_device_without_peaks_is_an_error_not_a_default(monkeypatch, capsys):
    from modal_tpu.server import worker

    monkeypatch.setattr(worker, "probe_jax_devices", lambda timeout_s=0: (1, "tpu", "TPU v9 imaginary"))
    rc = bench_run.main(["--workload", "mistral-7b.chat-steady", "--seed", "1", "--seconds", "5", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "peaks.json" in out.err
