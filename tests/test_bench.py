"""The scoreboard is CI-covered like everything else.

One contract: `python bench.py` on a host where jax finds only the CPU runs
its one full-stack attempt there, prints one parseable JSON line labelled
with the platform it ran on — under a name that does not claim a device
metric — and exits 0; a failed attempt exits non-zero with no result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "bench.py")


def _bench_env(**overrides: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(overrides)
    return env


def _parse_last_json_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    assert lines, f"bench printed nothing; stdout={stdout!r}"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_bench_smoke_forced_cpu():
    """The full-stack CPU bench prints one valid JSON record in <120s."""
    env = _bench_env(
        MODAL_TPU_BENCH_TIMEOUT="110",
        MODAL_TPU_BENCH_CPU_TIMEOUT="100",
        MODAL_TPU_BENCH_SNAP="0",
        MODAL_TPU_BENCH_8B="0",
        MODAL_TPU_BENCH_REAL_WEIGHTS="0",
        MODAL_TPU_BENCH_MODEL="tiny",
        MODAL_TPU_BENCH_BATCH="2",
        MODAL_TPU_BENCH_GEN="8",
        MODAL_TPU_BENCH_PROMPT="16",
    )
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True, timeout=120, env=env
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = _parse_last_json_line(proc.stdout)
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, f"missing {key}: {rec}"
    assert rec["value"] > 0, rec
    # labelled with the device it ran on, and not under a device metric's name
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert rec["metric"].startswith("cpu_backend_") and "chip" not in rec["unit"].split("(")[0]
    assert rec["mfu"] is None and rec["chip_peak_flops"] is None
    assert elapsed < 120


def test_bench_failed_attempt_exits_nonzero_without_a_result():
    """No CPU fallback record, no exit 0: an attempt that cannot finish (here
    a 1 s budget) is a failed bench."""
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True, timeout=120,
        env=_bench_env(MODAL_TPU_BENCH_CPU_TIMEOUT="1"),
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip(), proc.stdout
