"""`emit()` refuses every line the driver's check would refuse (ledger, PR 22)."""

import io
import json

import pytest

from . import _paths  # noqa: F401
from benchlib import emit as emit_mod


def good(**over):
    args = dict(
        correct=True, attempted=40, failed=0,
        metrics={"ttft_p90_ms": 212.4, "setup_s": 50.1},
        required={"ttft_p90_ms": "ms", "setup_s": "s"},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 11_000_000_000},
        traced=False,
        compared={"logit_gap_max": {"value": 0.1, "limit": 0.35}},
    )
    args.update(over)
    return args


def traced(**device):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1, "busy_s": 3.1, "window_s": 4.0}
    dev.update(device)
    return good(
        traced=True, device=dev, metrics={"decode_step_ms": 90.0, "serve_mfu_pct": 3.0},
        required={"decode_step_ms": "ms", "serve_mfu_pct": "%"},
        breakdown={"device_ops": [["fusion.1", 1.0]] * 12, "idle_gaps": [["after a before b", 0.2]]},
    )


def test_good_line_has_the_keys_in_order_and_compared_last():
    line = emit_mod.build_line(**good(extra={"workload": "w", "compared": "never"}))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["metrics"]["ttft_p90_ms"] == {"value": 212.4, "unit": "ms"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_traced_line_carries_busy_window_and_a_short_breakdown():
    line = emit_mod.build_line(**traced())
    assert line["device"]["busy_s"] == 3.1 and line["device"]["window_s"] == 4.0
    assert len(line["breakdown"]["device_ops"]) == 10


def test_emit_prints_compared_on_stderr_and_the_line_last_on_stdout():
    out, err = io.StringIO(), io.StringIO()
    emit_mod.emit(emit_mod.build_line(**good()), out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] is True
    tail = err.getvalue().splitlines()
    assert tail[-1] == "correct: True" and "logit_gap_max: value 0.1 limit 0.35" in tail[-2]


MALFORMED = {
    "metric_missing": good(metrics={"setup_s": 50.1}),
    "metric_none": good(metrics={"ttft_p90_ms": None, "setup_s": 50.1}),
    "metric_nan": good(metrics={"ttft_p90_ms": float("nan"), "setup_s": 50.1}),
    "metric_inf": good(metrics={"ttft_p90_ms": float("inf"), "setup_s": 50.1}),
    "metric_is_a_string": good(metrics={"ttft_p90_ms": "212", "setup_s": 50.1}),
    "correct_not_bool": good(correct=1),
    "nothing_attempted": good(attempted=0),
    "failed_over_attempted": good(failed=41),
    "attempted_not_int": good(attempted=40.0),
    "device_kind_missing": good(device={"platform": "tpu", "count": 1, "memory_peak_bytes": 1}),
    "device_memory_zero": good(device={"platform": "tpu", "kind": "k", "count": 1, "memory_peak_bytes": 0}),
    "device_count_zero": good(device={"platform": "tpu", "kind": "k", "count": 0, "memory_peak_bytes": 1}),
    "traced_without_busy": traced(busy_s=None),
    "traced_busy_zero": traced(busy_s=0.0),
    "traced_busy_over_window": traced(busy_s=4.5),
    "traced_window_nan": traced(window_s=float("nan")),
    "share_of_peak_over_100": {**traced(), "metrics": {"decode_step_ms": 90.0, "serve_mfu_pct": 101.0}},
    "share_of_peak_zero": {**traced(), "metrics": {"decode_step_ms": 90.0, "serve_mfu_pct": 0.0}},
    "compared_without_limit": good(compared={"logit_gap_max": {"value": 0.1}}),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_emit_raises_instead_of_printing(case):
    with pytest.raises(emit_mod.MalformedLine):
        emit_mod.build_line(**MALFORMED[case])


def test_a_nan_that_slips_into_an_extra_key_still_cannot_be_printed():
    line = emit_mod.build_line(**good(extra={"timings": {"x": float("nan")}}))
    with pytest.raises(ValueError):
        emit_mod.emit(line, out=io.StringIO(), err=io.StringIO())
