"""The reduction from a trace to numbers, on tables with known answers: one
made by hand, one cut from a trace recorded on the chip."""

import json
import os

import pytest

from . import _paths
from benchlib import trace_reduce

MS = 1_000_000  # nanoseconds


def hand_made():
    ops = [
        ["%fusion.1 = f32[8]{0} fusion(...)", 0 * MS, 10 * MS],
        ['%closed_call.2 = bf16[4]{0} custom-call(...), custom_call_target="tpu_custom_call"', 10 * MS, 20 * MS],
        ["%while.9 = (s32[]) while(...)", 40 * MS, 15 * MS],
        ["%fusion.3 = f32[8]{0} fusion(...)", 45 * MS, 10 * MS],  # inside the while: the union counts 40..55 once
        ["%fusion.1 = f32[8]{0} fusion(...)", 100 * MS, 18 * MS],
    ]
    modules = [
        ["jit_paged_prefill(111)", 0 * MS, 30 * MS], ["jit_paged_decode_step(222)", 40 * MS, 15 * MS],
        ["jit_paged_decode_step(222)", 100 * MS, 20 * MS], ["jit_paged_decode_step(222)", 150 * MS, 25 * MS],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [["x", 0, 999 * MS]]}]},
    ]}


def test_busy_is_the_union_of_the_device_s_operations():
    out = trace_reduce.reduce_table(hand_made())
    assert out["busy_s"] == pytest.approx((30 + 15 + 18) / 1e3)
    assert out["span_s"] == pytest.approx(0.118) and out["device_planes"] == 1


def test_module_medians_by_the_name_jit_gives():
    out = trace_reduce.reduce_table(hand_made())
    assert out["modules"]["jit_paged_decode_step"] == {"count": 3, "median_ms": 20.0, "total_s": pytest.approx(0.060)}
    assert out["modules"]["jit_paged_prefill"]["median_ms"] == 30.0
    assert trace_reduce.module_base("jit_paged_decode_step(12345)") == "jit_paged_decode_step"


def test_gaps_are_labelled_by_the_modules_on_either_side_and_ops_by_total_time():
    out = trace_reduce.reduce_table(hand_made())
    gaps = dict(out["idle_gaps"])
    assert gaps["after jit_paged_decode_step before jit_paged_decode_step (host: unattributed)"] == pytest.approx(0.045 + 0.030)
    assert gaps["after jit_paged_prefill before jit_paged_decode_step (host: unattributed)"] == pytest.approx(0.010)
    # each operation by its own time (what runs inside it taken out), named by the program it ran in
    assert out["device_ops"] == [
        ["jit_paged_prefill/closed_call.2", pytest.approx(0.020)],
        ["jit_paged_decode_step/fusion.1", pytest.approx(0.018)],
        ["jit_paged_prefill/fusion.1", pytest.approx(0.010)],
        ["jit_paged_decode_step/fusion.3", pytest.approx(0.010)],
        ["jit_paged_decode_step/while.9", pytest.approx(0.005)],
    ]
    # a Mosaic call is named by the program it ran in too: XLA numbers it anew in each
    assert out["kernels"] == {"jit_paged_prefill/closed_call.2": {"count": 1, "median_ms": 20.0, "total_s": pytest.approx(0.020)}}


def test_a_kernel_s_roofline_reads_the_mosaic_calls_of_its_program_whatever_xla_numbers_them():
    from kernels import counts
    from readers import trace_kernel_roofline

    cfg = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8, "intermediate_size": 14336,
           "num_hidden_layers": 16, "vocab_size": 32768}
    row = lambda total_s: {"count": 160, "median_ms": 1.0, "total_s": total_s}  # noqa: E731
    ctx = {
        "config": cfg, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "traced_work": {"mean_active_slots": 4.0, "mean_live_kv_tokens": 2000.0},
        "trace": {"modules": {"jit_paged_decode_step": {"count": 10}}, "kernels": {
            "jit_paged_decode_step/closed_call.10": row(0.16), "jit_paged_prefill/closed_call.3": row(9.0)}},
    }
    floor_s = counts.paged_decode_kernel_bytes(cfg, 4.0, 2000.0) / 819e9  # memory-bound
    assert trace_kernel_roofline.read(ctx) == pytest.approx(100.0 * floor_s / (0.16 / (10 * 16)))
    # renumbered by XLA, or split in two calls a layer: the same bytes against the time of both
    ctx["trace"]["kernels"] = {"jit_paged_decode_step/closed_call.7": row(0.10), "jit_paged_decode_step/custom-call.2": row(0.06)}
    assert trace_kernel_roofline.read(ctx) == pytest.approx(100.0 * floor_s / (0.16 / (10 * 16)))
    # taken off the decode step's path: nothing to read, never a 0
    ctx["trace"]["kernels"] = {"jit_paged_prefill/closed_call.3": row(9.0)}
    assert trace_kernel_roofline.read(ctx) is None


def test_busy_is_averaged_over_the_chips_used():
    table = hand_made()
    second = json.loads(json.dumps(table["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["%fusion.1 = f32[8]{0} fusion(...)", 0, 5 * MS]]
    table["planes"].append(second)
    assert trace_reduce.reduce_table(table)["busy_s"] == pytest.approx((0.063 + 0.005) / 2)


def test_a_trace_in_which_nothing_ran_on_the_device_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_table({"planes": [{"name": "/host:CPU", "lines": []}]})
    with pytest.raises(ValueError):
        trace_reduce.reduce_table({"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []}]}]})


RECORDED = os.path.join(_paths.FIXTURES, "recorded_trace_table.json")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace fixture")
def test_the_recorded_trace_reduces_to_the_numbers_worked_out_beside_it():
    with open(RECORDED) as f:
        fixture = json.load(f)
    out = trace_reduce.reduce_table(fixture["table"])
    known = fixture["known"]
    assert out["busy_s"] == pytest.approx(known["busy_s"], rel=1e-9)
    assert sum(k["count"] for k in out["kernels"].values()) == known["kernel_calls"] == 16  # one a layer
    for module, row in known["modules"].items():
        assert out["modules"][module]["count"] == row["count"]
        assert out["modules"][module]["median_ms"] == pytest.approx(row["median_ms"], rel=1e-9)
    # busy by another road: sweep the sorted op intervals
    events = sorted((s, s + d) for p in fixture["table"]["planes"] for l in p["lines"] if l["name"] == "XLA Ops" for _n, s, d in l["events"])
    covered, end = 0, -1
    for s, e in events:
        if s > end:
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    assert out["busy_s"] == pytest.approx(covered / 1e9, rel=1e-9)
    assert out["busy_s"] <= out["span_s"]
