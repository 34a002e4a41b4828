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


# -- the traced window: busy_s cannot pass window_s, on any trace (PR 36) ----------------


def device_plane(n: int, intervals: list) -> dict:
    ops = [["%fusion.1 = f32[8]{0} fusion(...)", int(a * MS), int((b - a) * MS)] for a, b in intervals]
    return {"name": f"/device:TPU:{n}", "lines": [{"name": "XLA Ops", "events": ops}]}


def back_to_back(start_ms: float, stop_ms: float, step_ms: float = 12.5) -> list:
    """A device that never idles: one operation ends where the next starts."""
    edges = [start_ms + i * step_ms for i in range(int((stop_ms - start_ms) / step_ms) + 1)]
    return list(zip(edges, edges[1:]))


# times in ms on the profile's own base; the container's clock read 4.000 s between start_trace returning and stop_trace being called
TRACES = {
    # the loop of PR 35: the recording reaches 20 ms before the clock's window and 30 ms past it, and the device is busy all through
    "never_idle_past_both_edges": ([back_to_back(-20.0, 4030.0)], 4.05, 4.05, 4.05),
    # what killed a traced run of PR 35 (busy 6.0208 against a clock window of 6.0053), at its own numbers
    "pr35_cell_2": ([[(0.0, 6020.841771)]], 6.020841771, 6.020841771, 6.020841771),
    # a device idle at both edges keeps the clock's window: its idle share is not shrunk to the span
    "idle_at_both_edges": ([[(1000.0, 2000.0)]], 1.0, 1.0, 4.0),
    "idle_inside_and_at_the_end": ([[(-5.0, 1000.0), (1500.0, 3000.0)]], 2.505, 3.005, 4.0),
    # two chips: busy is the mean, the span runs from the first operation of either to the last of either
    "two_planes": ([back_to_back(0.0, 3000.0), back_to_back(1000.0, 4025.0)], (3.0 + 3.025) / 2, 4.025, 4.025),
    "two_planes_one_nearly_idle": ([[(100.0, 110.0)], back_to_back(-10.0, 4010.0, 20.0)], (0.010 + 4.02) / 2, 4.02, 4.02),
}


@pytest.mark.parametrize("case", sorted(TRACES))
def test_busy_cannot_pass_the_window_on_any_trace(case):
    from benchlib import emit as emit_mod
    from readers import trace_idle_pct

    planes, busy_s, span_s, window_s = TRACES[case]
    clock = 6.005311369 if case == "pr35_cell_2" else 4.0
    out = trace_reduce.reduce_table({"planes": [device_plane(n, ops) for n, ops in enumerate(planes)]}, clock)
    assert out["busy_s"] == pytest.approx(busy_s, rel=1e-9) and out["span_s"] == pytest.approx(span_s, rel=1e-9)
    assert out["clock_window_s"] == clock and out["window_s"] == pytest.approx(window_s, rel=1e-9)
    assert 0 < out["busy_s"] <= out["span_s"] <= out["window_s"] and out["window_s"] >= clock
    assert trace_idle_pct.read({"trace": out}) == pytest.approx(100.0 * (1.0 - busy_s / window_s), abs=1e-9)
    # the pair goes onto the line as it is: the harness's own refusal (busy_s outside (0, window_s]) stays and finds nothing
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": len(planes), "memory_peak_bytes": 1, "busy_s": out["busy_s"], "window_s": out["window_s"]}
    line = dict(correct=True, attempted=1, failed=0, metrics={"device_idle_pct": 1.0}, required={"device_idle_pct": "%"}, traced=True, compared={})
    assert emit_mod.build_line(device=device, **line)["device"]["window_s"] == out["window_s"]
    if out["busy_s"] > clock:  # and the parent's pair, the clock's window alone, is the one that was refused
        with pytest.raises(emit_mod.MalformedLine):
            emit_mod.build_line(device={**device, "window_s": clock}, **line)


def test_the_child_takes_the_clock_s_window_on_its_command_line(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda trace_dir: trace_dir)
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: {"planes": [device_plane(0, back_to_back(-20.0, 4030.0))]})
    out_path, table_path = tmp_path / "summary.json", tmp_path / "table.json"
    assert trace_reduce.main([str(tmp_path), str(out_path), "4.0", "--table", str(table_path)]) == 0
    out = json.loads(out_path.read_text())
    assert (out["clock_window_s"], out["window_s"]) == (4.0, pytest.approx(4.05)) and out["busy_s"] <= out["window_s"]
    assert json.loads(table_path.read_text())["planes"][0]["name"] == "/device:TPU:0"


def test_the_harness_hands_the_clock_s_window_to_the_reduction_and_its_readers_the_pair_it_gives_back(tmp_path, monkeypatch):
    import run as bench_run

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda trace_dir: trace_dir)
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: {"planes": [device_plane(0, back_to_back(-20.0, 4030.0) + [(4040.0, 4050.0)])]})
    monkeypatch.setattr(bench_run, "run_child", lambda argv, timeout_s, env=None: trace_reduce.main(argv[1:]))  # the child, in this process
    cell, ctx = {"per_layer": {"device_idle_pct": {"unit": "%"}}}, {}
    clock = {"window_s": 4.0, "t_start": 0.0, "t_stop": 4.0, "stop_s": 0.2}
    required, reported, _breakdown, silent = bench_run.traced_metrics(cell, ctx, [], clock, str(tmp_path), False)
    trace = ctx["trace"]
    assert (trace["clock_window_s"], trace["span_s"], trace["window_s"]) == (4.0, pytest.approx(4.07), pytest.approx(4.07))
    assert trace["busy_s"] == pytest.approx(4.06) and required == {"device_idle_pct": "%"} and not silent
    assert reported["device_idle_pct"] == pytest.approx(100.0 * 0.01 / 4.07)


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
    # the cut is 0.36 s of a window: with no clock given the window is the cut's own span, and under a clock that read longer, the clock's
    assert out["busy_s"] <= out["span_s"] == out["window_s"] == pytest.approx((max(e for _s, e in events) - events[0][0]) / 1e9, rel=1e-12)
    assert trace_reduce.reduce_table(fixture["table"], 0.36)["window_s"] == 0.36
