"""A Pallas kernel's share of its roofline: the least time the chip could
take for one call (the larger of its operations over the peak rate and its
bytes over the peak bandwidth, both counted from shapes in `kernels/`)
over the device time the kernel took a call in the traced window.

The kernel is found by what it is and where it runs: the Mosaic custom
calls (`custom_call_target="tpu_custom_call"`) that start inside runs of
one compiled program, here the decode step, which calls the paged decode
attention once a layer. `pl.pallas_call` in ops/paged_attention.py passes
no name and XLA numbers the call anew in every program (`closed_call.10`),
so neither is read. If a later program splits the kernel in two, both
calls count against the same bytes; if it takes the kernel off the path,
nothing is found and the metric is silent."""

from kernels import counts


def read(ctx: dict, module: str = "jit_paged_decode_step"):
    trace, work = ctx.get("trace"), ctx.get("traced_work")
    if not trace or not work or not work["mean_active_slots"]:
        return None
    runs = (trace["modules"].get(module) or {}).get("count", 0)
    kernel_s = sum(row["total_s"] for name, row in (trace.get("kernels") or {}).items() if name.startswith(module + "/"))
    if not runs or kernel_s <= 0:
        return None
    cfg = ctx["config"]
    per_call_s = kernel_s / (runs * cfg["num_hidden_layers"])
    nbytes = counts.paged_decode_kernel_bytes(cfg, work["mean_active_slots"], work["mean_live_kv_tokens"])
    flops = counts.attention_flops(cfg, 0, 1) * work["mean_live_kv_tokens"] / cfg["num_hidden_layers"]
    floor_s = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"], flops / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * floor_s / per_call_s
