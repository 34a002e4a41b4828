"""A Pallas kernel's share of its roofline: the least time the chip could
take for one call (the larger of its operations over the peak rate and its
bytes over the peak bandwidth, both counted from shapes in `kernels/`)
over the device time the kernel took a call in the traced window.

The kernel is found by what it is and where it runs: the Mosaic custom
calls (`custom_call_target="tpu_custom_call"`) that start inside runs of
one compiled program, here the decode step, which calls the paged decode
attention once a layer. By default every such call of the module counts,
whatever it is named (XLA numbers it anew in every program:
`paged_decode_attention.6`, once `closed_call.10`): a kernel split in two
still counts against the same bytes, and one taken off the path leaves
nothing to find and the metric silent.

A program with more than one kernel tells them apart in the metric's file:
`kernel` is a substring of the operation's name after `<module>/` (the
`name=` of its `pallas_call`), `bytes_fn` and `flops_fn` name the functions
of the configuration's counts module that count ONE call, `f(cfg,
active_slots, live_kv_tokens)`, and `calls_key` the configuration's key
that says how many times a run of the module calls the kernel."""

from kernels import counts_for


def read(
    ctx: dict,
    module: str = "jit_paged_decode_step",
    kernel: str = "",
    bytes_fn: str = "paged_decode_kernel_bytes",
    flops_fn: str = "paged_decode_kernel_flops",
    calls_key: str = "num_hidden_layers",
):
    trace, work = ctx.get("trace"), ctx.get("traced_work")
    if not trace or not work or not work["mean_active_slots"]:
        return None
    runs = (trace["modules"].get(module) or {}).get("count", 0)
    prefix = module + "/"
    kernel_s = sum(
        row["total_s"] for name, row in (trace.get("kernels") or {}).items()
        if name.startswith(prefix) and kernel in name[len(prefix):]
    )
    if not runs or kernel_s <= 0:
        return None
    cfg = ctx["config"]
    counts = counts_for(cfg)
    per_call_s = kernel_s / (runs * cfg[calls_key])
    active, live = work["mean_active_slots"], work["mean_live_kv_tokens"]
    nbytes = getattr(counts, bytes_fn)(cfg, active, live)
    flops = getattr(counts, flops_fn)(cfg, active, live)
    floor_s = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"], flops / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * floor_s / per_call_s
