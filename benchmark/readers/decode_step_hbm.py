"""Share of the memory bandwidth one decode step uses: the bytes it must
read (all weights once, the live keys and values of the active slots) over
the peak bandwidth, over the step's median device time. The bytes are
counted by the module the configuration names (`counts`)."""

from kernels import counts_for


def read(ctx: dict, module: str = "jit_paged_decode_step"):
    trace, work = ctx.get("trace"), ctx.get("traced_work")
    if not trace or not work:
        return None
    row = trace["modules"].get(module)
    if not row or not row["count"] or not work["mean_active_slots"]:
        return None
    counts = counts_for(ctx["config"])
    need = counts.decode_step_bytes(ctx["config"], work["mean_active_slots"], work["mean_live_kv_tokens"])
    floor_ms = 1e3 * need / (ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * floor_ms / row["median_ms"]
