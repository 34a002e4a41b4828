"""The whole served step's share of the chip's peak: the operations the
model needs for the tokens processed in the traced window (prompts whose
first token arrived in it, and every output token received in it; attention
included, padding and recomputation not) over busy seconds x peak x chips.
The operations are counted by the module the configuration names
(`counts`, see kernels/__init__.py)."""

from kernels import counts_for


def read(ctx: dict):
    trace, work = ctx.get("trace"), ctx.get("traced_work")
    if not trace or not work or trace["busy_s"] <= 0:
        return None
    cfg = ctx["config"]
    counts = counts_for(cfg)
    flops = sum(counts.prefill_flops(cfg, p) for p in work["prefilled_prompts"])
    flops += sum(counts.decode_flops(cfg, c) for c in work["decode_contexts"])
    if flops <= 0:
        return None
    return 100.0 * flops / (trace["busy_s"] * ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"])
