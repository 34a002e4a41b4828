"""Median device duration of one compiled program (an XLA module, by the
name jit gives it) over its runs in the traced window."""


def read(ctx: dict, module: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    row = trace["modules"].get(module)
    return row["median_ms"] if row and row["count"] else None
