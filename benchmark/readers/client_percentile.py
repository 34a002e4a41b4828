"""A percentile of one of the client's series (`late_ms`, `ttft_ms`,
`itl_ms`): what the load generator itself saw."""

from benchlib.stats import percentile


def read(ctx: dict, series: str, q: float):
    values = ctx["client"].get(series) or []
    return percentile(values, q) if values else None
