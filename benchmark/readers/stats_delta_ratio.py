"""Counters of `/v1/stats` over the window: what one grew by (or several,
summed), over what another grew by. Nothing (never a 0) where either end
lacks a path, as a program without the counter does, or where the divisor
did not move."""

from readers.stats_delta import read as grew


def read(ctx: dict, num, den: str, scale: float = 1.0):
    parts = [grew(ctx, path) for path in ([num] if isinstance(num, str) else num)]
    by = grew(ctx, den)
    if by is None or any(part is None for part in parts):
        return None
    return scale * sum(parts) / by if by else None
