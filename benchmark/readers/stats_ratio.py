"""Two values of `/v1/stats` at the window's end, one over the other."""

from readers.stats_value import dig


def read(ctx: dict, num: str, den: str, scale: float = 1.0):
    a, b = dig(ctx["stats_end"] or {}, num), dig(ctx["stats_end"] or {}, den)
    return None if a is None or not b else scale * a / b
