"""A counter of `/v1/stats`: its value at the window's end less its value
at the window's start."""

from readers.stats_value import dig


def read(ctx: dict, path: str):
    a, b = dig(ctx["stats_start"] or {}, path), dig(ctx["stats_end"] or {}, path)
    return None if a is None or b is None else b - a
