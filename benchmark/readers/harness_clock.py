"""A time the harness took on its own clock (`ctx["timings"][key]`)."""


def read(ctx: dict, key: str):
    return ctx["timings"].get(key)
