"""One value of `/v1/stats` at one end of the window, by dotted path."""


def dig(stats: dict, path: str):
    node = stats
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def read(ctx: dict, path: str, at: str = "end", scale: float = 1.0):
    value = dig(ctx["stats_" + at] or {}, path)
    return None if value is None else value * scale
