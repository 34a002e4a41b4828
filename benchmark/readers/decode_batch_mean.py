"""Tokens a decode step produced, on average over the window: the engine's
`tokens_generated` less the first tokens (those come out of prefill), over
its `steps`. `/v1/stats` has no counter of first tokens, so they are the
requests whose first token the client received inside the window."""

from readers.stats_value import dig


def read(ctx: dict):
    a, b = ctx["stats_start"] or {}, ctx["stats_end"] or {}
    steps = (dig(b, "steps") or 0) - (dig(a, "steps") or 0)
    tokens = (dig(b, "tokens_generated") or 0) - (dig(a, "tokens_generated") or 0)
    if steps <= 0:
        return None
    return max(0, tokens - ctx["client"]["first_tokens_in_window"]) / steps
