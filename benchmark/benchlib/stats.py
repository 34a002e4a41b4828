"""Plain statistics over what the client recorded."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[min(len(ordered), rank) - 1]


def window_numbers(records: list, t0: float, window_s: float, give_up_s: float) -> dict:
    """End-to-end numbers of one window from the client's records.

    - ttft_ms: first token minus the time the request was due, over ALL
      requests of the window; one that failed or gave no token counts as the
      longest: the whole time the harness waited for it.
    - itl_ms: every gap between successive tokens of every request.
    - tokens_in_window: tokens received between the window's two ends.
    """
    close = t0 + window_s
    ttft, gaps, late = [], [], []
    tokens_in_window = 0
    for rec in records:
        if rec.cut and not rec.token_times:
            continue  # closed loop: sent just before the close, no token yet
        late.append(max(0.0, (rec.sent or rec.due) - rec.due) * 1e3)
        if rec.token_times and rec.error is None:
            ttft.append((rec.token_times[0] - rec.due) * 1e3)
        elif not rec.cut:
            ttft.append((close + give_up_s - rec.due) * 1e3)
        times = rec.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
        tokens_in_window += sum(1 for t in times if t0 <= t <= close)
    return {"ttft_ms": ttft, "itl_ms": gaps, "late_ms": late, "tokens_in_window": tokens_in_window}
