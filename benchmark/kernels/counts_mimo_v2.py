"""Operations and bytes the MiMo-V2-Flash block NEEDS, from the published
config.json keys of its configuration file, by `counts.py`'s rule: the
model's own widths (keys `head_dim` wide, values `v_head_dim` wide: a pool
that stores keys padded counts as the model's), a window layer counts
min(position + 1, sliding_window) keys, an expert layer counts the router
at full width and, of the experts, what EVEN routing sends to the share held
here: num_experts_per_tok x n_routed_experts_held / n_routed_experts experts
a token (0.5 at 8 x 16 / 256). How far a run's routing was from even is the
per-layer metric `moe_tokens_per_expert`; what the program computes beyond
that (every held expert over every token of a batch) is not counted.
"""

from __future__ import annotations

from kernels.counts import BYTES


def shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "hd": int(cfg["head_dim"]), "vd": int(cfg["v_head_dim"]),
        "kv": (int(cfg["num_key_value_heads"]), int(cfg["swa_num_key_value_heads"])),  # full, window
        "window": int(cfg["sliding_window"]), "ffn": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]), "routed": int(cfg["n_routed_experts"]),
        "held": int(cfg["n_routed_experts_held"]), "top": int(cfg["num_experts_per_tok"]),
        # the first `layers` of the published patterns run
        "attn": [int(v) for v in cfg["hybrid_layer_pattern"]][:layers],
        "moe": [int(v) for v in cfg["moe_layer_freq"]][:layers],
        "vocab": int(cfg["vocab_size"]), "bytes": BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def attention_params(cfg: dict, window: int) -> int:
    s = shapes(cfg)
    kv = s["kv"][window]
    return s["d"] * s["h"] * s["hd"] + s["d"] * kv * (s["hd"] + s["vd"]) + s["h"] * s["vd"] * s["d"]


def expert_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["expert"]


def token_matmul_params(cfg: dict) -> float:
    """Weights one token is multiplied with, over every layer that runs:
    attention, the dense FFN or the router whole and the held experts that
    even routing sends the token to."""
    s = shapes(cfg)
    total = 0.0
    for window, moe in zip(s["attn"], s["moe"]):
        total += attention_params(cfg, window)
        if moe:
            total += s["d"] * s["routed"] + s["top"] * s["held"] / s["routed"] * expert_params(cfg)
        else:
            total += 3 * s["d"] * s["ffn"]
    return total


def keys_seen(cfg: dict, window: int, position: int) -> int:
    """Keys a query at `position` attends in a full (0) or window (1) layer."""
    return min(position + 1, shapes(cfg)["window"]) if window else position + 1


def attention_flops(cfg: dict, first_pos: int, n_tokens: int) -> int:
    """QK^T (head_dim wide) and PV (v_head_dim wide) for queries at
    first_pos .. first_pos+n-1 over every layer, 2 flops a multiply-add."""
    s = shapes(cfg)
    total = 0
    for window in s["attn"]:
        if window:
            keys = sum(keys_seen(cfg, 1, p) for p in range(first_pos, first_pos + n_tokens))
        else:
            keys = n_tokens * first_pos + n_tokens * (n_tokens + 1) // 2
        total += 2 * s["h"] * (s["hd"] + s["vd"]) * keys
    return total


def head_flops(cfg: dict) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["vocab"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return prompt_len * 2 * token_matmul_params(cfg) + attention_flops(cfg, 0, prompt_len) + head_flops(cfg)


def decode_flops(cfg: dict, context_len: int) -> float:
    return 2 * token_matmul_params(cfg) + attention_flops(cfg, context_len, 1) + head_flops(cfg)


def kv_bytes_per_token(cfg: dict, window: int) -> int:
    """One layer's keys and values of one position."""
    s = shapes(cfg)
    return s["kv"][window] * (s["hd"] + s["vd"]) * s["bytes"]


def window_positions(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Positions a window layer holds live over the active slots: each
    slot's context, at most the window."""
    if not active_slots:
        return 0.0
    return active_slots * min(live_kv_tokens / active_slots, shapes(cfg)["window"])


def experts_hit(cfg: dict, active_slots: float) -> float:
    """Held experts that at least one of `active_slots` tokens is routed to,
    under even routing: held x (1 - (1 - top/routed)^tokens)."""
    s = shapes(cfg)
    return s["held"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** active_slots)


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: attention, router, norms, dense FFN
    and the output head once; the held experts the step's tokens hit; one
    embedding row a slot; the live keys and values (a window layer's: at most
    the window a slot)."""
    s = shapes(cfg)
    weights = s["d"] * s["vocab"] + s["d"]
    kv = 0.0
    for window, moe in zip(s["attn"], s["moe"]):
        weights += attention_params(cfg, window) + 2 * s["d"]
        if moe:
            weights += s["d"] * s["routed"] + s["routed"] + experts_hit(cfg, active_slots) * expert_params(cfg)
        else:
            weights += 3 * s["d"] * s["ffn"]
        positions = window_positions(cfg, active_slots, live_kv_tokens) if window else live_kv_tokens
        kv += positions * kv_bytes_per_token(cfg, window)
    return weights * s["bytes"] + active_slots * s["d"] * s["bytes"] + kv


def _decode_kernel(cfg: dict, window: int, active_slots: float, positions: float) -> tuple:
    """(bytes, flops) of ONE layer's decode attention over `positions` live
    keys and values: them, the queries in and the outputs out; each key and
    value meets its slot's query heads once."""
    s = shapes(cfg)
    nbytes = positions * kv_bytes_per_token(cfg, window) + active_slots * s["h"] * (s["hd"] + s["vd"]) * s["bytes"]
    return nbytes, 2 * s["h"] * (s["hd"] + s["vd"]) * positions


def full_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, 0, active_slots, live_kv_tokens)[0]


def full_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, 0, active_slots, live_kv_tokens)[1]


def swa_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, 1, active_slots, window_positions(cfg, active_slots, live_kv_tokens))[0]


def swa_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, 1, active_slots, window_positions(cfg, active_slots, live_kv_tokens))[1]
