"""Operations and bytes the Laguna-XS.2 block NEEDS, from the published
config.json keys of its configuration file, by `counts.py`'s rule: a layer's
own query-head count (`num_attention_heads_per_layer`), min(position + 1,
sliding_window) keys in a sliding layer, the attention gate's projection (a
scalar a head), the
router at full width, and for OPERATIONS the num_experts_per_tok routed
experts a token is sent to plus the shared one. For a decode step's BYTES
every weight is read once and, of the routed experts, as many as even routing
of num_experts_per_tok x rows draws over num_experts touches (never more than
all of them): an expert no row reached need not be read (the program's
whole-batch product reads them all: the step's share of its bytes,
`decode_step_hbm_pct`, says what that costs). How many a run really touched is
the per-layer metric `moe_experts_touched_pct`; padding and recomputation are
not counted.
"""

from __future__ import annotations

from kernels.counts import BYTES

FULL, SLIDING = "full_attention", "sliding_attention"


def shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    return {
        "d": int(cfg["hidden_size"]), "hd": int(cfg["head_dim"]), "kv": int(cfg["num_key_value_heads"]),
        # the first `layers` of the published lists run
        "heads": [int(v) for v in cfg["num_attention_heads_per_layer"]][:layers],
        "attn": list(cfg["layer_types"])[:layers],
        "moe": [v == "sparse" for v in cfg["mlp_layer_types"]][:layers],
        "window": int(cfg["sliding_window"]), "gated": bool(cfg["gating"]),
        "ffn": int(cfg["intermediate_size"]), "expert": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["shared_expert_intermediate_size"]), "routed": int(cfg["num_experts"]),
        "top": int(cfg["num_experts_per_tok"]), "vocab": int(cfg["vocab_size"]),
        "bytes": BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def attention_params(cfg: dict, heads: int) -> int:
    """q and o at the layer's own head count, the gate's scalar a head; k and v."""
    s = shapes(cfg)
    return 2 * s["d"] * heads * s["hd"] + (s["d"] * heads if s["gated"] else 0) + 2 * s["d"] * s["kv"] * s["hd"]


def expert_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["expert"]


def shared_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["shared"]


def token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with, over every layer that runs."""
    s = shapes(cfg)
    total = 0
    for heads, moe in zip(s["heads"], s["moe"]):
        total += attention_params(cfg, heads)
        total += s["d"] * s["routed"] + s["top"] * expert_params(cfg) + shared_params(cfg) if moe else 3 * s["d"] * s["ffn"]
    return total


def keys_seen(cfg: dict, kind: str, position: int) -> int:
    """Keys a query at `position` attends in a layer of `kind`."""
    return min(position + 1, shapes(cfg)["window"]) if kind == SLIDING else position + 1


def attention_flops(cfg: dict, first_pos: int, n_tokens: int) -> int:
    """QK^T and PV for queries at first_pos .. first_pos+n-1 over every layer,
    2 flops a multiply-add, each layer at its own head count."""
    s = shapes(cfg)
    total = 0
    for heads, kind in zip(s["heads"], s["attn"]):
        if kind == SLIDING:
            keys = sum(keys_seen(cfg, kind, p) for p in range(first_pos, first_pos + n_tokens))
        else:
            keys = n_tokens * first_pos + n_tokens * (n_tokens + 1) // 2
        total += 4 * heads * s["hd"] * keys
    return total


def head_flops(cfg: dict) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["vocab"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return prompt_len * 2 * token_matmul_params(cfg) + attention_flops(cfg, 0, prompt_len) + head_flops(cfg)


def decode_flops(cfg: dict, context_len: int) -> float:
    return 2 * token_matmul_params(cfg) + attention_flops(cfg, context_len, 1) + head_flops(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """One layer's keys and values of one position (both kinds: 8 KV heads of head_dim)."""
    s = shapes(cfg)
    return 2 * s["kv"] * s["hd"] * s["bytes"]


def window_positions(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Positions a sliding layer holds live over the active slots: each slot's
    context, at most the window."""
    if not active_slots:
        return 0.0
    return active_slots * min(live_kv_tokens / active_slots, shapes(cfg)["window"])


def experts_touched(cfg: dict, rows: float) -> float:
    """Routed experts that at least one of `rows` tokens is sent to under even
    routing: num_experts x (1 - (1 - top / num_experts)^rows), never more than all."""
    s = shapes(cfg)
    return min(s["routed"], s["routed"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** rows))


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: attention with its gate, router,
    norms, dense FFN, shared expert and output head once; the routed experts
    the step's tokens touch; one embedding row a slot; the live keys and values
    (a sliding layer's: at most the window a slot)."""
    s = shapes(cfg)
    weights = s["d"] * s["vocab"] + s["d"]
    kv = 0.0
    for heads, kind, moe in zip(s["heads"], s["attn"], s["moe"]):
        weights += attention_params(cfg, heads) + 2 * s["d"]
        if moe:
            weights += s["d"] * s["routed"] + shared_params(cfg) + experts_touched(cfg, active_slots) * expert_params(cfg)
        else:
            weights += 3 * s["d"] * s["ffn"]
        positions = window_positions(cfg, active_slots, live_kv_tokens) if kind == SLIDING else live_kv_tokens
        kv += positions * kv_bytes_per_token(cfg)
    return weights * s["bytes"] + active_slots * s["d"] * s["bytes"] + kv


def _heads_of(cfg: dict, kind: str) -> int:
    s = shapes(cfg)
    return next(h for h, k in zip(s["heads"], s["attn"]) if k == kind)


def _decode_kernel(cfg: dict, kind: str, active_slots: float, positions: float) -> tuple:
    """(bytes, flops) of ONE layer's decode attention over `positions` live
    keys and values: them, the queries in and the outputs out; each key and
    value meets its slot's query heads once."""
    s = shapes(cfg)
    heads = _heads_of(cfg, kind)
    nbytes = positions * kv_bytes_per_token(cfg) + 2 * active_slots * heads * s["hd"] * s["bytes"]
    return nbytes, 4 * heads * s["hd"] * positions


def full_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, FULL, active_slots, live_kv_tokens)[0]


def full_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, FULL, active_slots, live_kv_tokens)[1]


def swa_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, SLIDING, active_slots, window_positions(cfg, active_slots, live_kv_tokens))[0]


def swa_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return _decode_kernel(cfg, SLIDING, active_slots, window_positions(cfg, active_slots, live_kv_tokens))[1]
