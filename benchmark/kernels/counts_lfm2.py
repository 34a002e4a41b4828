"""Operations and bytes the LFM2-8B-A1B block NEEDS, from the published
config.json keys of its configuration file, by `counts.py`'s rule: a
convolution layer's in-projection, taps and out-projection in place of
attention (its state is read once a step and no key or value is), an
attention layer's GQA with the heads' norms (too small to count), the router
at full width, and for OPERATIONS the num_experts_per_tok routed experts a
token is sent to. For a decode step's BYTES every weight is read once and,
of the routed experts, as many as even routing of num_experts_per_tok x rows
draws over num_experts touches (never more than all of them); the
convolution state of every active slot is read once. Padding,
recomputation, the whole-batch expert product's rows of weight 0 and the
decode kernel's masked columns of a packed row are not counted.
"""

from __future__ import annotations

from kernels.counts import BYTES


def shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "h": h, "kv": int(cfg["num_key_value_heads"]), "hd": d // h,
        # the first `layers` of the published list run
        "conv": [v == "conv" for v in cfg["layer_types"]][:layers],
        "moe": [i >= int(cfg["num_dense_layers"]) for i in range(layers)],
        "taps": int(cfg["conv_L_cache"]), "ffn": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]), "routed": int(cfg["num_experts"]),
        "top": int(cfg["num_experts_per_tok"]), "bias": bool(cfg["use_expert_bias"]),
        "vocab": int(cfg["vocab_size"]), "bytes": BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def conv_params(cfg: dict) -> int:
    """W_in (three projections), the taps, W_out."""
    s = shapes(cfg)
    return 3 * s["d"] * s["d"] + s["taps"] * s["d"] + s["d"] * s["d"]


def attention_params(cfg: dict) -> int:
    """q and o, k and v (the heads' norms are two gains of a head's width)."""
    s = shapes(cfg)
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def expert_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["expert"]


def router_params(cfg: dict) -> int:
    s = shapes(cfg)
    return s["d"] * s["routed"] + (s["routed"] if s["bias"] else 0)


def attention_layers(cfg: dict) -> int:
    return sum(not c for c in shapes(cfg)["conv"])


def token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with, over every layer that runs."""
    s = shapes(cfg)
    total = 0
    for conv, moe in zip(s["conv"], s["moe"]):
        total += conv_params(cfg) if conv else attention_params(cfg)
        total += s["d"] * s["routed"] + s["top"] * expert_params(cfg) if moe else 3 * s["d"] * s["ffn"]
    return total


def param_count(cfg: dict) -> int:
    """What the chip holds: every layer whole (every expert), the norms, the
    embedding and an untied head."""
    s = shapes(cfg)
    total = 2 * s["vocab"] * s["d"] + s["d"]
    for conv, moe in zip(s["conv"], s["moe"]):
        total += conv_params(cfg) if conv else attention_params(cfg) + 2 * s["hd"]
        total += 2 * s["d"] + (router_params(cfg) + s["routed"] * expert_params(cfg) if moe else 3 * s["d"] * s["ffn"])
    return total


def attention_flops(cfg: dict, first_pos: int, n_tokens: int) -> int:
    """QK^T and PV for queries at first_pos .. first_pos+n-1 over every
    attention layer, 2 flops a multiply-add; a convolution layer attends nothing."""
    s = shapes(cfg)
    keys = n_tokens * first_pos + n_tokens * (n_tokens + 1) // 2
    return 4 * s["h"] * s["hd"] * keys * attention_layers(cfg)


def head_flops(cfg: dict) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["vocab"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return prompt_len * 2 * token_matmul_params(cfg) + attention_flops(cfg, 0, prompt_len) + head_flops(cfg)


def decode_flops(cfg: dict, context_len: int) -> float:
    return 2 * token_matmul_params(cfg) + attention_flops(cfg, context_len, 1) + head_flops(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """One attention layer's keys and values of one position."""
    s = shapes(cfg)
    return 2 * s["kv"] * s["hd"] * s["bytes"]


def conv_state_bytes_per_slot(cfg: dict) -> int:
    """What every convolution layer carries for a slot: its last taps - 1 rows of B * x."""
    s = shapes(cfg)
    return sum(s["conv"]) * (s["taps"] - 1) * s["d"] * s["bytes"]


def experts_touched(cfg: dict, rows: float) -> float:
    """Routed experts that at least one of `rows` tokens is sent to under even
    routing: num_experts x (1 - (1 - top / num_experts)^rows), never more than all."""
    s = shapes(cfg)
    return min(s["routed"], s["routed"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** rows))


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: every weight outside the routed
    experts once (the output head, one embedding row a slot); the routed
    experts the step's tokens touch; the live keys and values of the
    attention layers; the convolution state of every active slot."""
    s = shapes(cfg)
    weights = s["d"] * s["vocab"] + s["d"]
    for conv, moe in zip(s["conv"], s["moe"]):
        weights += (conv_params(cfg) if conv else attention_params(cfg) + 2 * s["hd"]) + 2 * s["d"]
        weights += router_params(cfg) + experts_touched(cfg, active_slots) * expert_params(cfg) if moe else 3 * s["d"] * s["ffn"]
    kv = live_kv_tokens * kv_bytes_per_token(cfg) * attention_layers(cfg)
    return weights * s["bytes"] + active_slots * (s["d"] * s["bytes"] + conv_state_bytes_per_slot(cfg)) + kv


def gqa_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes ONE attention layer's decode kernel must move: the live keys and
    values at the model's width (64 a head), the queries in and the outputs out."""
    s = shapes(cfg)
    return live_kv_tokens * kv_bytes_per_token(cfg) + 2 * active_slots * s["h"] * s["hd"] * s["bytes"]


def gqa_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Operations it needs: each live key and value meets its slot's 4 query heads of its KV head once."""
    s = shapes(cfg)
    return 4 * s["h"] * s["hd"] * live_kv_tokens
