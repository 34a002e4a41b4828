"""Operations and bytes the model NEEDS, counted from the configuration's
shapes (the published config.json keys), whatever implements the step.

Padding, recomputation, gathers over dead pages and masked positions are
not counted: a share of a peak worked out from these can only fall short of
what the hardware did, never pass it.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def shapes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    # a published `head_dim` need not be hidden_size / heads; where the config gives none, it is
    hd = int(cfg["head_dim"]) if cfg.get("head_dim") else d // h
    return {
        "d": d, "h": h, "kv": kv, "hd": hd, "ffn": int(cfg["intermediate_size"]),
        "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "bytes": BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that every token is multiplied with."""
    s = shapes(cfg)
    attn = s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"] + s["h"] * s["hd"] * s["d"]
    return attn + 3 * s["d"] * s["ffn"]


def param_count(cfg: dict) -> int:
    s = shapes(cfg)
    norms = 2 * s["d"] * s["layers"] + s["d"]
    head = s["d"] * s["vocab"] * (1 if cfg.get("tie_word_embeddings") else 2)
    return layer_matmul_params(cfg) * s["layers"] + head + norms


def token_matmul_flops(cfg: dict) -> int:
    """Multiply-adds x2 of one token through every layer (no attention
    scores, no output head)."""
    return 2 * layer_matmul_params(cfg) * shapes(cfg)["layers"]


def head_flops(cfg: dict) -> int:
    """The output head for one position whose logits are needed."""
    s = shapes(cfg)
    return 2 * s["d"] * s["vocab"]


def attention_flops(cfg: dict, first_pos: int, n_tokens: int) -> int:
    """QK^T and PV for queries at positions first_pos .. first_pos+n-1, each
    attending the keys at or before it (causal; 2 products x 2 flops)."""
    s = shapes(cfg)
    keys = n_tokens * first_pos + n_tokens * (n_tokens + 1) // 2
    return 4 * s["h"] * s["hd"] * keys * s["layers"]


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A prompt of prompt_len tokens: every token through the layers, causal
    attention, one output head (the first token's logits)."""
    return prompt_len * token_matmul_flops(cfg) + attention_flops(cfg, 0, prompt_len) + head_flops(cfg)


def decode_flops(cfg: dict, context_len: int) -> int:
    """One generated token whose query sits at position context_len."""
    return token_matmul_flops(cfg) + attention_flops(cfg, context_len, 1) + head_flops(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    s = shapes(cfg)
    return 2 * s["kv"] * s["hd"] * s["bytes"] * s["layers"]


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: every layer weight and the output
    head once, one embedding row a slot, and the live keys and values of the
    active slots. Activations and writes are left out (small)."""
    s = shapes(cfg)
    weights = (layer_matmul_params(cfg) * s["layers"] + s["d"] * s["vocab"] + 2 * s["d"] * s["layers"] + s["d"]) * s["bytes"]
    return weights + active_slots * s["d"] * s["bytes"] + live_kv_tokens * kv_bytes_per_token(cfg)


def paged_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes the paged decode attention of ONE layer must move: the live
    keys and values, the queries in and the outputs out."""
    s = shapes(cfg)
    per_layer_kv = live_kv_tokens * kv_bytes_per_token(cfg) / s["layers"]
    return per_layer_kv + 2 * active_slots * s["h"] * s["hd"] * s["bytes"]


def paged_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Operations the paged decode attention of ONE layer needs: each live
    key and value meets its slot's query once (QK^T and PV)."""
    return attention_flops(cfg, 0, 1) * live_kv_tokens / cfg["num_hidden_layers"]
