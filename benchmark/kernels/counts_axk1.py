"""Operations and bytes the A.X-K1 block NEEDS, from the published config.json
keys of its configuration file, by `counts.py`'s rule.

Latent attention is counted in the form that needs least, a phase: a DECODE
step in the absorbed form (the query taken onto the latent, every cached row
met once by 64 heads over kv_lora_rank + qk_rope_head_dim for the score and
kv_lora_rank for the value, the output taken up through W_UV: kv_b_proj's
parameters are multiplied once a token either way), a PREFILL in the plain
form (a token's keys and values a head built ONCE from its latent, a (query,
key) pair 192 + 128 wide a head: 40,960 operations against the absorbed
139,264). The program rebuilds a prefix block's keys and values in every
chunk that meets it; that is recomputation and is not counted. A cached row
is kv_lora_rank + qk_rope_head_dim (576) wide whatever width the pool stores.

An expert layer counts the router at full width, the shared expert, and of the
routed experts what EVEN routing sends to the share held here:
num_experts_per_tok x n_routed_experts_held / n_routed_experts experts a token
(0.5 at 8 x 12 / 192: the other 7.5 of a token's experts lie on chips this run
does not have, and what they would add is left out of the result too). For a
decode step's BYTES, of the held experts as many as even routing of the step's
rows touches, never more than are held. How far a run's routing was from even
(the group limit moves it) is `moe_held_share_pct` and
`moe_rows_per_held_expert`; what the program computes beyond this (every held
expert over every row of a batch) is not counted.
"""

from __future__ import annotations

from kernels.counts import BYTES


def shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    dense_first, every = int(cfg["first_k_dense_replace"]), int(cfg["moe_layer_freq"])
    expert = int(cfg["moe_intermediate_size"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]), "q_rank": int(cfg["q_lora_rank"]),
        "kv_rank": int(cfg["kv_lora_rank"]), "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]), "ffn": int(cfg["intermediate_size"]), "expert": expert,
        "shared": int(cfg["n_shared_experts"]) * expert, "routed": int(cfg["n_routed_experts"]),
        "held": int(cfg["n_routed_experts_held"]), "top": int(cfg["num_experts_per_tok"]),
        # the first `layers` of the published pattern run
        "moe": [i >= dense_first and i % every == 0 for i in range(layers)],
        "vocab": int(cfg["vocab_size"]), "bytes": BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def attention_params(cfg: dict) -> int:
    """W_DQ, W_UQ, W_DKV, kv_b_proj (W_UK and W_UV) and W_O."""
    s = shapes(cfg)
    head = s["nope"] + s["rope"]
    return (
        s["d"] * s["q_rank"] + s["q_rank"] * s["h"] * head + s["d"] * (s["kv_rank"] + s["rope"])
        + s["kv_rank"] * s["h"] * (s["nope"] + s["vd"]) + s["h"] * s["vd"] * s["d"]
    )


def expert_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["expert"]


def shared_params(cfg: dict) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["shared"]


def token_matmul_params(cfg: dict) -> float:
    """Weights one token is multiplied with, over every layer that runs:
    attention (every projection once, in either form), the dense FFN, or the
    router whole, the shared expert and the held experts even routing sends
    the token to."""
    s = shapes(cfg)
    total = 0.0
    for moe in s["moe"]:
        total += attention_params(cfg)
        if moe:
            total += s["d"] * s["routed"] + shared_params(cfg) + s["top"] * s["held"] / s["routed"] * expert_params(cfg)
        else:
            total += 3 * s["d"] * s["ffn"]
    return total


def row_width(cfg: dict) -> int:
    """What a token leaves in the cache a layer: the latent and the one rope key."""
    s = shapes(cfg)
    return s["kv_rank"] + s["rope"]


def head_flops(cfg: dict) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["vocab"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The plain form: every (query, key) pair at or before the query, a head
    qk_nope + qk_rope wide for the score and v_head_dim for the value."""
    s = shapes(cfg)
    pairs = prompt_len * (prompt_len + 1) // 2
    attention = 2 * s["h"] * (s["nope"] + s["rope"] + s["vd"]) * pairs * len(s["moe"])
    return prompt_len * 2 * token_matmul_params(cfg) + attention + head_flops(cfg)


def decode_flops(cfg: dict, context_len: int) -> float:
    """The absorbed form: the query at position context_len meets
    context_len + 1 cached rows, a head row_width wide for the score and
    kv_lora_rank for the value."""
    s = shapes(cfg)
    attention = 2 * s["h"] * (row_width(cfg) + s["kv_rank"]) * (context_len + 1) * len(s["moe"])
    return 2 * token_matmul_params(cfg) + attention + head_flops(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """One layer's cached row of one position, at the model's width."""
    return row_width(cfg) * shapes(cfg)["bytes"]


def experts_hit(cfg: dict, rows: float) -> float:
    """Held experts that at least one of `rows` tokens is routed to, under
    even routing: held x (1 - (1 - top / routed)^rows), never more than held."""
    s = shapes(cfg)
    return min(s["held"], s["held"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** rows))


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: attention's five matrices, the norms
    (the two inner ones too), the router, the shared expert, the dense FFN and
    the output head once; the held experts the step's rows touch; one
    embedding row a slot; the live latent rows."""
    s = shapes(cfg)
    weights = s["d"] * s["vocab"] + s["d"]
    for moe in s["moe"]:
        weights += attention_params(cfg) + 2 * s["d"] + s["q_rank"] + s["kv_rank"]
        if moe:
            weights += s["d"] * s["routed"] + shared_params(cfg) + experts_hit(cfg, active_slots) * expert_params(cfg)
        else:
            weights += 3 * s["d"] * s["ffn"]
    kv = live_kv_tokens * kv_bytes_per_token(cfg) * len(s["moe"])
    return weights * s["bytes"] + active_slots * s["d"] * s["bytes"] + kv


def mla_decode_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """ONE layer's latent decode attention: the live rows, the absorbed
    queries in (row_width a head) and the mixes of latents out (kv_lora_rank a head)."""
    s = shapes(cfg)
    return live_kv_tokens * kv_bytes_per_token(cfg) + active_slots * s["h"] * (row_width(cfg) + s["kv_rank"]) * s["bytes"]


def mla_decode_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """Each live row meets its slot's heads once: the score over row_width,
    the value over kv_lora_rank, 2 operations a multiply-add (121 operations
    a byte of the row: this kernel is not plainly byte-bound)."""
    s = shapes(cfg)
    return 2 * s["h"] * (row_width(cfg) + s["kv_rank"]) * live_kv_tokens
