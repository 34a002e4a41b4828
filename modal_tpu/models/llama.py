"""Llama-3 family in pure functional JAX, TPU-first.

The reference platform never touches model math (SURVEY §2d) — this is the
workload layer the TPU build adds for the judged configs (BASELINE.json:
single-chip 8B greedy decode, 8B/70B FSDP pretrain).

Design choices for TPU/XLA:
- **Stacked layer params + `lax.scan` over layers**: one compiled layer body
  instead of n_layers inlined copies — 10-30x faster compiles, critical for
  cold-start-to-first-step.
- **bfloat16 weights/activations, fp32 accumulation** where it matters
  (attention logits, softmax, RMSNorm reductions) — keeps matmuls on the MXU
  at full rate without fp32 memory traffic.
- **Static shapes everywhere**: fixed max_seq KV cache with position masking;
  decode is a fixed-shape single-token step.
- **GQA**: n_kv_heads < n_heads (8B: 32/8; 70B: 64/8), KV cache stores only
  kv heads.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    norm_eps: float = 1e-5
    rope_theta: float = 500_000.0
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # MoE (expert-parallel FFN, switch-style top-1 routing — parallel/moe.py).
    # 0 = dense SwiGLU FFN. When > 0 each layer's FFN is n_experts experts of
    # width ffn_dim with a load-balancing aux loss.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        embed = self.vocab_size * self.dim
        if self.is_moe:
            ffn = self.dim * self.n_experts + 2 * self.n_experts * self.dim * self.ffn_dim
        else:
            ffn = 3 * self.dim * self.ffn_dim  # w1, w2, w3
        per_layer = (
            self.dim * self.n_heads * self.head_dim  # wq
            + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.dim  # wo
            + ffn
            + 2 * self.dim  # norms
        )
        return embed * 2 + per_layer * self.n_layers + self.dim


# Llama-3 architecture hyperparameters (public: Meta Llama 3 release).
CONFIGS: dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(
        name="tiny", vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=256, max_seq_len=256,
    ),
    "debug-1l": LlamaConfig(
        name="debug-1l", vocab_size=256, dim=64, n_layers=1, n_heads=2, n_kv_heads=1,
        ffn_dim=128, max_seq_len=128,
    ),
    "llama3-1b-proxy": LlamaConfig(
        name="llama3-1b-proxy", vocab_size=128_256, dim=2048, n_layers=16, n_heads=32,
        n_kv_heads=8, ffn_dim=8192, max_seq_len=8192,
    ),
    "llama3-8b": LlamaConfig(
        name="llama3-8b", vocab_size=128_256, dim=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
    ),
    "llama3-70b": LlamaConfig(
        name="llama3-70b", vocab_size=128_256, dim=8192, n_layers=80, n_heads=64,
        n_kv_heads=8, ffn_dim=28672, max_seq_len=8192,
    ),
    # MoE variants: switch-style top-1 expert FFNs (Mixtral-scale proxy at
    # the top; tiny-moe for tests/dryrun)
    "tiny-moe": LlamaConfig(
        name="tiny-moe", vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=256, max_seq_len=256, n_experts=4,
    ),
    "llama3-8x7b-proxy": LlamaConfig(
        name="llama3-8x7b-proxy", vocab_size=128_256, dim=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, ffn_dim=14336, max_seq_len=8192, n_experts=8,
    ),
}


def get_config(name: Any, **overrides: Any) -> LlamaConfig:
    """`name` is a preset name, a LlamaConfig, or a mapping
    `{"name": preset, **field overrides}` — the picklable form the entry
    points (`llm_service`, `train_demo`) take from a parent process that must
    not import jax, e.g. `{"name": "llama3-8b", "n_layers": 16}`."""
    if isinstance(name, LlamaConfig):
        cfg = name
    elif isinstance(name, str):
        cfg = CONFIGS[name]
    else:
        spec = dict(name)
        cfg = CONFIGS[spec.pop("name")]
        overrides = {**spec, **overrides}
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
# Layer params are STACKED along axis 0 (n_layers leading) so the forward
# pass scans over them with one compiled body.


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    hd = cfg.head_dim
    init = jax.nn.initializers.normal(stddev=0.02)

    def layer_init(k: jax.Array) -> dict:
        ks = jax.random.split(k, 7)
        layer = {
            "attn_norm": jnp.ones((cfg.dim,), cfg.dtype),
            "wq": init(ks[0], (cfg.dim, cfg.n_heads * hd), cfg.dtype),
            "wk": init(ks[1], (cfg.dim, cfg.n_kv_heads * hd), cfg.dtype),
            "wv": init(ks[2], (cfg.dim, cfg.n_kv_heads * hd), cfg.dtype),
            "wo": init(ks[3], (cfg.n_heads * hd, cfg.dim), cfg.dtype),
            "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype),
        }
        if cfg.is_moe:
            layer.update({
                "router": init(ks[4], (cfg.dim, cfg.n_experts), cfg.dtype),
                "w_in": init(ks[5], (cfg.n_experts, cfg.dim, cfg.ffn_dim), cfg.dtype),
                "w_out": init(ks[6], (cfg.n_experts, cfg.ffn_dim, cfg.dim), cfg.dtype),
            })
        else:
            layer.update({
                "w_gate": init(ks[4], (cfg.dim, cfg.ffn_dim), cfg.dtype),
                "w_up": init(ks[5], (cfg.dim, cfg.ffn_dim), cfg.dtype),
                "w_down": init(ks[6], (cfg.ffn_dim, cfg.dim), cfg.dtype),
            })
        return layer

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(layer_init)(layer_keys)  # stacked: leading axis n_layers
    return {
        "embed": init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": init(k_out, (cfg.dim, cfg.vocab_size), cfg.dtype),
    }


def init_params_abstract(cfg: LlamaConfig) -> dict:
    """ShapeDtypeStruct pytree (for sharding planning / orbax restore)."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    # fp32 reduction, bf16 output — matches TPU best practice.
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def rope_frequencies(cfg: LlamaConfig) -> jax.Array:
    """[head_dim/2] inverse frequencies."""
    hd = cfg.head_dim
    exponents = jnp.arange(0, hd, 2, dtype=jnp.float32) / hd
    return 1.0 / (cfg.rope_theta**exponents)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, hd/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, n_kv, hd] -> [B, S, n_kv*n_rep, hd] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, s, nkv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, nkv, n_rep, hd)).reshape(b, s, nkv * n_rep, hd)


def attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, H, hd]
    v: jax.Array,  # [B, Sk, H, hd]
    mask: Optional[jax.Array] = None,  # [B, 1, Sq, Sk] additive (0 / -inf)
) -> jax.Array:
    """Reference attention: einsum QK^T → softmax(fp32) → V. The pallas
    flash-attention kernel in ops/attention.py replaces this on TPU for long
    sequences (same signature).

    attn_impl contract (shared by flash/ring implementations): `mask=None`
    means pure causal attention with q and k aligned at position 0 — only
    valid when Sq == Sk; KV-cache calls must pass an explicit mask."""
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    if mask is None:
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                f"mask=None implies aligned causal attention but Sq={q.shape[1]} != Sk={k.shape[1]}"
            )
        causal = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), jnp.bool_))
        mask = jnp.where(causal, 0.0, -jnp.inf).astype(jnp.float32)[None, None, :, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class KVCache(NamedTuple):
    """Static-shape cache: [n_layers, B, max_seq, n_kv, hd]."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # [] int32 — filled positions

    @staticmethod
    def create(cfg: LlamaConfig, batch: int, max_len: Optional[int] = None) -> "KVCache":
        max_len = max_len or cfg.max_seq_len
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return KVCache(
            k=jnp.zeros(shape, cfg.dtype),
            v=jnp.zeros(shape, cfg.dtype),
            length=jnp.zeros((), jnp.int32),
        )


def _layer_forward(
    cfg: LlamaConfig,
    x: jax.Array,  # [B, S, D]
    layer: dict,
    positions: jax.Array,  # [B, S]
    mask: Optional[jax.Array],  # [B, 1, S, Sk] additive, or None = causal
    inv_freq: jax.Array,
    cache_kv: Optional[tuple[jax.Array, jax.Array]],  # ([B, max, n_kv, hd], ...)
    cache_offset: Optional[jax.Array],
    attn_impl: Optional[Any] = None,  # custom attention (ring/pallas); (q,k,v,mask)->out
) -> tuple[jax.Array, Optional[tuple[jax.Array, jax.Array]], jax.Array]:
    """Returns (x, new_cache, aux) — aux is the MoE load-balancing loss for
    this layer (0.0 for dense FFN layers)."""
    from .quant import qmm

    b, s, d = x.shape
    hd = cfg.head_dim
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = qmm(h, layer["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = qmm(h, layer["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = qmm(h, layer["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)

    if cache_kv is not None:
        ck, cv = cache_kv
        ck = lax.dynamic_update_slice_in_dim(ck, k, cache_offset, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cv, v, cache_offset, axis=1)
        k_att, v_att = ck, cv
        new_cache = (ck, cv)
    else:
        k_att, v_att = k, v
        new_cache = None

    n_rep = cfg.n_heads // cfg.n_kv_heads
    attn_fn = attn_impl or attention
    attn_out = attn_fn(q, repeat_kv(k_att, n_rep), repeat_kv(v_att, n_rep), mask)
    x = x + qmm(attn_out.reshape(b, s, cfg.n_heads * hd), layer["wo"])

    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    if cfg.is_moe:
        from ..parallel.moe import moe_ffn

        y, aux, _dropped = moe_ffn(
            h.reshape(b * s, d),
            {"router": layer["router"], "w_in": layer["w_in"], "w_out": layer["w_out"]},
            cfg.capacity_factor,
            act=jax.nn.silu,
        )
        x = x + y.reshape(b, s, d)
    else:
        gated = jax.nn.silu(qmm(h, layer["w_gate"]).astype(jnp.float32)).astype(x.dtype) * qmm(h, layer["w_up"])
        x = x + qmm(gated, layer["w_down"])
        aux = jnp.zeros((), jnp.float32)
    return x, new_cache, aux


def forward(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32
    positions: Optional[jax.Array] = None,  # [B, S]
    cache: Optional[KVCache] = None,
    attn_impl: Optional[Any] = None,  # e.g. ring attention for seq-parallel training
    remat: bool = False,  # checkpoint the layer scan body (per-layer remat)
) -> tuple[jax.Array, Optional[KVCache]]:
    """Full forward pass. Without cache: causal training/prefill forward.
    With cache: writes K/V at cache.length and attends over the cache
    (prefill chunks or single-token decode). Returns (logits, new_cache)."""
    logits, new_cache, _ = forward_with_aux(params, cfg, tokens, positions, cache, attn_impl, remat)
    return logits, new_cache


def forward_with_aux(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32
    positions: Optional[jax.Array] = None,  # [B, S]
    cache: Optional[KVCache] = None,
    attn_impl: Optional[Any] = None,
    remat: bool = False,
) -> tuple[jax.Array, Optional[KVCache], jax.Array]:
    """`forward` plus the mean per-layer MoE load-balancing aux loss (0.0
    for dense configs) — the training loss adds cfg.moe_aux_coef * aux."""
    b, s = tokens.shape
    if positions is None:
        base = cache.length if cache is not None else jnp.zeros((), jnp.int32)
        positions = base + jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    from .quant import qembed, qmm

    x = qembed(params["embed"], tokens)  # gather: [B, S, D]
    inv_freq = rope_frequencies(cfg)

    if cache is None:
        # mask=None = "pure causal, 0-aligned" per the attn_impl contract:
        # lets flash/ring impls use their internal causal masking (the pallas
        # kernel never materializes the [S, S] mask in HBM).
        # Default attention for the no-cache (training / full prefill) path
        # is the flash kernel — pallas forward+backward on TPU, einsum
        # fallback elsewhere (ops/attention.py dispatch).
        if attn_impl is None:
            from ..ops.attention import flash_attention

            attn_impl = flash_attention

        def body(carry, layer):
            x_carry, aux_acc = carry
            x_out, _, aux = _layer_forward(
                cfg, x_carry, layer, positions, None, inv_freq, None, None, attn_impl
            )
            return (x_out, aux_acc + aux), None

        if remat:
            # Checkpoint the scan BODY, not the whole forward: the backward
            # pass then recomputes one layer at a time from the inter-layer
            # carries, so peak residency is one layer's activations instead of
            # all n_layers at once.
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_sum), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
        new_cache = None
    else:
        max_len = cache.k.shape[2]
        offset = cache.length
        # attend to cache positions < offset + s, and causally within the block
        kv_pos = jnp.arange(max_len, dtype=jnp.int32)[None, None, None, :]
        q_pos = positions[:, None, :, None]
        visible = kv_pos <= q_pos
        mask = jnp.where(visible, 0.0, -jnp.inf).astype(jnp.float32)

        def body(carry, layer_and_cache):
            x_carry, aux_acc = carry
            layer, ck, cv = layer_and_cache
            x_out, new_kv, aux = _layer_forward(
                cfg, x_carry, layer, positions, mask, inv_freq, (ck, cv), offset
            )
            return (x_out, aux_acc + aux), new_kv

        (x, aux_sum), stacked_kv = lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["layers"], cache.k, cache.v)
        )
        new_cache = KVCache(k=stacked_kv[0], v=stacked_kv[1], length=offset + s)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache, aux_sum / cfg.n_layers


# ---------------------------------------------------------------------------
# Loss (training)
# ---------------------------------------------------------------------------


def causal_lm_loss(
    params: dict, cfg: LlamaConfig, tokens: jax.Array, attn_impl: Optional[Any] = None
) -> jax.Array:
    """Next-token cross-entropy, mean over all positions."""
    logits, _ = forward(params, cfg, tokens[:, :-1], attn_impl=attn_impl)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
