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


# the names config.json files give a layer's kinds in their per-layer lists
_PATTERN_NAMES = {"full_attention": 0, "sliding_attention": 1, "conv": 2, "dense": 0, "sparse": 1}
CONV = 2  # attn_pattern's value of a gated short-convolution layer


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is, for the served path: its attention (query and KV
    heads, a window or none, a sink or none, a gate on its output or none,
    its rotary rule: how much of a head turns, on what base, YaRN's blend or
    none) and its FFN (dense, or the routed experts the config describes).
    Hashable: part of a jit key."""

    n_heads: int
    n_kv_heads: int
    window: int = 0  # 0 = full causal attention
    sink: bool = False
    rope_theta: float = 500_000.0
    rope_dim: int = 0  # a head's first dims that turn
    # YaRN: (factor, original positions, beta_fast, beta_slow, attention factor); () = plain rotary
    yarn: tuple = ()
    gated: bool = False  # sigmoid(h Wg), one scalar a query head, multiplies that head's attention output before wo
    experts: bool = False
    attn_name: str = ""  # "", "full", "swa" or "mla": suffix of the scope's and the decode kernel's name
    # latent attention: (query rank, latent rank, nope width, rope width, value width) of a head. The
    # cache holds ONE row a token, [latent | shared rope key]; `n_kv_heads` is then 1 and `rope_dim` the
    # rope width. () = keys and values a KV head
    latent: tuple = ()
    softmax_scale: float = 0.0  # 0 = 1 / sqrt(head width); a YaRN with `mscale_all_dim` scales it
    qk_norm: bool = False  # an RMSNorm over each query and key head before rotary
    # a gated short convolution of this many taps IN PLACE of attention: [B | C | x] = h W_in,
    # y = (C * conv(B * x)) W_out, depthwise and causal. It reads no page: what it carries from one
    # step to the next is its last `conv - 1` rows of B * x a slot. 0 = attention
    conv: int = 0


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
    # -- what a layer is, where the layers are not all alike (served path:
    # models/paged_kv.py; `layer_kinds` below turns these into one LayerKind
    # a layer). Every default is "the dense block, every layer alike".
    qk_head_dim: int = 0  # query/key head width; 0 = dim // n_heads
    v_head_dim: int = 0  # value head width; 0 = the query/key width
    rope_fraction: float = 1.0  # rotary covers the first int(head_dim * fraction) dims of a head
    value_scale: float = 1.0  # v is multiplied by this before it is cached
    attn_pattern: tuple = ()  # a layer: 0 full attention, 1 sliding window, 2 short convolution; () = all full
    window: int = 0  # a window layer's query at p sees keys p-window+1 .. p
    window_kv_heads: int = 0  # KV heads of a window layer; 0 = n_kv_heads
    window_rope_theta: float = 0.0  # rotary base of a window layer; 0 = rope_theta
    window_sink: bool = False  # a learned logit a query head joins the window softmax's denominator
    ffn_pattern: tuple = ()  # a layer: 0 dense SwiGLU, 1 routed experts; () = all dense
    n_routed_experts: int = 0  # the router's width (sigmoid scores, top-k, weights renormalised)
    experts_per_token: int = 0
    expert_dim: int = 0  # one routed expert's SwiGLU width
    # the share of the routed experts that lives here (expert parallelism's
    # cut): experts experts_held_start .. +n_experts_held; 0 = all of them
    n_experts_held: int = 0
    experts_held_start: int = 0
    router_bias: bool = True  # a stored selection bias joins the scores for the top-k (never the weights)
    routed_scale: float = 1.0  # the renormalised weights of the chosen experts are multiplied by this
    shared_expert_dim: int = 0  # a SwiGLU every token passes beside the routed experts; 0 = none
    attn_gate: bool = False  # the attention output is gated, a sigmoid scalar a head, from the layer's normed input
    n_heads_per_layer: tuple = ()  # query heads a layer; () = n_heads in every layer
    # a rotary rule a layer type, as config.json files publish it: {"full_attention": {...},
    # "sliding_attention": {...}} with rope_theta, partial_rotary_factor, rope_type ("default" or
    # "yarn") and YaRN's numbers; a type it does not name keeps rope_theta / rope_fraction above
    rope_parameters: Any = ()
    # latent (MLA) attention in every layer, kv_rank > 0: h -> a latent of kv_rank and ONE rope key of
    # qk_rope_dim a token (all that is cached), the query through a rank of q_rank; a head is
    # [qk_nope_dim | qk_rope_dim] wide against keys rebuilt from the latent, its values v_head_dim wide
    q_rank: int = 0
    kv_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    # one YaRN for every layer, as the DeepSeek family publishes it: {"type": "yarn", "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}; with
    # m(s, a) = 0.1 a ln s + 1, cos and sin are multiplied by m(factor, mscale) / m(factor,
    # mscale_all_dim) and the softmax's scale by m(factor, mscale_all_dim)^2
    rope_scaling: Any = ()
    # the router's group limit: the routed experts lie in n_group groups of consecutive ones, a group
    # scores the sum of its two best experts, the best topk_group groups stay; 1 = no limit
    n_group: int = 1
    topk_group: int = 1
    conv_taps: int = 0  # a convolution layer's taps (config.json's conv_L_cache)
    qk_norm: bool = False  # every attention layer norms each query and key head before rotary

    def __post_init__(self):
        for field in ("attn_pattern", "ffn_pattern", "n_heads_per_layer"):
            # a depth cut keeps the first n_layers of a longer pattern: the
            # published one, so {"name": preset, "n_layers": 7} is a cut
            pattern = tuple(_PATTERN_NAMES[v] if isinstance(v, str) else int(v) for v in getattr(self, field))
            if pattern and len(pattern) < self.n_layers:
                raise ValueError(f"{field} names {len(pattern)} layers, n_layers is {self.n_layers}")
            object.__setattr__(self, field, pattern[: self.n_layers])  # a tuple: a list (JSON) would not hash under jit
        rules = self.rope_parameters.items() if isinstance(self.rope_parameters, dict) else self.rope_parameters
        object.__setattr__(self, "rope_parameters", tuple(
            (name, tuple(sorted(dict(rule).items()))) for name, rule in rules if not isinstance(rule, (int, float))
        ))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if 1 in self.attn_pattern and self.window < 1:
            raise ValueError("attn_pattern has window layers but window is 0")
        if CONV in self.attn_pattern and self.conv_taps < 2:
            raise ValueError(f"attn_pattern has convolution layers: conv_taps must be 2 or more, it is {self.conv_taps}")
        if any(self.ffn_pattern):
            lo, n = self.experts_held
            if not (0 < self.experts_per_token <= self.n_routed_experts and self.expert_dim > 0):
                raise ValueError("ffn_pattern has expert layers: n_routed_experts, experts_per_token and expert_dim must be set")
            if not (0 <= lo and n > 0 and lo + n <= self.n_routed_experts):
                raise ValueError(f"experts held {lo}..{lo + n} lie outside the {self.n_routed_experts} routed experts")
            if self.n_routed_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
                raise ValueError(f"{self.n_routed_experts} routed experts in {self.n_group} groups of which {self.topk_group} stay")
            if self.experts_per_token > self.topk_group * (self.n_routed_experts // self.n_group):
                raise ValueError(f"top-{self.experts_per_token} of {self.topk_group} groups of {self.n_routed_experts // self.n_group}")
        if self.kv_rank and not (self.q_rank > 0 and self.qk_nope_dim > 0 and self.qk_rope_dim > 0 and self.v_head_dim > 0):
            raise ValueError("latent attention (kv_rank): q_rank, qk_nope_dim, qk_rope_dim and v_head_dim must be set")
        if self.kv_rank and self.attn_pattern:
            raise ValueError("latent attention beside window layers: a latent row has no window pool")
        for kind in self.layer_kinds:
            if kind.rope_dim % 2:
                raise ValueError(f"rotary width {kind.rope_dim} of a head of {self.head_dim} must be even")
            if kind.n_heads % kind.n_kv_heads:
                raise ValueError(f"{kind.n_heads} query heads over {kind.n_kv_heads} KV heads")

    @property
    def head_dim(self) -> int:
        if self.kv_rank:
            return self.qk_nope_dim + self.qk_rope_dim
        return self.qk_head_dim or self.dim // self.n_heads

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rope_dim(self) -> int:
        return self._rope_width(self.rope_fraction)

    def _rope_width(self, fraction: float) -> int:
        return self.head_dim if fraction == 1.0 else int(self.head_dim * fraction)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def experts_held(self) -> tuple:
        """(first, count) of the routed experts whose weights live here."""
        return self.experts_held_start, self.n_experts_held or self.n_routed_experts

    @property
    def layer_kinds(self) -> tuple:
        """One LayerKind a layer: the description the served path runs by."""
        kinds = []
        rules, scaling = {name: dict(rule) for name, rule in self.rope_parameters}, dict(self.rope_scaling)
        if scaling and scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling {scaling}: the served path scales rotary positions by YaRN or not at all")
        latent = (self.q_rank, self.kv_rank, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim) if self.kv_rank else ()
        for i in range(self.n_layers):
            pattern = self.attn_pattern[i] if self.attn_pattern else 0
            windowed = pattern == 1
            routed = bool(self.ffn_pattern and self.ffn_pattern[i])
            if pattern == CONV:
                kinds.append(LayerKind(n_heads=1, n_kv_heads=1, rope_dim=0, experts=routed, conv=self.conv_taps))
                continue
            rule = rules.get("sliding_attention" if windowed else "full_attention", {})
            if rule.get("rope_type", "default") not in ("default", "yarn"):
                raise ValueError(f"rope_type {rule['rope_type']!r}: the served path turns heads by plain rotary or YaRN")
            yarn, softmax_scale = (), 0.0
            if rule.get("rope_type") == "yarn":
                factor = float(rule["factor"])
                yarn = (
                    factor, int(rule["original_max_position_embeddings"]), float(rule.get("beta_fast", 32)),
                    float(rule.get("beta_slow", 1)), float(rule.get("attention_factor") or yarn_mscale(factor, 1.0)),
                )
            elif scaling:
                factor, all_dim = float(scaling["factor"]), float(scaling.get("mscale_all_dim", 0))
                yarn = (
                    factor, int(scaling["original_max_position_embeddings"]), float(scaling.get("beta_fast", 32)),
                    float(scaling.get("beta_slow", 1)), yarn_mscale(factor, float(scaling.get("mscale", 1))) / yarn_mscale(factor, all_dim),
                )
                softmax_scale = self.head_dim ** -0.5 * yarn_mscale(factor, all_dim) ** 2
            kinds.append(LayerKind(
                n_heads=self.n_heads_per_layer[i] if self.n_heads_per_layer else self.n_heads,
                # to the cache a latent layer is one KV head under every query head
                n_kv_heads=1 if latent else (self.window_kv_heads or self.n_kv_heads) if windowed else self.n_kv_heads,
                window=self.window if windowed else 0,
                sink=windowed and self.window_sink,
                rope_theta=float(rule.get("rope_theta") or ((self.window_rope_theta or self.rope_theta) if windowed else self.rope_theta)),
                rope_dim=self.qk_rope_dim if latent else self._rope_width(float(rule.get("partial_rotary_factor", self.rope_fraction))),
                yarn=yarn,
                gated=self.attn_gate,
                experts=routed,
                # the dense models' scope and kernel keep their names; a model
                # with a layer pattern tells its kinds apart in a trace
                attn_name="mla" if latent else ("swa" if windowed else "full") if self.attn_pattern else "",
                latent=latent,
                softmax_scale=softmax_scale,
                qk_norm=self.qk_norm,
            ))
        return tuple(kinds)

    @property
    def layer_groups(self) -> tuple:
        """Runs of consecutive like layers, (kind, first, count) each: one
        compiled body and one stacked parameter tree a group."""
        groups: list = []
        for i, kind in enumerate(self.layer_kinds):
            if groups and groups[-1][0] == kind:
                groups[-1][2] += 1
            else:
                groups.append([kind, i, 1])
        return tuple((k, first, n) for k, first, n in groups)

    @property
    def uniform(self) -> bool:
        """Every layer alike with a dense FFN and keys and values a KV head:
        `params["layers"]` is ONE stacked tree and the KV pool one array (the
        dense presets). Otherwise
        both are tuples, one entry a group of `layer_groups`."""
        return not (self.attn_pattern or self.ffn_pattern or self.kv_rank or self.qk_norm)

    @property
    def has_window(self) -> bool:
        return any(k.window for k in self.layer_kinds)

    @property
    def has_conv(self) -> bool:
        return any(k.conv for k in self.layer_kinds)

    @property
    def has_experts(self) -> bool:
        return any(k.experts for k in self.layer_kinds)

    def param_count(self) -> int:
        if not self.uniform:
            # what is HELD here: the experts this share holds, the router whole
            hd, vd, (_lo, held) = self.head_dim, self.v_dim, self.experts_held
            total = 2 * self.vocab_size * self.dim + self.dim
            for k in self.layer_kinds:
                if k.conv:
                    total += self.dim * 3 * self.dim + k.conv * self.dim + self.dim * self.dim  # W_in, the taps, W_out
                elif k.latent:
                    q_rank, kv_rank, nope, rope, _vd = k.latent
                    total += self.dim * (q_rank + kv_rank + rope) + q_rank + kv_rank  # the two down-projections, their norms
                    total += q_rank * k.n_heads * hd + kv_rank * k.n_heads * (nope + vd) + k.n_heads * vd * self.dim
                else:
                    total += self.dim * (k.n_heads * hd + k.n_kv_heads * (hd + vd)) + k.n_heads * vd * self.dim
                total += 2 * self.dim + (k.n_heads if k.sink else 0) + (self.dim * k.n_heads if k.gated else 0)
                total += 2 * hd if k.qk_norm else 0
                if k.experts:
                    total += (self.dim + self.router_bias) * self.n_routed_experts
                    total += 3 * self.dim * (held * self.expert_dim + self.shared_expert_dim)
                else:
                    total += 3 * self.dim * self.ffn_dim
            return total
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


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(s, a) = 0.1 a ln s + 1 (1 at s <= 1): the DeepSeek family's rule for
    what a YaRN of factor s does to cos / sin and to the softmax's scale."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


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
    # MiMo-V2-Flash as published (XiaomiMiMo/MiMo-V2-Flash config.json): 5
    # window layers (128 positions, a learned sink a head, 8 KV heads, rotary
    # base 1e4) to 1 full (4 KV heads, base 5e6), keys 192 wide beside values
    # 128 wide, rotary on the first 64, a leading dense layer and then 256
    # routed experts, 8 a token, sigmoid scores with a selection bias. Whole it
    # is 309 B parameters: a chip serves a depth cut and its share of the
    # experts and of the vocabulary (n_layers + the two patterns, n_experts_held,
    # vocab_size; benchmark/configs/mimo-v2-flash-serve-1chip-ep16.json).
    "mimo-v2-flash": LlamaConfig(
        name="mimo-v2-flash", vocab_size=152_576, dim=4096, n_layers=48, n_heads=64,
        n_kv_heads=4, ffn_dim=16384, norm_eps=1e-5, rope_theta=5_000_000.0, max_seq_len=262_144,
        qk_head_dim=192, v_head_dim=128, rope_fraction=0.334, value_scale=0.707,
        attn_pattern=tuple(0 if i % 6 == 5 or i == 0 else 1 for i in range(48)),
        window=128, window_kv_heads=8, window_rope_theta=10_000.0, window_sink=True,
        ffn_pattern=(0,) + (1,) * 47, n_routed_experts=256, experts_per_token=8, expert_dim=2048,
    ),
    # the same description at a size the CPU tests hold: the three layer kinds
    # in the same order, 8 of 32 experts held, top-4, keys 24 / values 16 wide
    "tiny-mimo": LlamaConfig(
        name="tiny-mimo", vocab_size=512, dim=64, n_layers=7, n_heads=8, n_kv_heads=2,
        ffn_dim=128, max_seq_len=256, qk_head_dim=24, v_head_dim=16, rope_fraction=0.334,
        value_scale=0.707, attn_pattern=(0, 1, 1, 1, 1, 0, 1), window=8, window_kv_heads=4,
        window_rope_theta=10_000.0, window_sink=True, ffn_pattern=(0, 1, 1, 1, 1, 1, 1),
        n_routed_experts=32, experts_per_token=4, expert_dim=32, n_experts_held=8,
    ),
    # Laguna-XS.2 as published (poolside/Laguna-XS.2 config.json): 1 full layer
    # (48 query heads, YaRN x64 on half a head, base 5e5) to 3 sliding ones (64
    # query heads, a window of 512, plain rotary on the whole head, base 1e4),
    # 8 KV heads of 128 everywhere, a sigmoid gate a head on the attention output, a
    # leading dense layer and then 256 routed experts of 512, 8 a token, their
    # renormalised sigmoid weights times 2.5, beside a shared expert of 512. The
    # three per-layer lists are kept whole so that {"name": "laguna-xs.2",
    # "n_layers": 5} is a cut (benchmark/configs/laguna-xs.2-serve-1chip.json,
    # whose `assumed` says which readings of the row these are).
    "laguna-xs.2": LlamaConfig(
        name="laguna-xs.2", vocab_size=100_352, dim=2048, n_layers=40, n_heads=48, n_kv_heads=8,
        ffn_dim=8192, norm_eps=1e-6, rope_theta=500_000.0, max_seq_len=262_144, qk_head_dim=128,
        rope_fraction=0.5, attn_pattern=(0, 1, 1, 1) * 10, window=512, n_heads_per_layer=(48, 64, 64, 64) * 10,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500_000, "factor": 64, "original_max_position_embeddings": 4096,
                "beta_fast": 64, "beta_slow": 1, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 10_000, "partial_rotary_factor": 1},
        },
        attn_gate=True, ffn_pattern=(0,) + (1,) * 39, n_routed_experts=256, experts_per_token=8, expert_dim=512,
        shared_expert_dim=512, routed_scale=2.5, router_bias=False,
    ),
    # the same description at a size the CPU tests hold: the five layer kinds in
    # the same order, 6 and 8 query heads a KV head, all 32 experts held, top-4,
    # a shared expert, YaRN on half a head (a ramp over frequencies 0..3), window 8
    "tiny-laguna": LlamaConfig(
        name="tiny-laguna", vocab_size=512, dim=64, n_layers=5, n_heads=12, n_kv_heads=2,
        ffn_dim=128, norm_eps=1e-6, rope_theta=100.0, max_seq_len=256, qk_head_dim=16,
        rope_fraction=0.5, attn_pattern=(0, 1, 1, 1, 0), window=8, n_heads_per_layer=(12, 16, 16, 16, 12),
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 100, "factor": 4, "original_max_position_embeddings": 64,
                "beta_fast": 8, "beta_slow": 1, "partial_rotary_factor": 0.5,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 10_000, "partial_rotary_factor": 1},
        },
        attn_gate=True, ffn_pattern=(0, 1, 1, 1, 1), n_routed_experts=32, experts_per_token=4, expert_dim=32,
        shared_expert_dim=32, routed_scale=2.5, router_bias=False,
    ),
    # A.X-K1 as published (skt/A.X-K1 config.json): latent attention in all 61
    # layers (a query of rank 1536, ONE cached row of 512 + 64 a token, 64 heads
    # of 128 nope + 64 rope against values of 128), a YaRN x32 whose factor
    # enters the softmax's scale (mscale = mscale_all_dim = 1), a leading dense
    # layer and then 192 routed experts of 2048 in 8 groups of which the best 4
    # stay, 8 a token, renormalised sigmoid weights times 2.5, beside a shared
    # expert of 2048. Whole it is 519 B parameters: a chip serves a depth cut and
    # its share of the experts and of the vocabulary
    # (benchmark/configs/a.x-k1-serve-1chip-ep16.json, whose `assumed` says
    # which readings of the row these are).
    "a.x-k1": LlamaConfig(
        name="a.x-k1", vocab_size=163_840, dim=7168, n_layers=61, n_heads=64, n_kv_heads=64, ffn_dim=18432,
        norm_eps=1e-6, rope_theta=10_000.0, max_seq_len=131_072, q_rank=1536, kv_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128,
        rope_scaling={
            "type": "yarn", "factor": 32, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
        },
        ffn_pattern=(0,) + (1,) * 60, n_routed_experts=192, experts_per_token=8, expert_dim=2048, shared_expert_dim=2048,
        routed_scale=2.5, router_bias=False, n_group=8, topk_group=4,
    ),
    # the same description at a size the CPU tests hold: 1 dense + 4 expert
    # layers, a query rank of 24, a latent of 16 + a rope key of 8, 24 experts in
    # 4 groups of which 2 stay, top-4, 6 held, a YaRN (a ramp over frequencies
    # 0..3) that scales cos / sin (m(4, 1) / m(4, 0.5)) AND the softmax (m(4, 0.5)^2)
    "tiny-axk1": LlamaConfig(
        name="tiny-axk1", vocab_size=512, dim=64, n_layers=5, n_heads=4, n_kv_heads=4, ffn_dim=128, norm_eps=1e-6,
        rope_theta=100.0, max_seq_len=256, q_rank=24, kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        rope_scaling={
            "type": "yarn", "factor": 4, "original_max_position_embeddings": 64, "beta_fast": 8, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 0.5,
        },
        ffn_pattern=(0, 1, 1, 1, 1), n_routed_experts=24, experts_per_token=4, expert_dim=32, n_experts_held=6,
        shared_expert_dim=32, routed_scale=2.5, router_bias=False, n_group=4, topk_group=2,
    ),
    # LFM2-8B-A1B as published (LiquidAI/LFM2-8B-A1B config.json): 18 gated
    # short-convolution layers (3 taps) and 6 of GQA (32 query heads, 8 KV
    # heads of 64, an RMSNorm on each query and key head, rotary base 1e6), two
    # leading dense layers and then 32 routed experts of 1792, 4 a token,
    # sigmoid scores with a selection bias, renormalised. `layer_types` is
    # kept whole so that {"name": "lfm2-8b-a1b", "n_layers": 14} is a cut
    # (benchmark/configs/lfm2-8b-a1b-serve-1chip.json, whose `assumed` says
    # which readings of the row these are).
    "lfm2-8b-a1b": LlamaConfig(
        name="lfm2-8b-a1b", vocab_size=65_536, dim=2048, n_layers=24, n_heads=32, n_kv_heads=8, ffn_dim=7168,
        norm_eps=1e-5, rope_theta=1_000_000.0, max_seq_len=128_000,
        attn_pattern=(
            "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
            "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
            "conv", "conv", "full_attention", "conv", "conv",
        ),
        conv_taps=3, qk_norm=True, ffn_pattern=(0, 0) + (1,) * 22, n_routed_experts=32, experts_per_token=4,
        expert_dim=1792, router_bias=True, routed_scale=1.0,
    ),
    # the same description at a size the CPU tests hold: two dense convolution
    # layers, then attention and three convolutions over 8 experts, top-2 with
    # the selection bias, 3 taps, heads 16 wide; 8 KV heads fill a row of 128,
    # so the pool packs them as the published 8 of 64 are packed into 512
    "tiny-lfm2": LlamaConfig(
        name="tiny-lfm2", vocab_size=512, dim=256, n_layers=6, n_heads=16, n_kv_heads=8, ffn_dim=128,
        norm_eps=1e-5, rope_theta=10_000.0, max_seq_len=256,
        attn_pattern=("conv", "conv", "full_attention", "conv", "conv", "conv"), conv_taps=3, qk_norm=True,
        ffn_pattern=(0, 0, 1, 1, 1, 1), n_routed_experts=8, experts_per_token=2, expert_dim=32,
        router_bias=True, routed_scale=1.0,
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


def _init_kind(cfg: LlamaConfig, kind: LayerKind, k: jax.Array) -> dict:
    """One layer of a model whose layers are not all alike, from its key:
    ten keys (q, k, v, o; gate, up, down or the experts'; router, selection
    bias, sink), every matrix normal(0, 0.02) in cfg.dtype, the sinks and the
    bias too (so that both take part in what a test compares). A routed
    expert's weights are a function of (layer key, expert index) alone, so
    every share of the experts draws the same expert e. What later kinds
    added draws from four more keys, split from fold_in(key, 1) so that the
    ten stay what they were: the attention gate, the shared expert's gate,
    up and down. A latent layer draws its five projections from four keys split
    from fold_in(key, 2) and `wo` from the ten's, and has no wq / wk / wv. A
    convolution layer draws its in-projection [D, 3D], its taps [conv, D] and
    its out-projection [D, D] from three keys split from fold_in(key, 3), and
    has no attention weights; its operator's norm is `attn_norm`. A head's
    query and key norms (`qk_norm`) have gains of ones."""
    init = jax.nn.initializers.normal(stddev=0.02)
    ks = jax.random.split(k, 10)
    # drawn only where a kind has what they are for: a model without draws, and boots, as it did
    more = jax.random.split(jax.random.fold_in(k, 1), 4) if kind.gated or cfg.shared_expert_dim else None
    hd, vd = cfg.head_dim, cfg.v_dim
    layer = {"attn_norm": jnp.ones((cfg.dim,), cfg.dtype), "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype)}
    if kind.conv:
        k_in, k_taps, k_out = jax.random.split(jax.random.fold_in(k, 3), 3)
        layer.update({
            "conv_in": init(k_in, (cfg.dim, 3 * cfg.dim), cfg.dtype),  # [B | C | x]
            "conv_w": init(k_taps, (kind.conv, cfg.dim), cfg.dtype),  # tap j meets the row conv - 1 - j back
            "conv_out": init(k_out, (cfg.dim, cfg.dim), cfg.dtype),
        })
    else:
        layer["wo"] = init(ks[3], (kind.n_heads * vd, cfg.dim), cfg.dtype)
    if kind.qk_norm:
        layer.update({"q_head_norm": jnp.ones((hd,), cfg.dtype), "k_head_norm": jnp.ones((hd,), cfg.dtype)})
    if kind.latent:
        # four keys split from fold_in(key, 2): the query's down- and up-projection, the latent's
        # down-projection [latent | rope key] and its up-projection, a head [nope keys | values]
        # (the published kv_b_proj); the two inner norms' gains are ones
        q_rank, kv_rank, nope, rope, _vd = kind.latent
        down_q, up_q, down_kv, up_kv = jax.random.split(jax.random.fold_in(k, 2), 4)
        layer.update({
            "wq_down": init(down_q, (cfg.dim, q_rank), cfg.dtype),
            "q_norm": jnp.ones((q_rank,), cfg.dtype),
            "wq_up": init(up_q, (q_rank, kind.n_heads * hd), cfg.dtype),
            "wkv_down": init(down_kv, (cfg.dim, kv_rank + rope), cfg.dtype),
            "kv_norm": jnp.ones((kv_rank,), cfg.dtype),
            "wkv_up": init(up_kv, (kv_rank, kind.n_heads * (nope + vd)), cfg.dtype),
        })
    elif not kind.conv:
        layer.update({
            "wq": init(ks[0], (cfg.dim, kind.n_heads * hd), cfg.dtype),
            "wk": init(ks[1], (cfg.dim, kind.n_kv_heads * hd), cfg.dtype),
            "wv": init(ks[2], (cfg.dim, kind.n_kv_heads * vd), cfg.dtype),
        })
    if kind.sink:
        layer["sink"] = init(ks[9], (kind.n_heads,), cfg.dtype)
    if kind.gated:
        layer["wg"] = init(more[0], (cfg.dim, kind.n_heads), cfg.dtype)
    if not kind.experts:
        layer.update({
            "w_gate": init(ks[4], (cfg.dim, cfg.ffn_dim), cfg.dtype),
            "w_up": init(ks[5], (cfg.dim, cfg.ffn_dim), cfg.dtype),
            "w_down": init(ks[6], (cfg.ffn_dim, cfg.dim), cfg.dtype),
        })
        return layer
    lo, held = cfg.experts_held

    def held_experts(k_e: jax.Array, shape: tuple) -> jax.Array:
        keys = jax.random.split(k_e, cfg.n_routed_experts)[lo : lo + held]
        return jax.vmap(lambda ke: init(ke, shape, cfg.dtype))(keys)

    layer.update({
        "router": init(ks[7], (cfg.dim, cfg.n_routed_experts), cfg.dtype),
        "w_gate": held_experts(ks[4], (cfg.dim, cfg.expert_dim)),  # [held, D, F]
        "w_up": held_experts(ks[5], (cfg.dim, cfg.expert_dim)),
        "w_down": held_experts(ks[6], (cfg.expert_dim, cfg.dim)),  # [held, F, D]
    })
    if cfg.router_bias:
        layer["router_bias"] = init(ks[8], (cfg.n_routed_experts,), cfg.dtype)
    if cfg.shared_expert_dim:
        layer.update({
            "shared_gate": init(more[1], (cfg.dim, cfg.shared_expert_dim), cfg.dtype),
            "shared_up": init(more[2], (cfg.dim, cfg.shared_expert_dim), cfg.dtype),
            "shared_down": init(more[3], (cfg.shared_expert_dim, cfg.dim), cfg.dtype),
        })
    return layer


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    hd = cfg.head_dim
    init = jax.nn.initializers.normal(stddev=0.02)
    if not cfg.uniform:
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        return {
            "embed": init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
            # one stacked tree a group of like layers (cfg.layer_groups)
            "layers": tuple(
                jax.vmap(partial(_init_kind, cfg, kind))(layer_keys[first : first + n])
                for kind, first, n in cfg.layer_groups
            ),
            "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
            "lm_head": init(k_out, (cfg.dim, cfg.vocab_size), cfg.dtype),
        }

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


def rope_frequencies(cfg: LlamaConfig, kind: Optional[LayerKind] = None) -> jax.Array:
    """[rope_dim/2] inverse frequencies (rope_dim = head_dim unless the
    config turns only a head's first dims); of a layer `kind` where a kind has
    a rotary width, a base or a scaling rule of its own.

    YaRN (`kind.yarn`), as published: frequency j of rd/2 is a blend of the
    plain one, 1 / theta^(2j/rd), and that over `factor`, by a linear ramp in
    j between the two correction dims: the j at which a frequency makes
    `beta_fast` (below it: plain) and `beta_slow` (above it: divided) whole
    turns over the original positions, rounded down and up."""
    rd, theta = (kind.rope_dim, kind.rope_theta) if kind else (cfg.rope_dim, cfg.rope_theta)
    exponents = jnp.arange(0, rd, 2, dtype=jnp.float32) / rd
    plain = 1.0 / (theta ** exponents)
    if not (kind and kind.yarn):
        return plain
    factor, original, beta_fast, beta_slow, _attention_factor = kind.yarn

    def correction_dim(turns: float) -> float:
        return rd * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rd - 1)
    ramp = jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array, scale: float = 1.0) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S]. `scale` multiplies cos and sin
    (YaRN's attention factor)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, hd/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
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
