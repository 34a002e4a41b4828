"""The plain reference of the A.X-K1 block, for `correct` and for the CPU tests
alike (tests reach it through tests/benchmark/_paths.py).

The equations, from the published config.json's keys (ISSUE 37), in the
NON-absorbed form: keys and values a head are rebuilt from the latent, so the
program, whose decode step never builds one, is compared with the published
form and not with itself. x is the residual stream [hidden_size], RMSNorm
(`rms_norm_eps`) before each sub-block, residual add after it, H =
num_attention_heads, no bias anywhere (`attention_bias` false):

    query       h = rmsnorm(x); c_q = rmsnorm(h W_DQ) [q_lora_rank]; q = c_q W_UQ
                -> H x (qk_nope_head_dim + qk_rope_head_dim), each head
                [q_nope | q_rope]; rotary on q_rope.
    latent      [c | k_r] = h W_DKV [kv_lora_rank | qk_rope_head_dim]; c <-
                rmsnorm(c); rotary on k_r: ONE rope key a token, shared by the
                H heads. (What a cache holds is [c | k_r], after the norm and
                the rotary; here nothing is cached.)
    a head j    k_nope_j = c W_UK_j [qk_nope_head_dim], v_j = c W_UV_j
                [v_head_dim]: W_UK_j and W_UV_j are head j's two parts of the
                published kv_b_proj [kv_lora_rank, H x (qk_nope_head_dim +
                v_head_dim)].
    scores      s_j(p, t) = (q_nope_j(p) . k_nope_j(t) + q_rope_j(p) . k_r(t))
                x scale, causal softmax over t <= p, o_j = sum_t p_t v_j(t);
                x <- x + concat_j(o_j) W_O.
    rotary      base rope_theta on the qk_rope_head_dim dims, YaRN as published
                in rope_scaling: frequency j is a blend of 1 / theta^(2j/d) and
                that over `factor` by a linear ramp in j between the two
                correction dims, where a frequency makes beta_fast and
                beta_slow whole turns over original_max_position_embeddings,
                rounded down and up. With m(s, a) = 0.1 a ln s + 1: cos and sin
                are multiplied by m(factor, mscale) / m(factor,
                mscale_all_dim), and scale = (qk_nope_head_dim +
                qk_rope_head_dim)^-0.5 x m(factor, mscale_all_dim)^2.
    dense FFN   (layers before first_k_dense_replace) SwiGLU of intermediate_size.
    experts     s = sigmoid(h2 W_r) over all n_routed_experts in float32; the
                experts lie in n_group groups of consecutive ones; a group's
                score is the sum of its two largest s; the topk_group best
                groups stay, the rest are masked out; the top
                num_experts_per_tok of what stays; weights w =
                routed_scaling_factor x s_chosen / sum of s_chosen; y = sum of
                w_e x SwiGLU_e(h2) + the shared expert's SwiGLU(h2), every
                expert moe_intermediate_size wide and the shared one
                n_shared_experts x that. A share of the experts
                (`n_routed_experts_held` from `experts_held_first`) leaves out
                what the absent experts would add and keeps the shared one.
    head        final RMSNorm, untied output head over vocab_size rows.

Four readings the catalog row does not settle (the configuration's `assumed`
carries the same words); nothing else in a layer is assumed:

(1) `topk_method: "none"` beside `n_group` 8 / `topk_group` 4 / `scoring_func:
"sigmoid"` is read as the DeepSeek-V3 group rule (a group's score = the sum of
its two best scores; the best `topk_group` groups stay; the top
`num_experts_per_tok` of what stays) WITHOUT a stored selection bias:
`noaux_tc` is the name that brings one, and `seq_aux: true` says the balance
was trained by a loss instead.
(2) Rotary positions in the half-split convention this repo has; the family's
interleaved one is a fixed permutation of the rope columns of `W_UQ` and
`W_DKV`, which seeded random weights make immaterial.
(3) `mscale` 1 and `mscale_all_dim` 1 are read by the family's rule
(DeepSeek-V2/V3 modeling code): m(s, a) = 0.1 a ln s + 1; cos and sin are
multiplied by m(32, mscale) / m(32, mscale_all_dim) = 1; the softmax's scale is
192^-0.5 x m(32, mscale_all_dim)^2 = 0.130861.
(4) `torch_dtype` bfloat16 and `initializer_range` 0.02 are not in the row and
are this repo's; the gains of the two inner norms (on c_q and on c) are ones.

Straightforward `jax.numpy` in float32 under matmul precision "highest", one
forward pass with a plain mask, no cache, no paging, no kernels, no batching;
attention a head at a time and the experts one at a time so that 7,168 tokens
fit beside 9.7 GB of weights, which stay in bfloat16 as drawn and are raised
to float32 a matrix at a time. It imports nothing of the program: the weights
are made here from the seed by the rule the service documents (below).
"""

from __future__ import annotations

import math
import sys

from benchlib.reference import _fp8, _mm, main, padded


def model_shapes(cfg: dict) -> dict:
    layers, routed = int(cfg["num_hidden_layers"]), int(cfg["n_routed_experts"])
    dense_first, every = int(cfg["first_k_dense_replace"]), int(cfg["moe_layer_freq"])
    expert = int(cfg["moe_intermediate_size"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]), "q_rank": int(cfg["q_lora_rank"]),
        "kv_rank": int(cfg["kv_lora_rank"]), "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]), "theta": float(cfg["rope_theta"]), "scaling": dict(cfg["rope_scaling"]),
        # a depth cut runs the first num_hidden_layers of the published pattern
        "moe": [i >= dense_first and i % every == 0 for i in range(layers)],
        "ffn": int(cfg["intermediate_size"]), "expert": expert, "shared": int(cfg["n_shared_experts"]) * expert,
        "routed": routed, "held": int(cfg.get("n_routed_experts_held", routed)), "first": int(cfg.get("experts_held_first", 0)),
        "top": int(cfg["num_experts_per_tok"]), "groups": int(cfg["n_group"]), "groups_kept": int(cfg["topk_group"]),
        "scale": float(cfg["routed_scaling_factor"]), "layers": layers, "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]), "std": float(cfg.get("initializer_range", 0.02)),
    }


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed. The rule: key -> (embed, layers, head); layers ->
    one key a layer -> ten keys (q, k, v: not drawn from here; o; gate, up,
    down; router; two this model does not draw from), four more split from
    fold_in(layer key, 1) (the first unused; the shared expert's gate, up and
    down) and four split from fold_in(layer key, 2): W_DQ, W_UQ, W_DKV and the
    kv_b_proj. Every weight normal(0, std) drawn in bfloat16; norm gains are
    ones, the two inner ones too. An expert layer splits each of its gate / up
    / down keys into one key a ROUTED expert and draws the experts this share
    holds, so expert e is the same weights in every share. `layers` is a list,
    one dict a layer."""
    import jax
    import jax.numpy as jnp

    s = model_shapes(cfg)
    dt = jnp.bfloat16
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), 3)

    def normal(key, shape):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s["std"], dt)

    def held(key, shape):
        keys = jax.random.split(key, s["routed"])[s["first"] : s["first"] + s["held"]]
        return jax.vmap(lambda k: normal(k, shape))(keys)

    layers = []
    for i, key in enumerate(jax.random.split(k_layers, s["layers"])):
        ks = jax.random.split(key, 10)
        more = jax.random.split(jax.random.fold_in(key, 1), 4)
        down_q, up_q, down_kv, up_kv = jax.random.split(jax.random.fold_in(key, 2), 4)
        w = {
            "attn_norm": jnp.ones((s["d"],), dt),
            "wq_down": normal(down_q, (s["d"], s["q_rank"])),
            "q_norm": jnp.ones((s["q_rank"],), dt),
            "wq_up": normal(up_q, (s["q_rank"], s["h"] * (s["nope"] + s["rope"]))),
            "wkv_down": normal(down_kv, (s["d"], s["kv_rank"] + s["rope"])),
            "kv_norm": jnp.ones((s["kv_rank"],), dt),
            "wkv_up": normal(up_kv, (s["kv_rank"], s["h"] * (s["nope"] + s["vd"]))),
            "wo": normal(ks[3], (s["h"] * s["vd"], s["d"])),
            "mlp_norm": jnp.ones((s["d"],), dt),
        }
        if s["moe"][i]:
            w["router"] = normal(ks[7], (s["d"], s["routed"]))
            w["w_gate"] = held(ks[4], (s["d"], s["expert"]))
            w["w_up"] = held(ks[5], (s["d"], s["expert"]))
            w["w_down"] = held(ks[6], (s["expert"], s["d"]))
            w["shared_gate"] = normal(more[1], (s["d"], s["shared"]))
            w["shared_up"] = normal(more[2], (s["d"], s["shared"]))
            w["shared_down"] = normal(more[3], (s["shared"], s["d"]))
        else:
            w["w_gate"] = normal(ks[4], (s["d"], s["ffn"]))
            w["w_up"] = normal(ks[5], (s["d"], s["ffn"]))
            w["w_down"] = normal(ks[6], (s["ffn"], s["d"]))
        layers.append(w)
    return {
        "embed": normal(k_embed, (s["vocab"], s["d"])),
        "layers": layers,
        "final_norm": jnp.ones((s["d"],), dt),
        "lm_head": normal(k_out, (s["d"], s["vocab"])),
    }


def mscale(factor: float, a: float) -> float:
    """m(s, a) = 0.1 a ln s + 1 (1 where s <= 1)."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_rule(s: dict) -> tuple:
    """(inverse frequencies [rope / 2], the factor on cos and sin, the
    softmax's scale) of rope_theta and rope_scaling, YaRN written out."""
    import numpy as np

    dims, theta, rule = s["rope"], s["theta"], s["scaling"]
    assert rule["type"] == "yarn", rule
    factor, original = float(rule["factor"]), float(rule["original_max_position_embeddings"])
    plain = 1.0 / theta ** (np.arange(0, dims, 2, dtype=np.float64) / dims)

    def correction_dim(turns: float) -> float:
        return dims * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rule["beta_slow"]))), dims - 1)
    ramp = np.clip((np.arange(dims // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    blend = plain / factor * ramp + plain * (1.0 - ramp)
    all_dim = float(rule.get("mscale_all_dim", 0))
    on_cos_sin = mscale(factor, float(rule.get("mscale", 1))) / mscale(factor, all_dim)
    return blend.astype(np.float32), on_cos_sin, (s["nope"] + s["rope"]) ** -0.5 * mscale(factor, all_dim) ** 2


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(jnp.float32)


def attention(s: dict, x, w: dict, low: bool):
    """A layer's attention sub-block (before the residual add), x [S, d]:
    keys and values a head rebuilt from the latent."""
    import jax
    import jax.numpy as jnp

    n, heads, nope, rope, vd, rank = x.shape[0], s["h"], s["nope"], s["rope"], s["vd"], s["kv_rank"]
    inv, on_cos_sin, scale = rotary_rule(s)
    pos = jnp.arange(n)

    def turn(t):  # [S, heads, rope]: half-split
        ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
        cos, sin = jnp.cos(ang)[:, None, :] * on_cos_sin, jnp.sin(ang)[:, None, :] * on_cos_sin
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

    h = _rms(x, w["attn_norm"], s["eps"])
    c_q = _rms(_mm(h, w["wq_down"], low), w["q_norm"], s["eps"])
    q = _mm(c_q, w["wq_up"], low).reshape(n, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
    down = _mm(h, w["wkv_down"], low)
    c = _rms(down[:, :rank], w["kv_norm"], s["eps"])  # [S, kv_rank]
    k_r = turn(down[:, None, rank:])[:, 0]  # [S, rope]: one rope key a token
    w_up = w["wkv_up"].reshape(rank, heads, nope + vd).transpose(1, 0, 2)  # [heads, kv_rank, nope + vd], bfloat16 as drawn
    seen = pos[None, :] <= pos[:, None]

    def one_head(args):  # a head at a time: [S, S] scores are what memory holds
        qh, up = args
        kh = jnp.concatenate([_mm(c, up[:, :nope], low), k_r], axis=-1)  # k_j = [c W_UK_j | k_r]
        vh = _mm(c, up[:, nope:], low)  # v_j = c W_UV_j
        if low:
            qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
        probs = jax.nn.softmax(jnp.where(seen, (qh @ kh.T) * scale, -jnp.inf), axis=-1)
        if low:
            probs = _fp8(probs, -1)
        return probs @ vh

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), w_up)).transpose(1, 0, 2)  # [S, heads, vd]
    return _mm(out.reshape(n, heads * vd), w["wo"], low)


def route(s: dict, h, w: dict, low: bool):
    """[S, n_routed_experts]: each token's weight for each routed expert, 0
    where it was not chosen; the group rule written out."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(h, w["router"], low))
    grouped = scores.reshape(h.shape[0], s["groups"], -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # a group: its two best scores
    _, kept = jax.lax.top_k(group_score, s["groups_kept"])
    stays = jnp.zeros_like(group_score).at[jnp.arange(h.shape[0])[:, None], kept].set(1.0) > 0
    allowed = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(scores.shape)
    _, chosen = jax.lax.top_k(allowed, s["top"])
    picked = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0) * scores
    return s["scale"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def swiglu(h, gate, up, down, low: bool):
    import jax

    return _mm(jax.nn.silu(_mm(h, gate, low)) * _mm(h, up, low), down, low)


def routed(s: dict, h, w: dict, low: bool):
    """The held routed experts' weighted outputs for h [S, d], one expert at a time."""
    import jax

    weights = route(s, h, w, low)[:, s["first"] : s["first"] + s["held"]]  # [S, held]

    def add_expert(y, args):
        w_e, gate, up, down = args
        return y + w_e[:, None] * swiglu(h, gate, up, down, low), None

    y, _ = jax.lax.scan(add_expert, h * 0.0, (weights.T, w["w_gate"], w["w_up"], w["w_down"]))
    return y


def experts(s: dict, x, w: dict, low: bool):
    """An expert layer's output (before the residual add): the held routed
    experts and the shared one."""
    h = _rms(x, w["mlp_norm"], s["eps"])
    return routed(s, h, w, low) + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], low)


def dense_ffn(s: dict, x, w: dict, low: bool):
    return swiglu(_rms(x, w["mlp_norm"], s["eps"]), w["w_gate"], w["w_up"], w["w_down"], low)


def layer(s: dict, i: int, x, w: dict, low: bool):
    x = x + attention(s, x, w, low)
    return x + (experts if s["moe"][i] else dense_ffn)(s, x, w, low)


class Reference:
    """Holds the weights; `logits(tokens, positions)` is one forward pass."""

    def __init__(self, cfg: dict, seed: int, pad_to: int = 0):
        self.cfg = cfg
        self.pad_to = int(pad_to)
        self.s = model_shapes(cfg)
        self.weights = init_weights(cfg, seed)
        self._forward: dict = {}

    def _program(self, low: bool):
        import jax
        import jax.numpy as jnp

        if low not in self._forward:
            s = self.s

            def forward(weights, ids, positions):
                x = weights["embed"][ids].astype(jnp.float32)
                for i, w in enumerate(weights["layers"]):
                    x = layer(s, i, x, w, low)
                x = _rms(x[positions], weights["final_norm"], s["eps"])
                return _mm(x, weights["lm_head"], low)

            with jax.default_matmul_precision("highest"):
                self._forward[low] = jax.jit(forward)
        return self._forward[low]

    def logits(self, tokens: list, positions: list, low: bool = False):
        """float32 logits [len(positions), vocab] of the sequence `tokens`."""
        import jax
        import numpy as np

        ids, pos = padded(tokens, positions, self.pad_to)
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._program(low)(self.weights, ids, pos))[: len(positions)]


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], Reference))
