"""The plain reference of the Laguna-XS.2 block, for `correct` and for the CPU
tests alike (tests reach it through tests/benchmark/_paths.py).

The equations, from the published config.json's keys (ISSUE 34). x is the
residual stream, RMSNorm (`rms_norm_eps`) before each sub-block, residual add
after it. For layer i, H_i = num_attention_heads_per_layer[i] (48 where
layer_types[i] is full_attention, 64 where sliding_attention), 8 KV heads,
head width head_dim:

    attention   h = rmsnorm(x); q = h Wq -> H_i x head_dim; k = h Wk, v = h Wv
                -> num_key_value_heads x head_dim. Rotary positions
                (half-split) on the first head_dim x partial_rotary_factor
                dims of each q and k head, by the layer type's entry of
                rope_parameters. Scores q.k / sqrt(head_dim), causal; query
                head j reads KV head j // (H_i / num_key_value_heads); no sink.
    full        rope_type yarn: 64 of 128 dims turn, base rope_theta 500000;
                frequency j of 32 is a blend of 1 / theta^(2j/64) and that
                over `factor` (64) by a linear ramp in j between the two
                correction dims, where a frequency makes beta_fast (64) and
                beta_slow (1) whole turns over original_max_position_embeddings
                (4096), rounded down and up; cos and sin are multiplied by
                attention_factor (1.4158883083359672). Softmax over every key
                at or before the query.
    sliding     rope_type default: all 128 dims turn, base 10000, no scaling;
                the query at p sees keys p - sliding_window + 1 .. p.
    gate        g = sigmoid(h Wg), one scalar a query head (Wg is
                hidden_size x H_i), times that head's attention output a:
                attn = (a * g) Wo; x <- x + attn.
    dense FFN   (mlp_layer_types[i] == "dense") SwiGLU of intermediate_size.
    experts     ("sparse") s = sigmoid(h2 Wr) over all num_experts in float32;
                the top num_experts_per_tok of s; weights w =
                moe_routed_scaling_factor x s_chosen / sum of s_chosen; y =
                sum of w_e x SwiGLU_e(h2) over the chosen experts + the shared
                expert's SwiGLU(h2), every expert moe_intermediate_size wide and
                the shared one shared_expert_intermediate_size; the weights
                multiply the experts' OUTPUTS (moe_apply_router_weight_on_input
                false). A share of the experts (`n_routed_experts_held` from
                `experts_held_first`, both optional: all of them where the file
                names neither, as the benchmark's configuration does) leaves
                out what the absent experts would add and keeps the shared one.
    head        final RMSNorm, untied output head over vocab_size rows.

Three readings the catalog row does not settle (the configuration's `assumed`
carries the same words); nothing else in a layer is assumed:

(1) `gating: true` is read as the family's per-head output gate on attention
(arXiv:2505.06708's head-wise form): one sigmoid scalar a query head from the
layer's normed input, by a projection hidden_size x H_i, multiplying that
head's attention output before `Wo`; the shared expert is NOT gated. The
catalog's sibling row Laguna-S-2.1 spells the same key `gating: "per-head"`
with `gating_types` all `per_head`, and the published size agrees: with a gate
a head the 40 layers count 33.44 B parameters ("33.4B-A3B"), with an
element-wise gate as wide as `Wq` 34.07 B. (ISSUE 34 read it element-wise; the
review of PR 34 pointed to the sibling row.)
(2) The row has no `scoring_func` and no `norm_topk_prob`; the sibling row
Laguna-S-2.1 has `norm_topk_prob: true` and the same
`moe_routed_scaling_factor` 2.5 (DeepSeek-V3's value), read with that family's
rule: sigmoid scores, chosen weights renormalised, then scaled; no selection
bias (no key names one).
(3) No key names a QK-norm, so there is none.

Straightforward `jax.numpy` in float32 under matmul precision "highest", one
forward pass with a plain mask, no cache, no paging, no kernels, no batching;
attention a head at a time and the experts one at a time so that 3,000 tokens
fit. It imports nothing of the program: the weights are made here from the
seed by the rule the service documents (below).
"""

from __future__ import annotations

import math
import sys

from benchlib.reference import _fp8, _mm, main, padded

KINDS = {"full_attention": 0, "sliding_attention": 1}


def model_shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    hd = int(cfg["head_dim"])
    routed = int(cfg["num_experts"])
    rope = {}
    for name, kind in KINDS.items():
        rule = cfg["rope_parameters"][name]
        rope[kind] = dict(rule, dims=int(hd * float(rule["partial_rotary_factor"])))
    return {
        "d": int(cfg["hidden_size"]), "hd": hd, "kv": int(cfg["num_key_value_heads"]),
        # a depth cut runs the first num_hidden_layers of the published lists
        "heads": [int(v) for v in cfg["num_attention_heads_per_layer"]][:layers],
        "attn": [KINDS[v] for v in cfg["layer_types"]][:layers],
        "moe": [v == "sparse" for v in cfg["mlp_layer_types"]][:layers],
        "rope": rope, "window": int(cfg["sliding_window"]), "gated": bool(cfg["gating"]),
        "ffn": int(cfg["intermediate_size"]), "expert": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["shared_expert_intermediate_size"]), "routed": routed,
        "held": int(cfg.get("n_routed_experts_held", routed)), "first": int(cfg.get("experts_held_first", 0)),
        "top": int(cfg["num_experts_per_tok"]), "scale": float(cfg["moe_routed_scaling_factor"]),
        "layers": layers, "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "std": float(cfg.get("initializer_range", 0.02)),
    }


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed. The rule: key -> (embed, layers, head); layers ->
    one key a layer -> ten keys (q, k, v, o; gate, up, down; router; two this
    model does not draw from: a selection bias, sinks), and four more split
    from fold_in(layer key, 1): the attention gate, the shared expert's gate,
    up and down. Every weight normal(0, std) drawn in bfloat16; norm gains are
    ones. An expert layer splits each of its gate / up / down keys into one key
    a ROUTED expert and draws the experts this share holds, so expert e is the
    same weights in every share. `layers` is a list, one dict a layer."""
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
        heads = s["heads"][i]
        w = {
            "attn_norm": jnp.ones((s["d"],), dt),
            "wq": normal(ks[0], (s["d"], heads * s["hd"])),
            "wk": normal(ks[1], (s["d"], s["kv"] * s["hd"])),
            "wv": normal(ks[2], (s["d"], s["kv"] * s["hd"])),
            "wo": normal(ks[3], (heads * s["hd"], s["d"])),
            "mlp_norm": jnp.ones((s["d"],), dt),
        }
        if s["gated"]:
            w["wg"] = normal(more[0], (s["d"], heads))
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


def rotary_rule(rule: dict) -> tuple:
    """(inverse frequencies [dims / 2], the factor on cos and sin) of one
    layer type's entry of rope_parameters, YaRN written out."""
    import numpy as np

    dims, theta = int(rule["dims"]), float(rule["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, dims, 2, dtype=np.float64) / dims)
    if rule["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    assert rule["rope_type"] == "yarn", rule["rope_type"]
    factor, original = float(rule["factor"]), float(rule["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return dims * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rule["beta_slow"]))), dims - 1)
    ramp = np.clip((np.arange(dims // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    blend = plain / factor * ramp + plain * (1.0 - ramp)
    return blend.astype(np.float32), float(rule.get("attention_factor") or 0.1 * math.log(factor) + 1.0)


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(jnp.float32)


def attention(s: dict, i: int, x, w: dict, low: bool):
    """Layer i's attention sub-block (before the residual add), x [S, d]."""
    import jax
    import jax.numpy as jnp

    kind, heads, n, kv, hd = s["attn"][i], s["heads"][i], x.shape[0], s["kv"], s["hd"]
    inv, factor = rotary_rule(s["rope"][kind])
    rd = 2 * len(inv)
    pos = jnp.arange(n)

    def rope(t):  # [S, heads, hd]: the first rd dims turn, the rest pass through
        ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
        cos, sin = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
        t1, t2 = jnp.split(t[..., :rd], 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin, t[..., rd:]], axis=-1)

    h = _rms(x, w["attn_norm"], s["eps"])
    q = rope(_mm(h, w["wq"], low).reshape(n, heads, hd))
    k = rope(_mm(h, w["wk"], low).reshape(n, kv, hd))
    v = _mm(h, w["wv"], low).reshape(n, kv, hd)
    rep = heads // kv  # query head j reads KV head j // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    seen = pos[None, :] <= pos[:, None]
    if kind == 1:
        seen = seen & (pos[None, :] > pos[:, None] - s["window"])

    def one_head(args):  # a head at a time: [S, S] scores are what memory holds
        qh, kh, vh = args
        if low:
            qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
        probs = jax.nn.softmax(jnp.where(seen, (qh @ kh.T) / math.sqrt(hd), -jnp.inf), axis=-1)
        if low:
            probs = _fp8(probs, -1)
        return probs @ vh

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2)  # [S, heads, hd]
    if s["gated"]:
        out = out * jax.nn.sigmoid(_mm(h, w["wg"], low))[:, :, None]  # a scalar a head
    return _mm(out.reshape(n, heads * hd), w["wo"], low)


def route(s: dict, h, w: dict, low: bool):
    """[S, num_experts]: each token's weight for each routed expert, 0 where
    it was not chosen."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(h, w["router"], low))
    _, chosen = jax.lax.top_k(scores, s["top"])
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
    x = x + attention(s, i, x, w, low)
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
