"""The plain reference of the MiMo-V2-Flash block, for `correct` and for the
CPU tests alike (tests reach it through tests/benchmark/_paths.py).

The equations, from the published config.json's keys (ISSUE 29 section 1;
each assumption is in the configuration's `assumed`). x is the residual
stream, RMSNorm (`layernorm_epsilon`) before each sub-block, residual add
after it:

    attention   q = h Wq -> heads x head_dim; k = h Wk -> n_kv x head_dim;
                v = h Wv -> n_kv x v_head_dim, times attention_value_scale.
                Rotary positions (half-split) on the first
                int(head_dim * partial_rotary_factor) dims of q and k, base
                rope_theta in full layers, swa_rope_theta in window layers.
                Scores q.k / sqrt(head_dim), causal; query head i reads KV
                head i // (heads / n_kv).
    full layer  (hybrid_layer_pattern[l] == 0) num_key_value_heads; softmax
                over every key at or before the query.
    window      (== 1) swa_num_key_value_heads; the query at p sees keys
                p - sliding_window + 1 .. p; one learned logit s_h a query head
                joins the softmax's denominator and takes no value
                (add_swa_attention_sink_bias).
    dense FFN   (moe_layer_freq[l] == 0) SwiGLU of intermediate_size.
    experts     (== 1) z = h Wr (n_routed_experts outputs); s = sigmoid(z); the
                num_experts_per_tok experts are the top of s + b (b: the stored
                selection bias); weights s_e / sum of the chosen s
                (norm_topk_prob; the bias is not in the weights); y = sum of
                w_e * SwiGLU_e(h) over the chosen experts THIS SHARE HOLDS
                (experts experts_held_first .. + n_routed_experts_held): what
                the absent experts would add is left out, as in the program.
    head        final RMSNorm, untied output head over vocab_size rows (the
                slice).

Straightforward `jax.numpy` in float32 under matmul precision "highest", one
forward pass with a plain mask, no cache, no paging, no kernels, no
batching; attention a head at a time and the experts one at a time so that
7,000 tokens fit. It imports nothing of the program: the weights are made
here from the seed by the rule the service documents (below).
"""

from __future__ import annotations

import math
import sys

from benchlib.reference import _fp8, _mm, main, padded


def model_shapes(cfg: dict) -> dict:
    hd = int(cfg["head_dim"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]), "hd": hd, "vd": int(cfg["v_head_dim"]),
        "rope": int(hd * float(cfg["partial_rotary_factor"])), "vscale": float(cfg["attention_value_scale"]),
        "kv": {0: int(cfg["num_key_value_heads"]), 1: int(cfg["swa_num_key_value_heads"])},
        "theta": {0: float(cfg["rope_theta"]), 1: float(cfg["swa_rope_theta"])},
        "sink": {0: bool(cfg["add_full_attention_sink_bias"]), 1: bool(cfg["add_swa_attention_sink_bias"])},
        "window": int(cfg["sliding_window"]), "ffn": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]), "routed": int(cfg["n_routed_experts"]),
        "held": int(cfg["n_routed_experts_held"]), "first": int(cfg.get("experts_held_first", 0)),
        "top": int(cfg["num_experts_per_tok"]), "layers": int(cfg["num_hidden_layers"]),
        # a depth cut runs the first num_hidden_layers of the published patterns
        "attn": [int(v) for v in cfg["hybrid_layer_pattern"]], "moe": [int(v) for v in cfg["moe_layer_freq"]],
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["layernorm_epsilon"]),
        "std": float(cfg.get("initializer_range", 0.02)),
    }


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed. The rule: key -> (embed, layers, head); layers ->
    one key a layer -> ten keys (q, k, v, o; gate, up, down; router, selection
    bias, sinks); every weight normal(0, std) drawn in bfloat16, the sinks and
    the bias too; norm gains are ones. An expert layer splits each of its
    gate / up / down keys into one key a ROUTED expert and draws the experts
    this share holds, so expert e is the same weights in every share.
    `layers` is a list, one dict a layer."""
    import jax
    import jax.numpy as jnp

    s = model_shapes(cfg)
    dt = jnp.bfloat16
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), 3)

    def normal(key, shape):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s["std"], dt)

    def held(key, shape):
        keys = jax.random.split(key, s["routed"])[s["first"] : s["first"] + s["held"]]
        return jnp.stack([normal(k, shape) for k in keys])

    layers = []
    for i, key in enumerate(jax.random.split(k_layers, s["layers"])):
        ks = jax.random.split(key, 10)
        kind, kv = s["attn"][i], s["kv"][s["attn"][i]]
        w = {
            "attn_norm": jnp.ones((s["d"],), dt),
            "wq": normal(ks[0], (s["d"], s["h"] * s["hd"])),
            "wk": normal(ks[1], (s["d"], kv * s["hd"])),
            "wv": normal(ks[2], (s["d"], kv * s["vd"])),
            "wo": normal(ks[3], (s["h"] * s["vd"], s["d"])),
            "mlp_norm": jnp.ones((s["d"],), dt),
        }
        if s["sink"][kind]:
            w["sink"] = normal(ks[9], (s["h"],))
        if s["moe"][i]:
            w["router"] = normal(ks[7], (s["d"], s["routed"]))
            w["router_bias"] = normal(ks[8], (s["routed"],))
            w["w_gate"] = held(ks[4], (s["d"], s["expert"]))
            w["w_up"] = held(ks[5], (s["d"], s["expert"]))
            w["w_down"] = held(ks[6], (s["expert"], s["d"]))
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


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(jnp.float32)


def attention(s: dict, kind: int, x, w: dict, low: bool):
    """One attention sub-block's output (before the residual add), x [S, d]."""
    import jax
    import jax.numpy as jnp

    n, kv, hd, vd, rd = x.shape[0], s["kv"][kind], s["hd"], s["vd"], s["rope"]
    pos = jnp.arange(n)

    def rope(t):  # [S, heads, hd]: the first rd dims turn, the rest pass through
        inv = 1.0 / (s["theta"][kind] ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        t1, t2 = jnp.split(t[..., :rd], 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin, t[..., rd:]], axis=-1)

    h = _rms(x, w["attn_norm"], s["eps"])
    q = rope(_mm(h, w["wq"], low).reshape(n, s["h"], hd))
    k = rope(_mm(h, w["wk"], low).reshape(n, kv, hd))
    v = _mm(h, w["wv"], low).reshape(n, kv, vd) * s["vscale"]
    rep = s["h"] // kv  # query head i reads KV head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    seen = pos[None, :] <= pos[:, None]
    if kind == 1:
        seen = seen & (pos[None, :] > pos[:, None] - s["window"])
    sinks = w["sink"].astype(jnp.float32) if "sink" in w else jnp.full((s["h"],), -jnp.inf)

    def one_head(args):  # a head at a time: [S, S] scores are what memory holds
        qh, kh, vh, sink = args
        if low:
            qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
        a = jnp.where(seen, (qh @ kh.T) / math.sqrt(hd), -jnp.inf)
        m = jnp.maximum(jnp.max(a, axis=-1, keepdims=True), sink)
        e = jnp.exp(a - m)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))
        if low:
            probs = _fp8(probs, -1)
        return probs @ vh

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2), sinks))
    return _mm(out.transpose(1, 0, 2).reshape(n, s["h"] * vd), w["wo"], low)


def route(s: dict, h, w: dict, low: bool):
    """[S, n_routed_experts]: each token's weight for each routed expert, 0
    where it was not chosen."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(h, w["router"], low))
    _, chosen = jax.lax.top_k(scores + w["router_bias"].astype(jnp.float32), s["top"])
    picked = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0) * scores
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def experts(s: dict, x, w: dict, low: bool):
    """The held experts' part of an expert layer's output, one expert at a time."""
    import jax

    h = _rms(x, w["mlp_norm"], s["eps"])
    weights = route(s, h, w, low)[:, s["first"] : s["first"] + s["held"]]  # [S, held]

    def add_expert(y, args):
        w_e, gate, up, down = args
        return y + w_e[:, None] * _mm(jax.nn.silu(_mm(h, gate, low)) * _mm(h, up, low), down, low), None

    y, _ = jax.lax.scan(add_expert, x * 0.0, (weights.T, w["w_gate"], w["w_up"], w["w_down"]))
    return y


def dense_ffn(s: dict, x, w: dict, low: bool):
    import jax

    h = _rms(x, w["mlp_norm"], s["eps"])
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], low)) * _mm(h, w["w_up"], low), w["w_down"], low)


def layer(s: dict, i: int, x, w: dict, low: bool):
    x = x + attention(s, s["attn"][i], x, w, low)
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
