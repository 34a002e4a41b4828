"""The plain reference of the LFM2-8B-A1B block, for `correct` and for the CPU
tests alike (tests reach it through tests/benchmark/_paths.py).

The equations, from the published config.json's keys. x is the
residual stream; every layer is pre-norm with two residuals: h = x +
Op(RMSNorm(x)), then out = h + FFN(RMSNorm(h)), RMSNorm of `norm_eps`. Op is
the layer's entry of `layer_types`:

    conv        a gated short convolution of L = conv_L_cache taps, no bias
                (conv_bias false): [B | C | x~] = h W_in (W_in hidden_size x
                3 hidden_size, in that order); u = B * x~; v_t = sum over
                j = 0 .. L-1 of w_j * u_{t - L + 1 + j}, depthwise and causal,
                u zero before position 0 (zero left padding over the whole
                sequence); y = (C * v) W_out (hidden_size x hidden_size).
    full_attention
                GQA, num_attention_heads query heads and num_key_value_heads
                KV heads, each hidden_size / num_attention_heads wide; q and k
                get an RMSNorm over each head (a gain a head width, the same
                for every head) before rotary; rotary positions (half-split)
                on the whole head, base rope_theta; scores q.k / sqrt(head
                width), causal; query head j reads KV head j // (heads / KV
                heads); no sink, no gate, no window; then W_o.
    dense FFN   layers 0 .. num_dense_layers - 1: SwiGLU of intermediate_size.
    experts     every later layer: s = sigmoid(h2 W_r) over all num_experts in
                float32; the top num_experts_per_tok of s + b (a stored
                selection bias, use_expert_bias), which selects and never
                weighs; weights w = routed_scaling_factor x s_chosen / sum of
                s_chosen (norm_topk_prob); y = sum of w_e x SwiGLU_e(h2) over
                the chosen experts, each moe_intermediate_size wide; no shared
                expert.
    head        final RMSNorm, untied output head over vocab_size rows.

Readings the catalog row does not settle (the configuration's `assumed`
carries them): the head is untied; the renormalisation divides by the sum
alone, where the family's modeling code may add 1e-6 to it (a relative 1e-6
on a routed weight, under every limit); the taps are held as [L, hidden_size]
(the published Conv1d weight [hidden_size, 1, L] transposed); the rotary
convention is the half-split one this repo has.

Straightforward `jax.numpy` in float32 under matmul precision "highest", one
forward pass with a plain mask, no cache, no paging, no kernels, no batching;
attention a KV head at a time and the experts one at a time so that 6,000
tokens fit. It imports nothing of the program: the weights are made here from
the seed by the rule the service documents (below).
"""

from __future__ import annotations

import math
import sys

from benchlib.reference import _fp8, _mm, main, padded


def model_shapes(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    routed = int(cfg["num_experts"])
    return {
        "d": d, "h": heads, "kv": int(cfg["num_key_value_heads"]), "hd": d // heads,
        # a depth cut runs the first num_hidden_layers of the published list
        "conv": [v == "conv" for v in cfg["layer_types"]][:layers],
        "moe": [i >= int(cfg["num_dense_layers"]) for i in range(layers)],
        "taps": int(cfg["conv_L_cache"]), "ffn": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]), "routed": routed,
        "top": int(cfg["num_experts_per_tok"]), "scale": float(cfg["routed_scaling_factor"]),
        "bias": bool(cfg["use_expert_bias"]), "theta": float(cfg["rope_theta"]),
        "layers": layers, "vocab": int(cfg["vocab_size"]), "eps": float(cfg["norm_eps"]),
        "std": float(cfg.get("initializer_range", 0.02)),
    }


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed. The rule: key -> (embed, layers, head); layers ->
    one key a layer -> ten keys (q, k, v, o; gate, up, down; router, selection
    bias, and one this model does not draw from: sinks), and three more split
    from fold_in(layer key, 3) for a convolution layer: W_in, the taps, W_out
    (a convolution layer draws nothing from the first four). Every weight
    normal(0, std) drawn in bfloat16; norm gains, the per-head query and key
    norms' among them, are ones. An expert layer splits each of its gate / up /
    down keys into one key a routed expert. `layers` is a list, one dict a
    layer."""
    import jax
    import jax.numpy as jnp

    s = model_shapes(cfg)
    dt, d = jnp.bfloat16, s["d"]
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), 3)

    def normal(key, shape):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s["std"], dt)

    def experts(key, shape):
        return jax.vmap(lambda k: normal(k, shape))(jax.random.split(key, s["routed"]))

    layers = []
    for i, key in enumerate(jax.random.split(k_layers, s["layers"])):
        ks = jax.random.split(key, 10)
        w = {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt)}
        if s["conv"][i]:
            k_in, k_taps, k_o = jax.random.split(jax.random.fold_in(key, 3), 3)
            w.update(conv_in=normal(k_in, (d, 3 * d)), conv_w=normal(k_taps, (s["taps"], d)), conv_out=normal(k_o, (d, d)))
        else:
            w.update(
                wq=normal(ks[0], (d, s["h"] * s["hd"])), wk=normal(ks[1], (d, s["kv"] * s["hd"])),
                wv=normal(ks[2], (d, s["kv"] * s["hd"])), wo=normal(ks[3], (s["h"] * s["hd"], d)),
                q_head_norm=jnp.ones((s["hd"],), dt), k_head_norm=jnp.ones((s["hd"],), dt),
            )
        if s["moe"][i]:
            w.update(
                router=normal(ks[7], (d, s["routed"])), w_gate=experts(ks[4], (d, s["expert"])),
                w_up=experts(ks[5], (d, s["expert"])), w_down=experts(ks[6], (s["expert"], d)),
            )
            if s["bias"]:
                w["router_bias"] = normal(ks[8], (s["routed"],))
        else:
            w.update(w_gate=normal(ks[4], (d, s["ffn"])), w_up=normal(ks[5], (d, s["ffn"])), w_down=normal(ks[6], (s["ffn"], d)))
        layers.append(w)
    return {
        "embed": normal(k_embed, (s["vocab"], d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": normal(k_out, (d, s["vocab"])),
    }


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(jnp.float32)


def short_conv(s: dict, x, w: dict, low: bool):
    """A convolution layer's operator (before the residual add), x [S, d]: the
    whole sequence at once, zeros to the left of position 0."""
    import jax.numpy as jnp

    n, taps = x.shape[0], s["taps"]
    b, c, xt = jnp.split(_mm(_rms(x, w["attn_norm"], s["eps"]), w["conv_in"], low), 3, axis=-1)
    u = jnp.pad(b * xt, ((taps - 1, 0), (0, 0)))
    taps_w = w["conv_w"].astype(jnp.float32)
    v = sum(taps_w[j] * u[j : j + n] for j in range(taps))
    return _mm(c * v, w["conv_out"], low)


def attention(s: dict, x, w: dict, low: bool):
    """An attention layer's operator (before the residual add), x [S, d]."""
    import jax
    import jax.numpy as jnp

    n, heads, kv, hd = x.shape[0], s["h"], s["kv"], s["hd"]
    pos = jnp.arange(n)
    inv = 1.0 / (s["theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))

    def rope(t):  # [S, heads, hd]: the whole head turns
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

    h = _rms(x, w["attn_norm"], s["eps"])
    q = rope(_rms(_mm(h, w["wq"], low).reshape(n, heads, hd), w["q_head_norm"], s["eps"]))
    k = rope(_rms(_mm(h, w["wk"], low).reshape(n, kv, hd), w["k_head_norm"], s["eps"]))
    v = _mm(h, w["wv"], low).reshape(n, kv, hd)
    rep = heads // kv  # query head j reads KV head j // rep
    qg = q.reshape(n, kv, rep, hd).transpose(1, 2, 0, 3)  # [kv, rep, S, hd]
    causal = pos[None, :] <= pos[:, None]

    def one_group(args):  # one KV head and its query heads at a time: [rep, S, S] scores
        qh, kh, vh = args
        if low:
            qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
        scores = jnp.einsum("rqd,kd->rqk", qh, kh) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        if low:
            probs = _fp8(probs, -1)
        return jnp.einsum("rqk,kd->rqd", probs, vh)

    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [kv, rep, S, hd]
    return _mm(out.transpose(2, 0, 1, 3).reshape(n, heads * hd), w["wo"], low)


def route(s: dict, h, w: dict, low: bool):
    """[S, num_experts]: each token's weight for each routed expert, 0 where
    it was not chosen."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(h, w["router"], low))
    select = scores + w["router_bias"].astype(jnp.float32) if s["bias"] else scores
    _, chosen = jax.lax.top_k(select, s["top"])
    picked = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0) * scores
    return s["scale"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def swiglu(h, gate, up, down, low: bool):
    import jax

    return _mm(jax.nn.silu(_mm(h, gate, low)) * _mm(h, up, low), down, low)


def experts(s: dict, x, w: dict, low: bool):
    """An expert layer's FFN (before the residual add), one expert at a time."""
    import jax

    h = _rms(x, w["mlp_norm"], s["eps"])
    weights = route(s, h, w, low)  # [S, experts]

    def add_expert(y, args):
        w_e, gate, up, down = args
        return y + w_e[:, None] * swiglu(h, gate, up, down, low), None

    y, _ = jax.lax.scan(add_expert, h * 0.0, (weights.T, w["w_gate"], w["w_up"], w["w_down"]))
    return y


def dense_ffn(s: dict, x, w: dict, low: bool):
    return swiglu(_rms(x, w["mlp_norm"], s["eps"]), w["w_gate"], w["w_up"], w["w_down"], low)


def layer(s: dict, i: int, x, w: dict, low: bool):
    x = x + (short_conv if s["conv"][i] else attention)(s, x, w, low)
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
