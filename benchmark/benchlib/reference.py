"""The plain reference, and the comparison that decides `correct`.

A decoder-only transformer as its published description gives it (RMSNorm,
rotary positions in the half-split convention, grouped-query causal
attention, SwiGLU, untied output head) in straightforward `jax.numpy`, in
float32 with matmul precision "highest", with no cache, no paging, no
kernels and no batching. It imports nothing of the program and takes nothing
the program made: the weights are made here from the seed by the same rule
(normal(0, initializer_range) in bfloat16 from `jax.random` keys split in
the documented order), op by op, so that they round as the program's do.

Run as a child process once the window has closed and the container has
exited (the chip is free then, and its memory peak has been read):

    python reference.py job.json out.json

The job names the configuration, the seed and the sampled requests (prompt
and served tokens, as the client received them). For each, ONE forward pass
over prompt + served tokens gives the reference's logits at every served
position; the number compared is the widest gap by which a served token's
logit lies below the reference's best at its position. With
`"control": "fp8"` the same pass is made a second time with every matmul
operand rounded to float8 (e4m3, scaled per channel / per token) and the
gap of the token THAT pass puts first is read against the reference: the
control that has to come out as not correct.

What is architecture here is `model_shapes`, `init_weights`, `_layer` and
`Reference`. The rest is for every architecture: a configuration whose key
`reference` names a file of its own (a sibling of this one; the harness
puts the benchmark's directory on the child's path) brings `init_weights`
and the forward pass and takes the comparison from here:

    from benchlib.reference import _fp8, _mm, main, padded
    class Reference: ...   # __init__(cfg, seed, pad_to), logits(tokens, positions, low=False)
    if __name__ == "__main__":
        sys.exit(main(sys.argv[1:], Reference))

The child's contract, whatever the file: `python <file> job.json out.json`.
The job holds `config`, `seed`, `control` ("" or "fp8"), `require_platform`,
`pad_to` and `requests` ([{index, prompt, tokens}]); the output holds
`logit_gap_max`, `logit_gap_mean`, `tokens_compared`, `requests_compared`,
`per_request`, `platform`, `init_s`, `compare_s`, under `control: "fp8"`
also `control_logit_gap_max` and `control_logit_gap_mean`; exit 3 where
jax is not on `require_platform`.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

PAD_TO = 512


def model_shapes(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "h": h, "kv": int(cfg["num_key_value_heads"]), "hd": d // h,
        "ffn": int(cfg["intermediate_size"]), "layers": int(cfg["num_hidden_layers"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]), "std": float(cfg.get("initializer_range", 0.02)),
    }


def init_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed. The rule (it is the service's documented one):
    key -> (embed, layers, head); layers -> one key a layer -> seven keys
    (q, k, v, o, gate, up, down); each weight normal(0, std) drawn in
    bfloat16; norm gains are ones. Stacked over layers, leading axis."""
    import jax
    import jax.numpy as jnp

    s = model_shapes(cfg)
    dt = jnp.bfloat16
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), 3)

    def normal(key, shape):
        return jax.random.normal(key, shape, dt) * jnp.asarray(s["std"], dt)

    def one_layer(key):
        ks = jax.random.split(key, 7)
        return {
            "attn_norm": jnp.ones((s["d"],), dt),
            "wq": normal(ks[0], (s["d"], s["h"] * s["hd"])),
            "wk": normal(ks[1], (s["d"], s["kv"] * s["hd"])),
            "wv": normal(ks[2], (s["d"], s["kv"] * s["hd"])),
            "wo": normal(ks[3], (s["h"] * s["hd"], s["d"])),
            "mlp_norm": jnp.ones((s["d"],), dt),
            "w_gate": normal(ks[4], (s["d"], s["ffn"])),
            "w_up": normal(ks[5], (s["d"], s["ffn"])),
            "w_down": normal(ks[6], (s["ffn"], s["d"])),
        }

    layers = jax.vmap(one_layer)(jax.random.split(k_layers, s["layers"]))
    return {
        "embed": normal(k_embed, (s["vocab"], s["d"])),
        "layers": layers,
        "final_norm": jnp.ones((s["d"],), dt),
        "lm_head": normal(k_out, (s["d"], s["vocab"])),
    }


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale along `axis` (absmax -> 448)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, low: bool):
    """x @ w in float32; under the control both operands are float8 first:
    activations scaled per row (token), weights per output channel."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _layer(s: dict, low: bool):
    import jax
    import jax.numpy as jnp

    def rms(x, gamma):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + s["eps"]) * gamma.astype(jnp.float32)

    def rope(x, positions):  # x [S, heads, hd]
        inv = 1.0 / (s["theta"] ** (jnp.arange(0, s["hd"], 2, dtype=jnp.float32) / s["hd"]))
        ang = positions[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def layer(x, w):  # x [S, d] float32
        n = x.shape[0]
        pos = jnp.arange(n)
        h = rms(x, w["attn_norm"])
        q = rope(_mm(h, w["wq"], low).reshape(n, s["h"], s["hd"]), pos)
        k = rope(_mm(h, w["wk"], low).reshape(n, s["kv"], s["hd"]), pos)
        v = _mm(h, w["wv"], low).reshape(n, s["kv"], s["hd"])
        rep = s["h"] // s["kv"]
        qg = q.reshape(n, s["kv"], rep, s["hd"]).transpose(1, 2, 0, 3)  # [kv, rep, S, hd]
        causal = pos[None, :] <= pos[:, None]

        def one_group(args):  # one kv head and its `rep` query heads at a time: memory
            qh, kh, vh = args  # [rep, S, hd], [S, hd], [S, hd]
            if low:
                qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
            scores = jnp.einsum("rqd,kd->rqk", qh, kh) / math.sqrt(s["hd"])
            probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
            if low:
                probs = _fp8(probs, -1)
            return jnp.einsum("rqk,kd->rqd", probs, vh)

        out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [kv, rep, S, hd]
        attn = out.transpose(2, 0, 1, 3).reshape(n, s["h"] * s["hd"])
        x = x + _mm(attn, w["wo"], low)
        h = rms(x, w["mlp_norm"])
        x = x + _mm(jax.nn.silu(_mm(h, w["w_gate"], low)) * _mm(h, w["w_up"], low), w["w_down"], low)
        return x

    return layer


class Reference:
    """Holds the weights; `logits(tokens, positions)` is one forward pass."""

    def __init__(self, cfg: dict, seed: int, pad_to: int = 0):
        self.cfg = cfg
        self.pad_to = int(pad_to)
        self.s = model_shapes(cfg)
        self.weights = init_weights(cfg, seed)
        self._forward: dict = {}

    def _program(self, low: bool):
        """embed -> the layers one after another (a scan over the stacked
        weights, each layer's raised to float32 as it is used, so the whole
        model never exists in float32) -> final norm -> output head at the
        positions asked for."""
        import jax
        import jax.numpy as jnp

        if low not in self._forward:
            layer, s = _layer(self.s, low), self.s

            def forward(weights, ids, positions):
                x = weights["embed"][ids].astype(jnp.float32)
                x, _ = jax.lax.scan(lambda carry, w: (layer(carry, w), None), x, weights["layers"])
                x = x[positions]
                x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + s["eps"])
                return _mm(x * weights["final_norm"].astype(jnp.float32), weights["lm_head"], low)

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


def padded(tokens: list, positions: list, pad_to: int = 0) -> tuple:
    """The padding rule: sequences and position lists are padded with zeros
    (to `pad_to`, to 512s) so that a cell's reference is one compiled
    shape; a padded position comes after the real ones and, the model being
    causal, changes none of them."""
    import numpy as np

    n, m = len(tokens), len(positions)
    ids = np.zeros((max(int(pad_to), -(-n // PAD_TO) * PAD_TO),), np.int32)
    ids[:n] = tokens
    pos = np.zeros((-(-m // PAD_TO) * PAD_TO,), np.int32)
    pos[:m] = positions
    return ids, pos


def served_gap(logits, served: list) -> list:
    """For each served token, how far its logit lies below the best."""
    import numpy as np

    best = logits.max(axis=-1)
    mine = logits[np.arange(len(served)), np.asarray(served)]
    return [float(g) for g in (best - mine)]


def compare(ref, requests: list, control: str = "") -> dict:
    """The numbers of `correct` for the sampled requests. `ref` is any
    object with `logits(tokens, positions, low=False)`: float32 logits
    [len(positions), vocab] of the sequence `tokens`, in float8 operands
    where `low`. Nothing else of it is touched."""
    import numpy as np

    gaps, control_gaps, per_request = [], [], []
    for req in requests:
        prompt, served = list(req["prompt"]), list(req["tokens"])
        if not served:
            continue
        seq = prompt + served[:-1]  # the last served token is never fed back
        positions = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
        logits = ref.logits(seq, positions)
        g = served_gap(logits, served)
        gaps.extend(g)
        entry = {"index": req.get("index"), "prompt_len": len(prompt), "served": len(served), "gap_max": max(g)}
        if control == "fp8":
            low = ref.logits(seq, positions, low=True)
            cg = served_gap(logits, [int(t) for t in np.argmax(low, axis=-1)])
            control_gaps.extend(cg)
            entry["control_gap_max"] = max(cg)
        per_request.append(entry)
    out = {
        "logit_gap_max": max(gaps) if gaps else None,
        "logit_gap_mean": float(np.mean(gaps)) if gaps else None,
        "tokens_compared": len(gaps),
        "requests_compared": len(per_request),
        "per_request": per_request,
    }
    if control_gaps:
        out["control_logit_gap_max"] = max(control_gaps)
        out["control_logit_gap_mean"] = float(np.mean(control_gaps))
    return out


def main(argv: list, make_reference=None) -> int:
    """`make_reference(config, seed, pad_to)` builds the object `compare`
    takes: another architecture's file passes its own class."""
    job_path, out_path = argv[0], argv[1]
    with open(job_path) as f:
        job = json.load(f)
    t0 = time.monotonic()
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    device = jax.devices()[0]
    if job.get("require_platform") and device.platform != job["require_platform"]:
        sys.stderr.write(f"reference: jax is on {device.platform!r}, wanted {job['require_platform']!r}\n")
        return 3
    ref = (make_reference or Reference)(job["config"], job["seed"], job.get("pad_to", 0))
    t1 = time.monotonic()
    out = compare(ref, job["requests"], job.get("control", ""))
    out.update(platform=device.platform, init_s=t1 - t0, compare_s=time.monotonic() - t1)
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
