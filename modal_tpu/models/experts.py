"""Routed experts for the served path: the share of an expert layer that
lives on this chip.

The layer is told which experts it holds (`cfg.experts_held`: first, count).
It routes over ALL `n_routed_experts` as the model's equations say — sigmoid
scores in float32, the top `experts_per_token` of score + selection bias,
weights = the chosen scores renormalised (the bias selects, it does not
weigh) — keeps the (token, expert) pairs that fell on held experts, and
returns their weighted sum. What the absent experts would have added is left
out: on one chip the layer runs without its exchange, and that partial sum is
what goes on to the next layer (the plain reference is given the same share).

No token is ever dropped: there is no capacity. Every held expert's buffer is
the whole batch (a token chooses an expert at most once, so T rows is the
worst case and cannot overflow), with weight 0 where the token was not routed
to it. At a decode step of ~100 tokens and a chunk of 256 that is what the
MXU costs anyway: an expert's matrices are loaded tile by tile whatever the
rows, and 16 x 25 M weights a layer bound the step by their bytes
(PERF.md section 5 has the chip's numbers). The other experts are not computed.

`parallel/moe.py` is another layer (the mesh trainer's switch-style top-1
with a capacity that drops tokens and softmax gates) and is not touched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(cfg, h: jax.Array, layer: dict) -> tuple[jax.Array, jax.Array]:
    """h [T, D] -> (chosen [T, k] expert ids, weights [T, k] float32)."""
    z = jnp.einsum("td,de->te", h, layer["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(z)
    _, chosen = lax.top_k(scores + layer["router_bias"].astype(jnp.float32), cfg.experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def held_weights(cfg, chosen: jax.Array, weights: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(w [T, held]: each held expert's weight for each token, 0 where the
    token was not routed to it; here [T, k]: the pairs that fell on held
    experts)."""
    lo, held = cfg.experts_held
    local = chosen - lo
    here = (local >= 0) & (local < held)
    onto = here[..., None] & (local[..., None] == jnp.arange(held, dtype=chosen.dtype))  # [T, k, held]
    return jnp.sum(jnp.where(onto, weights[..., None], 0.0), axis=1), here


def routed_experts(cfg, h: jax.Array, layer: dict, valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's output for h [T, D], and how
    many (token, expert) pairs of the `valid` tokens it computed (uint32; a
    padded position or an idle slot computes garbage and is not counted)."""
    with jax.named_scope("moe_router"):
        chosen, weights = route(cfg, h, layer)
        w_held, here = held_weights(cfg, chosen, weights)
        used = jnp.sum(here & valid[:, None]).astype(jnp.uint32)
    with jax.named_scope("moe_experts"):
        gate = jnp.einsum("td,edf->etf", h, layer["w_gate"])
        up = jnp.einsum("td,edf->etf", h, layer["w_up"])
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) * w_held.T[:, :, None]
        y = jnp.einsum("etf,efd->td", act.astype(h.dtype), layer["w_down"], preferred_element_type=jnp.float32)
    return y.astype(h.dtype), used
