"""Routed experts for the served path: the share of an expert layer that
lives on this chip.

The layer is told which experts it holds (`cfg.experts_held`: first, count).
It routes over ALL `n_routed_experts` as the model's equations say — sigmoid
scores in float32, the top `experts_per_token` of the scores (plus a stored
selection bias where the model has one; within the best `topk_group` of
`n_group` groups where the router has a group limit), weights = the chosen scores
renormalised (the bias selects, it does not weigh) times `cfg.routed_scale` —
keeps the (token, expert) pairs that fell on held experts, and returns their
weighted sum, plus the shared expert's output where the model has one (every
token passes it; it is not gated). What the absent experts would have added
is left out: on one chip the layer runs without its exchange, and that
partial sum is what goes on to the next layer (the plain reference is given
the same share).

No token is ever dropped: there is no capacity. Every held expert multiplies
the whole batch, with weight 0 where the token was not routed to it: held x
rows row-products where the model needs rows x k. With a chip's share of 16
held that is what the MXU costs anyway; with all 256 of a layer held and up to
256 rows a call it is still bound by the experts' weights, which every call
reads whole: a grouped product over the pairs sorted by expert measured no
faster end to end in the benchmark's cell (PERF.md section 6, PR 34). Past
~256 rows a call the arithmetic, 32 times the model's, would bind.

`parallel/moe.py` is another layer (the mesh trainer's switch-style top-1
with a capacity that drops tokens and softmax gates) and is not touched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _group_limit(select: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """select [T, E] float32 with the experts outside each token's best
    `topk_group` groups at -inf. The E experts lie in `n_group` groups of
    consecutive ones; a group scores the sum of its two largest entries."""
    grouped = select.reshape(select.shape[0], n_group, -1)
    group_scores = jnp.sum(lax.top_k(grouped, min(2, grouped.shape[-1]))[0], axis=-1)  # [T, n_group]
    _, best = lax.top_k(group_scores, topk_group)
    stays = jnp.any(best[..., None] == jnp.arange(n_group, dtype=best.dtype), axis=1)  # [T, n_group]
    return jnp.where(stays[..., None], grouped, -jnp.inf).reshape(select.shape)


def route(cfg, h: jax.Array, layer: dict) -> tuple[jax.Array, jax.Array]:
    """h [T, D] -> (chosen [T, k] expert ids, weights [T, k] float32)."""
    z = jnp.einsum("td,de->te", h, layer["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(z)
    bias = layer.get("router_bias")
    select = scores if bias is None else scores + bias.astype(jnp.float32)
    if cfg.n_group > 1:
        with jax.named_scope("moe_group_limit"):
            select = _group_limit(select, cfg.n_group, cfg.topk_group)
    _, chosen = lax.top_k(select, cfg.experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, weights if cfg.routed_scale == 1.0 else weights * cfg.routed_scale


def _whole_batch_experts(h, layer, local, weights, here, held: int):
    """Every held expert over every row of h, weight 0 where the token was
    not routed to it. Returns (y [T, D] float32, onto [T, k, held])."""
    onto = here[..., None] & (local[..., None] == jnp.arange(held, dtype=local.dtype))
    w_held = jnp.sum(jnp.where(onto, weights[..., None], 0.0), axis=1)  # [T, held]
    gate = jnp.einsum("td,edf->etf", h, layer["w_gate"])
    up = jnp.einsum("td,edf->etf", h, layer["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) * w_held.T[:, :, None]
    y = jnp.einsum("etf,efd->td", act.astype(h.dtype), layer["w_down"], preferred_element_type=jnp.float32)
    return y, onto


def routed_experts(cfg, h: jax.Array, layer: dict, valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The layer's output for h [T, D] (the held experts' part and the shared
    expert's), and two counts over the `valid` tokens, uint32 [2]: the (token,
    expert) pairs that fell on held experts, and the held experts that got at
    least one of them (a padded position or an idle slot is computed and not
    counted)."""
    lo, held = cfg.experts_held
    with jax.named_scope("moe_router"):
        chosen, weights = route(cfg, h, layer)
        local = chosen - lo
        here = (local >= 0) & (local < held)
        counted = here & valid[:, None]
    with jax.named_scope("moe_experts"):
        y, onto = _whole_batch_experts(h, layer, local, weights, here, held)
    touched = jnp.sum(jnp.any(onto & valid[:, None, None], axis=(0, 1)))
    if "shared_gate" in layer:
        with jax.named_scope("moe_shared_expert"):
            gated = jax.nn.silu(jnp.dot(h, layer["shared_gate"]).astype(jnp.float32)).astype(h.dtype) * jnp.dot(h, layer["shared_up"])
            y = y + jnp.dot(gated, layer["shared_down"], preferred_element_type=jnp.float32)
    return y.astype(h.dtype), jnp.stack([jnp.sum(counted), touched]).astype(jnp.uint32)
