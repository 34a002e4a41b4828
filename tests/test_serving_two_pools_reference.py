"""The plain reference of the MiMo-V2-Flash block (benchmark/benchlib/
reference_mimo_v2.py, reached through tests/benchmark/_paths.py: the one
file that also decides the benchmark's `correct`) against the program at a
size the CPU holds (`tiny-mimo`: the same description, three layer kinds, 7
layers, 8 of 32 experts held, top-4, keys 24 / values 16 wide, window 8, 2 / 4
KV heads, sinks, partial rotary, two bases): its weights are the program's bit
for bit, chunked prefill then decode through both pools equals its full
forward pass, the shares of the experts add up to the uncut layer, and what
the engine serves lies by it where float8 operands do not."""

import dataclasses
import json
import os

import numpy as np
import pytest

from tests.benchmark import _paths
from benchlib import reference, reference_mimo_v2


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


TINY = load(_paths.FIXTURES, "tiny_mimo.json")
PAGE, CHUNK = 4, 16  # the window of 8 is two pages; a chunk is two windows

# one layer of each kind alone, the 7-layer stack, and keys wide enough to be
# stored padded (192 -> 256, as at the published widths): (program overrides, reference overrides)
KINDS = {
    "full-dense": (dict(n_layers=1, attn_pattern=(0,), ffn_pattern=(0,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[0], moe_layer_freq=[0])),
    "window-experts": (dict(n_layers=1, attn_pattern=(1,), ffn_pattern=(1,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[1], moe_layer_freq=[1])),
    "full-experts": (dict(n_layers=1, attn_pattern=(0,), ffn_pattern=(1,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[0], moe_layer_freq=[1])),
    "stack": ({}, {}),
    "stack-keys-192": (dict(n_layers=2, qk_head_dim=192, v_head_dim=128), dict(num_hidden_layers=2, head_dim=192, v_head_dim=128)),
}


def program(kind, seed, float32=True):
    """(params, cfg) of the program's own init; in float32 (the same values)
    where a test wants the arithmetic and not bfloat16's rounding."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny-mimo", **KINDS[kind][0])
    params = init_params(cfg, jax.random.PRNGKey(seed & 0x7FFFFFFF))
    if float32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return params, cfg


def layers_of(params, cfg):
    """The program's groups of stacked layers, one dict a layer."""
    import jax

    return [
        jax.tree_util.tree_map(lambda a, j=j: a[j], group)
        for (_kind, _first, n), group in zip(cfg.layer_groups, params["layers"]) for j in range(n)
    ]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_the_reference_makes_the_program_s_weights_from_the_seed_alone(seed):
    mine = reference_mimo_v2.init_weights(TINY, seed)
    params, cfg = program("stack", seed, float32=False)
    theirs = dict(params, layers=layers_of(params, cfg))
    assert sorted(mine) == sorted(theirs) and len(mine["layers"]) == len(theirs["layers"]) == 7
    for a, b in zip([mine] + mine["layers"], [theirs] + theirs["layers"]):
        assert sorted(a) == sorted(b)
        for key in a:
            if key != "layers":
                assert a[key].dtype == b[key].dtype and a[key].shape == b[key].shape and bool((a[key] == b[key]).all()), key
    assert {"sink", "router", "router_bias"} <= set(mine["layers"][1]) and "sink" not in mine["layers"][5]


def paged_logits(params, cfg, tokens, n_prompt, impl):
    """Chunked `paged_prefill` then `paged_decode_step` through both pools, in
    slot 1 of 2, pages handed out as the engine does: the window row gets a
    chunk's pages before it and gives back what fell behind the window after,
    and a freed page is handed out again at once."""
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk
    from modal_tpu.serving.pages import PageAllocator

    pages_per_slot, window_pool = 24, pk.default_window_num_pages(cfg, 1, PAGE, CHUNK) if cfg.has_window else None
    cache = pk.PagedKVCache.create(cfg, 2, 40, PAGE, pages_per_slot, window_pool)
    cache = pk.assign_pages(cache, 1, 0, jnp.arange(10, 10 + pages_per_slot, dtype=jnp.int32))
    pool = PageAllocator(window_pool, PAGE) if cfg.has_window else None
    held, high = {}, 0  # row index -> page of the window pool

    def window_row(first_pos, last_pos):
        nonlocal cache, high
        if pool is None:
            return
        lo = max(0, first_pos - (cfg.window - 1)) // PAGE
        for index in [i for i in held if i < lo]:
            pool.free([held.pop(index)])
        for index in range(lo, last_pos // PAGE + 1):
            if index not in held:
                held[index] = pool.alloc(1)[0]
                cache = pk.assign_window_pages(cache, jnp.asarray([1]), jnp.asarray([index]), jnp.asarray([held[index]]))
        high = max(high, len(held))

    out = []
    for start in range(0, n_prompt, CHUNK):
        chunk = tokens[start : min(start + CHUNK, n_prompt)]
        window_row(start, start + len(chunk) - 1)
        padded = np.zeros((CHUNK,), np.int32)
        padded[: len(chunk)] = chunk
        logits, _tok, cache = pk.paged_prefill(
            params, cfg, jnp.asarray(padded), jnp.int32(len(chunk)), cache, jnp.int32(1), jnp.int32(start)
        )
    out.append(np.asarray(logits))
    decode_high = 0
    for pos in range(n_prompt, len(tokens)):
        window_row(pos, pos)
        decode_high = max(decode_high, len(held))
        fed = jnp.asarray([0, tokens[pos]], jnp.int32)
        logits, _tok, cache = pk.paged_decode_step(params, cfg, fed, cache, jnp.asarray([False, True]), impl)
        out.append(np.asarray(logits[1]))
    if pool is not None:
        assert decode_high <= pk.window_pages_per_slot(cfg.window, PAGE)
    return np.stack(out), cache


@pytest.mark.parametrize("n_prompt", [5, 21, 37], ids=["shorter-than-the-window", "longer-than-the-window", "longer-than-a-chunk-and-the-window"])
@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_prefill_then_decode_through_both_pools_equals_the_reference_s_forward_pass(kind, impl, n_prompt):
    seed, n_decode = 11, 7
    params, cfg = program(kind, seed)
    ref = reference_mimo_v2.Reference({**TINY, **KINDS[kind][1]}, seed)
    tokens = [int(t) for t in np.random.default_rng(n_prompt).integers(0, 512, size=n_prompt + n_decode)]
    got, cache = paged_logits(params, cfg, tokens, n_prompt, impl)
    want = ref.logits(tokens, list(range(n_prompt - 1, n_prompt + n_decode)))
    # float32 both sides, sums in another order: a mask off by one position, a
    # missing sink or a wrong rotary base reads 1e-2 and more on logits of ~0.5
    assert np.abs(want).max() > 0.2
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    if cfg.has_experts:
        layers = sum(k.experts for k in cfg.layer_kinds)
        assert 0 < int(cache.moe_pairs) <= len(tokens) * cfg.experts_per_token * layers


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.experts import routed_experts
    from modal_tpu.models.llama import get_config, init_params

    seed = 5
    uncut = reference_mimo_v2.Reference({**TINY, "n_routed_experts_held": 32}, seed)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), uncut.weights["layers"][2])
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = reference_mimo_v2.experts(uncut.s, x, w, low=False)
        h = reference_mimo_v2._rms(x, w["mlp_norm"], uncut.s["eps"])
        total, pairs = 0.0, 0
        for first in (0, 8, 16, 24):  # four chips share the layer, 8 of 32 experts each
            cfg = get_config("tiny-mimo", experts_held_start=first)
            share = layers_of(init_params(cfg, jax.random.PRNGKey(seed)), cfg)[2]
            share = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), share)
            # every share draws the same expert e, and routes alike
            assert bool((share["w_gate"] == w["w_gate"][first : first + 8]).all()) and bool((share["router"] == w["router"]).all())
            y, used = routed_experts(cfg, h, share, jnp.ones((40,), bool))
            total, pairs = total + y, pairs + int(used)
    assert pairs == 40 * 4  # every routed pair fell on exactly one share
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-6, rtol=1e-5)


def test_a_batch_routed_wholly_onto_one_held_expert_is_computed_in_full():
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.experts import routed_experts

    params, cfg = program("window-experts", 3)
    layer = layers_of(params, cfg)[0]
    # the selection bias sends EVERY token to expert 3 (held) and to 20, 21, 22 (held elsewhere)
    layer["router_bias"] = jnp.zeros((32,), jnp.float32).at[jnp.asarray([3, 20, 21, 22])].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(2), (50, 64), jnp.float32)
    valid = jnp.arange(50) < 45  # five padded positions are computed but not counted
    with jax.default_matmul_precision("highest"):
        y, used = routed_experts(cfg, h, layer, valid)
        scores = jax.nn.sigmoid(h @ layer["router"])
        weight = scores[:, 3] / scores[:, jnp.asarray([3, 20, 21, 22])].sum(axis=-1)  # the bias is not in the weights
        want = weight[:, None] * ((jax.nn.silu(h @ layer["w_gate"][3]) * (h @ layer["w_up"][3])) @ layer["w_down"][3])
    assert int(used) == 45  # one pair a valid token, none dropped
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6, rtol=1e-5)


def served(seed):
    """Requests through the program's own engine, two pools and all: prompts
    shorter than the window, longer than a chunk, and in between."""
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    cfg = get_config("tiny-mimo")
    engine = ServingEngine(
        init_params(cfg, jax.random.PRNGKey(seed)), cfg, max_slots=3, page_size=PAGE, prefill_chunk=CHUNK, num_pages=120
    ).start()
    try:
        rng = np.random.default_rng(seed)
        prompts = [[int(x) for x in rng.integers(0, 512, size=n)] for n in (70, 5, 30, 100, 17)]
        handles = [engine.submit(p, 24) for p in prompts]
        out = [{"prompt": p, "tokens": h.result(timeout=300)} for p, h in zip(prompts, handles)]
        return out, engine.stats()
    finally:
        engine.stop()


@pytest.mark.parametrize("seed", [1, 2])
def test_what_the_engine_serves_lies_by_the_reference_and_the_fp8_control_does_not(seed):
    requests, stats = served(seed)
    out = reference.compare(reference_mimo_v2.Reference(TINY, seed), requests, control="fp8")
    assert out["tokens_compared"] == 120 and out["requests_compared"] == 5
    # logits of this size are ~0.5 wide: bfloat16 through the engine stays within 0.02 of the
    # reference's best, float8 operands do not
    assert out["logit_gap_max"] < 0.02 < out["control_logit_gap_max"]
    assert stats["kv_window_pages_released"] > 0 and stats["kv_window_pages_high_water"] <= stats["kv_window_pages_total"]
    moe = stats["moe"]
    assert 0 < moe["local_assignments"] < moe["assignments"] and moe["expert_calls"] % (6 * 8) == 0
