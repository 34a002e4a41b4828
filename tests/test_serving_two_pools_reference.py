"""The plain references of the models whose layers are not all alike
(benchmark/benchlib/reference_mimo_v2.py and reference_laguna.py, reached
through tests/benchmark/_paths.py: the files that also decide the benchmark's
`correct`) against the program at a size the CPU holds. `tiny-mimo`: three
layer kinds, 7 layers, 8 of 32 experts held, top-4, keys 24 / values 16 wide,
window 8, 2 / 4 KV heads, sinks, partial rotary, two bases. `tiny-laguna`: 5
layers, 6 and 8 query heads a KV head by layer kind, a gate on the attention
output, YaRN on half a head beside plain rotary on the whole, all 32 experts
held, top-4 scaled by 2.5, a shared expert. `tiny-axk1`
(benchlib/reference_axk1.py, the NON-absorbed equations): 1 dense + 4 expert
layers of latent attention (one cached row of 16 + 8 a token, an absorbed
decode kernel of its own, a prefill chunk that rebuilds a block's keys and
values), a YaRN that scales cos / sin and the softmax, 6 of 24 experts held
under a router with a group limit (4 groups of which 2 stay). For
each: the reference's weights are the program's bit for bit, chunked prefill
then decode through both pools equals its full forward pass, the shares of the
experts add up to the uncut layer, and what the engine serves lies by it where
float8 operands do not."""

import dataclasses
import json
import os

import numpy as np
import pytest

from tests.benchmark import _paths
from benchlib import reference, reference_axk1, reference_laguna, reference_mimo_v2


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


TINY = load(_paths.FIXTURES, "tiny_mimo.json")
TINY_LAGUNA = load(_paths.FIXTURES, "lagunaroot", "benchmark", "configs", "tiny-laguna.json")
TINY_AXK1 = load(_paths.FIXTURES, "axk1root", "benchmark", "configs", "tiny-axk1.json")
PAGE, CHUNK = 4, 16  # the window of 8 is two pages; a chunk is two windows


def one_laguna_layer(attn, ffn, heads):
    return (
        dict(n_layers=1, attn_pattern=(attn,), ffn_pattern=(ffn,), n_heads_per_layer=(heads,)),
        dict(num_hidden_layers=1, layer_types=[["full_attention", "sliding_attention"][attn]],
             mlp_layer_types=[["dense", "sparse"][ffn]], num_attention_heads_per_layer=[heads]),
    )


# one layer of each kind alone, the whole stack, and (MiMo) keys wide enough to be stored padded
# (192 -> 256, as at the published widths): (preset, program overrides, reference overrides)
KINDS = {
    "full-dense": ("tiny-mimo", dict(n_layers=1, attn_pattern=(0,), ffn_pattern=(0,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[0], moe_layer_freq=[0])),
    "window-experts": ("tiny-mimo", dict(n_layers=1, attn_pattern=(1,), ffn_pattern=(1,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[1], moe_layer_freq=[1])),
    "full-experts": ("tiny-mimo", dict(n_layers=1, attn_pattern=(0,), ffn_pattern=(1,)), dict(num_hidden_layers=1, hybrid_layer_pattern=[0], moe_layer_freq=[1])),
    "stack": ("tiny-mimo", {}, {}),
    "stack-keys-192": ("tiny-mimo", dict(n_layers=2, qk_head_dim=192, v_head_dim=128), dict(num_hidden_layers=2, head_dim=192, v_head_dim=128)),
    "laguna-full-gqa6-yarn-dense": ("tiny-laguna", *one_laguna_layer(0, 0, 12)),
    "laguna-sliding-gqa8-experts": ("tiny-laguna", *one_laguna_layer(1, 1, 16)),
    "laguna-full-gqa6-yarn-experts": ("tiny-laguna", *one_laguna_layer(0, 1, 12)),
    "laguna-stack": ("tiny-laguna", {}, {}),
    # latent attention: over a dense FFN, over the grouped router's experts, the stack, and a row wide
    # enough to be stored padded (160 + 8 -> 256, as 512 + 64 -> 640 at the published widths)
    "axk1-latent-dense": ("tiny-axk1", dict(n_layers=1, ffn_pattern=(0,)), dict(num_hidden_layers=1)),
    "axk1-latent-grouped-experts": ("tiny-axk1", dict(n_layers=1, ffn_pattern=(1,)), dict(num_hidden_layers=1, first_k_dense_replace=0)),
    "axk1-stack": ("tiny-axk1", {}, {}),
    "axk1-stack-row-168": ("tiny-axk1", dict(n_layers=2, kv_rank=160), dict(num_hidden_layers=2, kv_lora_rank=160)),
}
MODELS = {
    "tiny-mimo": (reference_mimo_v2, TINY), "tiny-laguna": (reference_laguna, TINY_LAGUNA), "tiny-axk1": (reference_axk1, TINY_AXK1),
}


def reference_of(kind, seed):
    module, fixture = MODELS[KINDS[kind][0]]
    return module.Reference({**fixture, **KINDS[kind][2]}, seed)


def program(kind, seed, float32=True):
    """(params, cfg) of the program's own init; in float32 (the same values)
    where a test wants the arithmetic and not bfloat16's rounding."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config(KINDS[kind][0], **KINDS[kind][1])
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
@pytest.mark.parametrize("stack", ["stack", "laguna-stack", "axk1-stack"])
def test_the_reference_makes_the_program_s_weights_from_the_seed_alone(stack, seed):
    module, fixture = MODELS[KINDS[stack][0]]
    mine = module.init_weights(fixture, seed)
    params, cfg = program(stack, seed, float32=False)
    theirs = dict(params, layers=layers_of(params, cfg))
    assert sorted(mine) == sorted(theirs) and len(mine["layers"]) == len(theirs["layers"]) == cfg.n_layers
    for a, b in zip([mine] + mine["layers"], [theirs] + theirs["layers"]):
        assert sorted(a) == sorted(b)
        for key in a:
            if key != "layers":
                assert a[key].dtype == b[key].dtype and a[key].shape == b[key].shape and bool((a[key] == b[key]).all()), key
    if stack == "stack":
        assert {"sink", "router", "router_bias"} <= set(mine["layers"][1]) and "sink" not in mine["layers"][5]
    elif stack == "axk1-stack":  # five projections and two inner norms in place of wq / wk / wv; no selection bias
        latent = {"wq_down", "q_norm", "wq_up", "wkv_down", "kv_norm", "wkv_up", "wo"}
        assert latent <= set(mine["layers"][0]) and not {"wq", "wk", "wv", "router_bias"} & set(mine["layers"][1])
        assert {"router", "shared_gate", "shared_up", "shared_down"} <= set(mine["layers"][1]) and "router" not in mine["layers"][0]
        assert mine["layers"][1]["wkv_down"].shape == (64, 16 + 8) and mine["layers"][1]["wkv_up"].shape == (16, 4 * (16 + 16))
        assert mine["layers"][1]["w_gate"].shape == (6, 64, 32)
    else:  # the gate, the router and the shared expert are drawn, so all three take part in what is compared
        assert {"wg", "router", "shared_gate", "shared_up", "shared_down"} <= set(mine["layers"][1])
        assert "router_bias" not in mine["layers"][1] and "wg" in mine["layers"][0] and "router" not in mine["layers"][0]
        assert mine["layers"][0]["wq"].shape == (64, 12 * 16) and mine["layers"][1]["wq"].shape == (64, 16 * 16)


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
        given = []
        for index in range(lo, last_pos // PAGE + 1):
            if index not in held:
                held[index] = pool.alloc(1)[0]
                given.append((1, index, held[index]))
        if given:  # the growing pool's row is whole from the start: no entry of its own
            cache = pk.assign_entries(cache, pk.pack_entries(2, CHUNK // PAGE + 1, [], given))
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
    ref = reference_of(kind, seed)
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
            total, pairs = total + y, pairs + int(used[0])
    assert pairs == 40 * 4  # every routed pair fell on exactly one share
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-6, rtol=1e-5)


def test_four_shares_under_the_group_limit_and_the_shared_expert_counted_once_add_up_to_the_uncut_layer():
    """A.X-K1's expert layer: four chips of 6 of the 24 experts each (a share
    is one whole group of the router's four; every share routes over all 24
    with the group limit on and computes the shared expert alike) against the
    reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import experts
    from modal_tpu.models.llama import get_config, init_params

    seed = 5
    uncut = reference_axk1.Reference({**TINY_AXK1, "n_routed_experts_held": 24}, seed)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), uncut.weights["layers"][2])
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    ones = jnp.ones((40,), bool)
    with jax.default_matmul_precision("highest"):
        whole = reference_axk1.experts(uncut.s, x, w, low=False)
        h = reference_axk1._rms(x, w["mlp_norm"], uncut.s["eps"])
        shared = reference_axk1.swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], low=False)
        total, pairs, by_share = 0.0, 0, []
        for first in (0, 6, 12, 18):
            cfg = get_config("tiny-axk1", experts_held_start=first)
            assert (cfg.n_group, cfg.topk_group, cfg.experts_held) == (4, 2, (first, 6))
            share = layers_of(init_params(cfg, jax.random.PRNGKey(seed)), cfg)[2]
            share = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), share)
            assert bool((share["w_gate"] == w["w_gate"][first : first + 6]).all()) and bool((share["shared_up"] == w["shared_up"]).all())
            y, counts = experts.routed_experts(cfg, h, share, ones)
            total, pairs = total + y, pairs + int(counts[0])
            by_share.append(int(counts[0]))
    assert pairs == 40 * 4  # every routed pair fell on exactly one share
    # the limit is on: a token's four experts lie in two groups, so no share can have them spread evenly
    chosen, _w = experts.route(get_config("tiny-axk1"), h, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), share))
    assert all(len(set(row // 6)) <= 2 for row in np.asarray(chosen))
    assert float(jnp.abs(whole).max()) > 1e-3 and float(jnp.abs(shared).max()) > 1e-4
    # the shared expert was computed by every share: count it once
    np.testing.assert_allclose(np.asarray(total - 3 * shared), np.asarray(whole), atol=1e-6, rtol=1e-5)


def route_before_the_group_limit(cfg, h, layer):
    """`experts.route` as it was before the router had groups, written out."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    z = jnp.einsum("td,de->te", h, layer["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(z)
    bias = layer.get("router_bias")
    _, chosen = lax.top_k(scores if bias is None else scores + bias.astype(jnp.float32), cfg.experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, weights if cfg.routed_scale == 1.0 else weights * cfg.routed_scale


@pytest.mark.parametrize("kind", ["window-experts", "laguna-sliding-gqa8-experts"])
def test_a_router_with_one_group_routes_bit_for_bit_as_it_did(kind):
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import experts

    params, cfg = program(kind, 3, float32=False)  # bfloat16, as served; MiMo's has a selection bias, Laguna's a scale
    layer = layers_of(params, cfg)[0]
    h = jax.random.normal(jax.random.PRNGKey(4), (64, 64), jnp.bfloat16)
    assert cfg.n_group == 1
    got, want = jax.jit(lambda: experts.route(cfg, h, layer))(), jax.jit(lambda: route_before_the_group_limit(cfg, h, layer))()
    assert bool((got[0] == want[0]).all()) and bool((got[1] == want[1]).all())


def test_the_group_limit_is_the_rule_written_out_and_a_masked_group_s_best_expert_is_not_chosen():
    """numpy, a token at a time: a group's score is the sum of its two largest
    scores, the best `topk_group` groups stay, the top k of what stays, the
    weights renormalised and scaled. Among the tokens is one whose single best
    expert lies in a group that does not stay."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import experts

    params, cfg = program("axk1-latent-grouped-experts", 8)
    layer = layers_of(params, cfg)[0]
    h = jax.random.normal(jax.random.PRNGKey(9), (200, 64), jnp.float32) * 3
    with jax.default_matmul_precision("highest"):
        chosen, weights = experts.route(cfg, h, layer)
        scores = np.asarray(jax.nn.sigmoid(h @ layer["router"]), np.float64)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    lost_its_best = 0
    for t, s in enumerate(scores):
        groups = s.reshape(4, 6)
        group_score = np.sort(groups, axis=-1)[:, -2:].sum(axis=-1)
        stays = np.argsort(-group_score, kind="stable")[:2]
        allowed = np.full(24, -np.inf)
        for g in stays:
            allowed[g * 6 : (g + 1) * 6] = s[g * 6 : (g + 1) * 6]
        want = np.argsort(-allowed, kind="stable")[:4]
        assert sorted(chosen[t]) == sorted(want), t
        np.testing.assert_allclose(np.sort(weights[t]), np.sort(2.5 * s[want] / s[want].sum()), rtol=1e-5)
        if np.argmax(s) // 6 not in stays:
            lost_its_best += 1
            assert np.argmax(s) not in chosen[t]
    assert lost_its_best >= 3  # the limit bit: without it every token gets its best expert


def test_eight_shares_and_the_shared_expert_counted_once_add_up_to_the_uncut_and_the_all_held_layer():
    """Laguna's expert layer: eight chips of 4 experts each (every share
    computes the shared expert alike) against the reference's uncut layer and
    against the program's all-held layer."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import experts
    from modal_tpu.models.llama import get_config, init_params

    seed = 5
    uncut = reference_laguna.Reference(TINY_LAGUNA, seed)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), uncut.weights["layers"][2])
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    ones = jnp.ones((40,), bool)

    def share_of(**held):
        cfg = get_config("tiny-laguna", **held)
        layer = layers_of(init_params(cfg, jax.random.PRNGKey(seed)), cfg)[2]
        return cfg, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)

    with jax.default_matmul_precision("highest"):
        whole = reference_laguna.experts(uncut.s, x, w, low=False)
        h = reference_laguna._rms(x, w["mlp_norm"], uncut.s["eps"])
        shared = reference_laguna.swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], low=False)
        total, pairs, touched = 0.0, 0, 0
        for first in range(0, 32, 4):
            cfg, share = share_of(experts_held_start=first, n_experts_held=4)
            assert bool((share["w_gate"] == w["w_gate"][first : first + 4]).all()) and bool((share["shared_up"] == w["shared_up"]).all())
            y, counts = experts.routed_experts(cfg, h, share, ones)
            total, pairs, touched = total + y, pairs + int(counts[0]), touched + int(counts[1])
        cfg, held_all = share_of()
        y_all, counts_all = experts.routed_experts(cfg, h, held_all, ones)
    assert pairs == 40 * 4 == int(counts_all[0]) and touched == int(counts_all[1]) <= 32
    assert float(jnp.abs(whole).max()) > 1e-3 and float(jnp.abs(shared).max()) > 1e-4
    # the shared expert was computed by every share: count it once
    np.testing.assert_allclose(np.asarray(total - 7 * shared), np.asarray(whole), atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y_all), np.asarray(whole), atol=1e-6, rtol=1e-5)


def laguna_expert_layer(seed):
    import jax
    import jax.numpy as jnp

    params, cfg = program("laguna-sliding-gqa8-experts", seed)
    return cfg, layers_of(params, cfg)[0], jax.random.normal(jax.random.PRNGKey(seed + 1), (50, 64), jnp.float32)


@pytest.mark.parametrize("case", ["even", "one-expert", "two-experts-only", "nothing-valid"])
def test_the_all_held_layer_equals_its_pairs_written_out_one_by_one(case):
    """The routed part of the layer against a sum over (token, chosen expert)
    pairs written out here, each pair its own expert's SwiGLU: routing as the
    router gives it, a batch routed wholly onto one expert, experts that get
    no row, and a batch with no valid row. The counts are of the valid
    tokens: their pairs, and the held experts those touched."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import experts

    cfg, layer, h = laguna_expert_layer(3)
    valid = jnp.arange(50) < (0 if case == "nothing-valid" else 45)
    if case == "one-expert":  # every token's four choices are experts 3 (by far), 20, 21, 22: expert 3 gets all 45 valid rows
        layer["router"] = jnp.zeros((64, 32), jnp.float32)
        h = jnp.abs(h)
        layer["router"] = layer["router"].at[:, 3].set(1.0).at[:, jnp.asarray([20, 21, 22])].set(0.5)
    if case == "two-experts-only":
        cfg = dataclasses.replace(cfg, experts_per_token=2)
        h = jnp.abs(h)
        layer["router"] = jnp.zeros((64, 32), jnp.float32).at[:, 7].set(1.0).at[:, 30].set(0.5)
    routed_only = {k: v for k, v in layer.items() if not k.startswith("shared_")}
    with jax.default_matmul_precision("highest"):
        y, counts = experts.routed_experts(cfg, h, routed_only, valid)
        chosen, weights = experts.route(cfg, h, layer)
        gate = jnp.einsum("td,tkdf->tkf", h, layer["w_gate"][chosen])
        up = jnp.einsum("td,tkdf->tkf", h, layer["w_up"][chosen])
        out = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(gate) * up, layer["w_down"][chosen])
        want = jnp.sum(out * weights[..., None], axis=1)
    sizes = np.bincount(np.asarray(chosen)[np.asarray(valid)].reshape(-1), minlength=32)
    assert [int(c) for c in counts] == [int(valid.sum()) * cfg.experts_per_token, int((sizes > 0).sum())]
    if case == "one-expert":
        assert sizes[3] == 45 and sorted(np.flatnonzero(sizes)) == [3, 20, 21, 22]
    if case == "two-experts-only":
        assert sorted(np.flatnonzero(sizes)) == [7, 30] and int(counts[1]) == 2
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6, rtol=1e-5)
    assert float(jnp.abs(want[:45]).max()) > 1e-3


def test_yarn_s_frequencies_and_factor_are_the_published_formula():
    """The formula written out here in numpy (the YaRN paper's and the
    transformers library's `_compute_yarn_parameters`), at the published
    numbers and at tiny-laguna's."""
    import math

    from modal_tpu.models.llama import get_config, rope_frequencies

    def published(dim, base, factor, original, beta_fast, beta_slow):
        pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

        def correction_dim(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low, high = max(math.floor(correction_dim(beta_fast)), 0), min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        extrapolation_factor = 1 - ramp
        return interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor, 0.1 * math.log(factor) + 1.0, (low, high)

    real = get_config("laguna-xs.2")
    full, sliding = real.layer_kinds[0], real.layer_kinds[1]
    want, factor, dims = published(64, 500_000.0, 64.0, 4096, 64.0, 1.0)
    assert dims == (5, 16) and full.rope_dim == 64 and sliding.rope_dim == 128 and sliding.yarn == ()
    assert full.yarn[4] == 1.4158883083359672 == pytest.approx(factor, rel=1e-12)  # the published attention_factor is the formula's
    got = np.asarray(rope_frequencies(real, full))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 1.0 / 500_000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=2e-6)  # fast frequencies keep their own rate
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=2e-6)  # slow ones are divided by the factor
    assert np.all(got[6:16] < plain[6:16]) and np.all(got[6:16] > plain[6:16] / 64)
    np.testing.assert_allclose(np.asarray(rope_frequencies(real, sliding)), 1.0 / 10_000.0 ** (np.arange(0, 128, 2) / 128), rtol=2e-6)
    tiny = get_config("tiny-laguna")
    want, factor, dims = published(8, 100.0, 4.0, 64, 8.0, 1.0)
    assert dims == (0, 3) and tiny.layer_kinds[0].yarn[4] == pytest.approx(factor, rel=1e-12)
    np.testing.assert_allclose(np.asarray(rope_frequencies(tiny, tiny.layer_kinds[0])), want, rtol=2e-6)
    # the reference writes the same formula out for itself
    inv, ref_factor = reference_laguna.rotary_rule(dict(TINY_LAGUNA["rope_parameters"]["full_attention"], dims=8))
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert ref_factor == pytest.approx(factor, rel=1e-12)


def test_yarn_s_factor_enters_the_softmax_s_scale_by_the_family_s_rule():
    """m(s, a) = 0.1 a ln s + 1: cos and sin take m(s, mscale) / m(s,
    mscale_all_dim), the softmax's scale (qk_nope + qk_rope)^-0.5 x m(s,
    mscale_all_dim)^2; at the published numbers 1 and 0.130861."""
    import math

    from modal_tpu.models.llama import get_config, rope_frequencies

    kind = get_config("a.x-k1").layer_kinds[3]
    m = 0.1 * 1 * math.log(32) + 1
    assert m == pytest.approx(1.346574, rel=1e-6) and kind.yarn == (32.0, 4096, 32.0, 1.0, 1.0)
    assert kind.softmax_scale == pytest.approx(192**-0.5 * m * m, rel=1e-12) and kind.softmax_scale == pytest.approx(0.130861, rel=1e-5)
    assert (kind.rope_dim, kind.rope_theta, kind.n_kv_heads, kind.attn_name, kind.latent) == (64, 10_000.0, 1, "mla", (1536, 512, 128, 64, 128))
    inv, on_cos_sin, scale = reference_axk1.rotary_rule(reference_axk1.model_shapes(load(_paths.BENCH_DIR, "configs", "a.x-k1-serve-1chip-ep16.json")))
    np.testing.assert_allclose(np.asarray(rope_frequencies(get_config("a.x-k1"), kind)), inv, rtol=2e-6)
    plain = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=2e-6)  # correction dims 10 and 23
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=2e-6)
    assert (on_cos_sin, scale) == (1.0, pytest.approx(kind.softmax_scale, rel=1e-12))
    # tiny-axk1 has both effects: mscale 1 over mscale_all_dim 0.5
    tiny = get_config("tiny-axk1").layer_kinds[0]
    m_all = 0.1 * 0.5 * math.log(4) + 1
    assert tiny.yarn[4] == pytest.approx((0.1 * math.log(4) + 1) / m_all, rel=1e-12) and tiny.yarn[4] > 1.05
    assert tiny.softmax_scale == pytest.approx(24**-0.5 * m_all**2, rel=1e-12)
    _inv, on_cos_sin, scale = reference_axk1.rotary_rule(reference_axk1.model_shapes(TINY_AXK1))
    assert (on_cos_sin, scale) == (pytest.approx(tiny.yarn[4], rel=1e-12), pytest.approx(tiny.softmax_scale, rel=1e-12))
    # a model without the rule keeps 1 / sqrt(head width)
    assert get_config("laguna-xs.2").layer_kinds[0].softmax_scale == 0.0 and get_config("llama3-8b").layer_kinds[0].latent == ()


@pytest.mark.parametrize("stored", [24, 128], ids=["row-as-wide-as-the-model", "row-stored-padded"])
def test_the_absorbed_scores_and_outputs_equal_the_rebuilt_keys_and_values_on_the_same_latent(stored):
    """The decode step's form (`q' = q_nope W_UK^T` against the cached rows,
    their latents as values, the mix taken up through W_UV) against keys and
    values a head rebuilt from the same rows, written out here."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    params, cfg = program("axk1-latent-dense", 2)
    layer, kind = layers_of(params, cfg)[0], cfg.layer_kinds[0]
    _q_rank, rank, nope, rope, vd = kind.latent
    keys = jax.random.split(jax.random.PRNGKey(stored), 2)
    rows = jax.random.normal(keys[0], (9, PAGE, 1, stored), jnp.float32).at[..., rank + rope :].set(0.0)
    q = jax.random.normal(keys[1], (2, 1, 4, nope + rope), jnp.float32)
    table = jnp.asarray([[3, 1, 7], [2, 8, 5]], jnp.int32)
    positions = jnp.asarray([9, 6], jnp.int32)
    mask = jnp.where(jnp.arange(3 * PAGE)[None, None, None, :] <= positions[:, None, None, None], 0.0, -jnp.inf).astype(jnp.float32)
    scale = pk._softmax_scale(cfg, kind)
    with jax.default_matmul_precision("highest"):
        got = pk._absorbed_attention(
            kind, q, layer, stored, lambda q_abs: pk._paged_attention(q_abs, rows, None, table, mask, scale=scale, latent=rank)
        )
        w_up = layer["wkv_up"].reshape(rank, 4, nope + vd)
        for slot in range(2):
            live = rows[table[slot]].reshape(3 * PAGE, stored)[: int(positions[slot]) + 1]
            c, k_r = live[:, :rank], live[:, rank : rank + rope]
            for head in range(4):
                k = jnp.concatenate([c @ w_up[:, head, :nope], k_r], axis=-1)  # [T, nope + rope]
                probs = jax.nn.softmax(k @ q[slot, 0, head] * scale)
                want = probs @ (c @ w_up[:, head, nope:])
                np.testing.assert_allclose(np.asarray(got[slot, 0, head]), np.asarray(want), atol=2e-6, rtol=1e-5)
        # and the block a prefill chunk rebuilds is the same keys and values
        k_blk, v_blk = pk._rebuild_kv(kind, layer, rows[3])
        np.testing.assert_allclose(np.asarray(k_blk[:, 2, :nope]), np.asarray(rows[3, :, 0, :rank] @ w_up[:, 2, :nope]), atol=2e-6)
        np.testing.assert_allclose(np.asarray(v_blk[:, 2]), np.asarray(rows[3, :, 0, :rank] @ w_up[:, 2, nope:]), atol=2e-6)
    assert k_blk.shape == (PAGE, 4, nope + rope) and bool((k_blk[:, 0, nope:] == k_blk[:, 3, nope:]).all())  # ONE rope key under every head


def test_the_latent_cache_is_one_array_a_layer_group_and_no_value_pool():
    import jax

    from modal_tpu.models import paged_kv as pk
    from modal_tpu.models.llama import get_config

    cfg = get_config("tiny-axk1")
    cache = pk.PagedKVCache.create(cfg, 3, 50, PAGE, 24)
    assert [(first, n) for _k, first, n in cfg.layer_groups] == [(0, 1), (1, 4)] and not cfg.uniform and not cfg.has_window
    assert [a.shape for a in cache.k_pages] == [(1, 50, PAGE, 1, 24), (4, 50, PAGE, 1, 24)]  # a token's row: latent 16 + rope key 8
    assert cache.v_pages == (None, None) and cache.window_table is None and cache.page_size == PAGE
    assert cache.pool_bytes() == 50 * PAGE * 5 * 24 * 2 == pk.pool_bytes_by_kind(cfg, cache)[0] and pk.pool_bytes_by_kind(cfg, cache)[1] == 0
    # at the published widths a row of 512 + 64 is stored 640 wide (the chip's layout: PERF.md section 6, PR 37)
    real = get_config({"name": "a.x-k1", "n_layers": 7})
    assert pk.k_cache_dim(real) == 640 and pk.k_cache_dim(cfg) == 24 and pk.k_cache_dim(get_config("tiny-axk1", kv_rank=160)) == 256
    shapes = jax.eval_shape(lambda: pk.PagedKVCache.create(real, 64, 20481, 16))
    assert [a.shape for a in shapes.k_pages] == [(1, 20481, 16, 1, 640), (6, 20481, 16, 1, 640)] and shapes.v_pages == (None, None)
    assert sum(a.size * 2 for a in shapes.k_pages) / (20481 * 16) == 7 * 640 * 2 == 8960


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
        y, (used, touched) = routed_experts(cfg, h, layer, valid)
        scores = jax.nn.sigmoid(h @ layer["router"])
        weight = scores[:, 3] / scores[:, jnp.asarray([3, 20, 21, 22])].sum(axis=-1)  # the bias is not in the weights
        want = weight[:, None] * ((jax.nn.silu(h @ layer["w_gate"][3]) * (h @ layer["w_up"][3])) @ layer["w_down"][3])
    assert int(used) == 45 and int(touched) == 1  # one pair a valid token, none dropped; one held expert got them all
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6, rtol=1e-5)


def served(preset, seed):
    """Requests through the program's own engine, two pools and all: prompts
    shorter than the window, longer than a chunk, and in between."""
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    cfg = get_config(preset)
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
@pytest.mark.parametrize("preset", sorted(MODELS))
def test_what_the_engine_serves_lies_by_the_reference_and_the_fp8_control_does_not(preset, seed):
    requests, stats = served(preset, seed)
    module, fixture = MODELS[preset]
    out = reference.compare(module.Reference(fixture, seed), requests, control="fp8")
    assert out["tokens_compared"] == 120 and out["requests_compared"] == 5
    # logits of this size are ~0.5 wide: bfloat16 through the engine stays within 0.012 of the
    # reference's best (0.0085 the largest over both models and four seeds), float8 operands do not
    # (0.018 the smallest); the means lie ten times apart (under 1e-4 against over 7e-4)
    assert out["logit_gap_max"] < 0.012 < out["control_logit_gap_max"]
    assert out["logit_gap_mean"] < 3e-4 < out["control_logit_gap_mean"]
    moe = stats["moe"]
    if preset == "tiny-axk1":  # one pool of latent rows; 6 of 24 held in 4 expert layers
        assert "kv_window_pages_total" not in stats and stats["kv_bytes_per_token"] == 5 * 24 * 2
        assert 0 < moe["local_assignments"] < moe["assignments"] and moe["expert_calls"] % (4 * 6) == 0
        return
    assert stats["kv_window_pages_released"] > 0 and stats["kv_window_pages_high_water"] <= stats["kv_window_pages_total"]
    if preset == "tiny-mimo":  # 8 of 32 held in 6 expert layers
        assert 0 < moe["local_assignments"] < moe["assignments"] and moe["expert_calls"] % (6 * 8) == 0
    else:  # all 32 held in 4 expert layers: every pair is local, and a call touches no more experts than it has pairs or holds
        assert 0 < moe["local_assignments"] == moe["assignments"] and moe["expert_calls"] % (4 * 32) == 0
        assert 0 < moe["experts_touched"] <= min(moe["expert_calls"], moe["local_assignments"])
