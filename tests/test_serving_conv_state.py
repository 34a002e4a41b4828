"""A model with gated short-convolution layers in the serving engine: the
third holding beside the two kinds of KV pool, a state a slot of each
convolution layer (the last rows of B * x its prefix left), and QK-normed
attention whose 8 KV heads of 16 are stored one row a token (`tiny-lfm2`:
two dense convolution layers, then attention and three convolutions over 8
experts, top-2 with a selection bias). Against the plain reference
(benchmark/benchlib/reference_lfm2.py, which writes the convolution over the
whole sequence at once): chunked prefill whose prompt ends 0-3 rows into a
chunk, decode with slots joining mid-decode, a half-prefilled slot's state
left as it was, a released slot reused by a shorter prompt, a preemption and
its re-prefill; both decode paths. Then what the engine refuses, by mechanism,
and what it reports."""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

from tests.benchmark import _paths
from benchlib import reference_lfm2

with open(os.path.join(_paths.FIXTURES, "lfm2root", "benchmark", "configs", "tiny-lfm2.json")) as _f:
    TINY = json.load(_f)
PAGE, CHUNK, SEED = 4, 16, 5
# float32 both sides, sums in another order: a state shifted by one row, a padded row let into the
# state or a missing head norm reads 1e-2 and more on logits of ~1
ATOL = 1e-5


@pytest.fixture(scope="module")
def model():
    """(params, cfg) of the program's own init, in float32 (the same values)."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny-lfm2")
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params), dataclasses.replace(cfg, dtype=jnp.float32)


@pytest.fixture(scope="module")
def ref():
    return reference_lfm2.Reference(TINY, SEED)


def tokens_of(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, size=n)]


def fresh_cache(cfg, slots=3, pages_per_slot=24):
    """Every slot's row whole from the start (the page manager's bookkeeping is not what is tested)."""
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    cache = pk.PagedKVCache.create(cfg, slots, 1 + slots * pages_per_slot, PAGE, pages_per_slot)
    for slot in range(slots):
        row = jnp.arange(1 + slot * pages_per_slot, 1 + (slot + 1) * pages_per_slot, dtype=jnp.int32)
        cache = pk.assign_pages(cache, slot, 0, row)
    return cache


def prefill(params, cfg, cache, slot, tokens, start=0, stop=None):
    """Chunks of CHUNK from `start` up to `stop` (all of `tokens`): (last logits, cache)."""
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    logits = None
    for at in range(start, len(tokens) if stop is None else stop, CHUNK):
        chunk = tokens[at : min(at + CHUNK, len(tokens) if stop is None else stop)]
        padded = np.zeros((CHUNK,), np.int32)
        padded[: len(chunk)] = chunk
        logits, _tok, cache = pk.paged_prefill(
            params, cfg, jnp.asarray(padded), jnp.int32(len(chunk)), cache, jnp.int32(slot), jnp.int32(at)
        )
    return np.asarray(logits), cache


def decode(params, cfg, cache, feeds, impl):
    """One step; `feeds` {slot: token} decode, every other slot is idle: ({slot: logits}, cache)."""
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    slots = cache.num_slots
    tokens = np.zeros((slots,), np.int32)
    active = np.zeros((slots,), bool)
    for slot, tok in feeds.items():
        tokens[slot], active[slot] = tok, True
    logits, _next, cache = pk.paged_decode_step(params, cfg, jnp.asarray(tokens), cache, jnp.asarray(active), impl)
    return {slot: np.asarray(logits[slot]) for slot in feeds}, cache


def states_of(cache, slot):
    return [np.asarray(st[:, slot]) for st in cache.conv_state if st is not None]


IMPLS = ["gather", "kernel_interpret"]


def test_the_reference_makes_the_program_s_weights_and_the_pool_packs_the_heads():
    import jax

    from modal_tpu.models import paged_kv as pk
    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny-lfm2")
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    theirs = [jax.tree_util.tree_map(lambda a, j=j: a[j], g) for (_k, _f, n), g in zip(cfg.layer_groups, params["layers"]) for j in range(n)]
    mine = reference_lfm2.init_weights(TINY, SEED)
    assert len(mine["layers"]) == len(theirs) == 6
    for a, b in zip([mine] + mine["layers"], [params] + theirs):
        assert sorted(k for k in a if k != "layers") == sorted(k for k in b if k != "layers")
        for key in a:
            if key != "layers":
                assert a[key].dtype == b[key].dtype and a[key].shape == b[key].shape and bool((a[key] == b[key]).all()), key
    assert {"conv_in", "conv_w", "conv_out"} <= set(mine["layers"][0]) and "wq" not in mine["layers"][0]
    assert {"q_head_norm", "k_head_norm", "router_bias"} <= set(mine["layers"][2]) and mine["layers"][0]["conv_w"].shape == (3, 256)
    # the attention pool holds a token's 8 KV heads of 16 as ONE row of 128; the convolution groups hold no page
    cache = pk.PagedKVCache.create(cfg, 3, 40, PAGE, 24)
    assert [None if k is None else k.shape for k in cache.k_pages] == [None, (1, 40, PAGE, 128), None]
    assert [None if s is None else s.shape for s in cache.conv_state] == [(2, 3, 2, 256), None, (3, 3, 2, 256)]
    assert cache.pool_bytes() == 2 * 40 * PAGE * 128 * 2 and cache.conv_state_bytes() == 5 * 3 * 2 * 256 * 2


@pytest.mark.parametrize("n_prompt", [32, 33, 34, 35], ids=["0-rows-into-a-chunk", "1-row", "2-rows", "3-rows"])
@pytest.mark.parametrize("impl", IMPLS)
def test_chunked_prefill_then_decode_equals_the_reference(model, ref, impl, n_prompt):
    """A prompt that ends 1 or 2 rows into its last chunk hands the next
    chunk a state that is part old state, part new rows; padded rows never
    enter it."""
    params, cfg = model
    tokens = tokens_of(n_prompt, n_prompt + 6)
    last, cache = prefill(params, cfg, fresh_cache(cfg), 1, tokens[:n_prompt])
    got = [last]
    for pos in range(n_prompt, len(tokens)):
        out, cache = decode(params, cfg, cache, {1: tokens[pos]}, impl)
        got.append(out[1])
    want = ref.logits(tokens, list(range(n_prompt - 1, len(tokens))))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(np.stack(got), want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("impl", IMPLS)
def test_slots_join_mid_decode_and_a_half_prefilled_slot_keeps_its_state(model, ref, impl):
    params, cfg = model
    a, b = tokens_of(1, 20 + 12), tokens_of(2, 37 + 5)
    got = {0: [], 2: []}
    last, cache = prefill(params, cfg, fresh_cache(cfg), 0, a[:20])
    got[0].append(last)
    pos_a = 20
    for _ in range(3):
        out, cache = decode(params, cfg, cache, {0: a[pos_a]}, impl)
        got[0].append(out[0])
        pos_a += 1
    _, cache = prefill(params, cfg, cache, 2, b[:37], stop=16)  # one chunk of three: b is half prefilled
    held = states_of(cache, 2)
    for _ in range(4):  # slot 0 decodes; slot 2 has a length and no query, and its state must not move
        out, cache = decode(params, cfg, cache, {0: a[pos_a]}, impl)
        got[0].append(out[0])
        pos_a += 1
        assert all(np.array_equal(x, y) for x, y in zip(held, states_of(cache, 2)))
    assert int(cache.seq_lens[2]) == 16
    last, cache = prefill(params, cfg, cache, 2, b[:37], start=16)
    got[2].append(last)
    for pos_b in range(37, 41):  # both decode
        out, cache = decode(params, cfg, cache, {0: a[pos_a], 2: b[pos_b]}, impl)
        got[0].append(out[0])
        got[2].append(out[2])
        pos_a += 1
    np.testing.assert_allclose(np.stack(got[0]), ref.logits(a, list(range(19, pos_a))), atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.stack(got[2]), ref.logits(b, list(range(36, 41))), atol=ATOL, rtol=0)


def test_a_released_slot_reused_by_a_shorter_prompt_starts_from_zeros(model, ref):
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    params, cfg = model
    long, short = tokens_of(3, 35 + 5), tokens_of(4, 7 + 4)
    _, cache = prefill(params, cfg, fresh_cache(cfg), 1, long[:35])
    for pos in range(35, 40):
        _, cache = decode(params, cfg, cache, {1: long[pos]}, "gather")
    assert any(np.abs(s).max() > 0 for s in states_of(cache, 1))
    cache = pk.release_slot(cache, 1)  # as the page manager leaves a finished slot; nothing zeroes the state
    cache = pk.assign_pages(cache, 1, 0, jnp.arange(30, 54, dtype=jnp.int32))
    last, cache = prefill(params, cfg, cache, 1, short[:7])
    got = [last]
    for pos in range(7, 11):
        out, cache = decode(params, cfg, cache, {1: short[pos]}, "gather")
        got.append(out[1])
    np.testing.assert_allclose(np.stack(got), ref.logits(short, list(range(6, 11))), atol=ATOL, rtol=0)


def engine_of(model, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    params, cfg = model
    kwargs = dict(max_slots=3, page_size=PAGE, prefill_chunk=CHUNK, num_pages=120)
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


def test_a_preempted_request_re_prefills_its_state_and_every_stream_lies_by_the_reference(model, ref):
    prompts = [tokens_of(10 + i, n) for i, n in enumerate((60, 44, 70, 25))]
    roomy = engine_of(model).start()
    try:
        want = [h.result(timeout=300) for h in [roomy.submit(p, 40) for p in prompts]]
    finally:
        roomy.stop()
    tight = engine_of(model, num_pages=40).start()  # 39 pages = 156 positions: three of these cannot reach their ends
    try:
        got = [h.result(timeout=300) for h in [tight.submit(p, 40) for p in prompts]]
        stats = tight.stats()
    finally:
        tight.stop()
    assert stats["preemptions"] > 0 and got == want
    conv = stats["conv"]
    # every admission, the re-admissions among them, started its slot's state from zeros in its first chunk
    assert conv["state_resets"] == stats["requests_admitted"] == len(prompts) + stats["preemptions"]
    assert conv["chunks_carried"] == stats["prefill_chunks"] - conv["state_resets"] > 0
    for prompt, served in zip(prompts, got):
        logits = ref.logits(prompt + served[:-1], list(range(len(prompt) - 1, len(prompt) + len(served) - 1)))
        gap = logits.max(axis=-1) - logits[np.arange(len(served)), served]
        assert gap.max() < 1e-4, gap.max()


def test_the_engine_reports_the_state_beside_the_pool_and_zeroes_it_at_admission(model, monkeypatch):
    from modal_tpu.serving import engine as engine_mod

    spans = []
    monkeypatch.setattr(engine_mod.tracing, "current_context", lambda: types.SimpleNamespace(trace_id="t", span_id="s"))
    monkeypatch.setattr(engine_mod.tracing, "record_span", lambda name, **kw: spans.append((name, kw.get("attrs", {}))))
    engine = engine_of(model)
    engine.spans_on = False  # the admit span is recorded wherever a request carries a context
    engine.start()
    try:
        assert len(engine.submit(tokens_of(9, 20), 5).result(timeout=300)) == 5
        stats = engine.stats()
    finally:
        engine.stop()
    # 5 convolution layers x 2 rows x 256 wide in float32 a slot; the one attention layer's keys and values
    assert stats["conv"] == {"layers": 5, "state_bytes_per_slot": 5 * 2 * 256 * 4, "state_resets": 1, "chunks_carried": 1}
    assert stats["kv_bytes_per_token"] == 2 * 8 * 16 * 4
    admits = [attrs for name, attrs in spans if name == "serving.admit"]
    assert admits and all(attrs["conv_state"] == "zeroed" for attrs in admits)


def test_the_prefix_cache_is_off_by_default_and_refused_when_asked(model):
    engine = engine_of(model)
    assert engine.pages.prefix_cache is None and engine.pages.lookup(list(range(20))) is None
    with pytest.raises(ValueError, match="prefix_cache=True with convolution layers") as refused:
        engine_of(model, prefix_cache=True)
    assert "state at the prefix's end" in str(refused.value) and "lfm" not in str(refused.value).lower()


@pytest.mark.parametrize("case", ["draft", "prefill", "decode"])
def test_speculation_and_shipments_are_refused_naming_the_convolution_state(model, case):
    kwargs = {"draft": model} if case == "draft" else {"role": case}
    with pytest.raises(ValueError, match="speculative decoding" if case == "draft" else "ships KV pages") as refused:
        engine_of(model, **kwargs)
    message = str(refused.value)
    assert "convolution" in message and "state" in message and "lfm" not in message.lower()
