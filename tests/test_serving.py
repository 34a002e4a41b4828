"""ISSUE 9: production inference serving — paged KV cache, continuous
batching, SSE streaming, SLO autoscaling.

Contracts pinned here (docs/SERVING.md):
- the block allocator survives alloc/free churn with zero stranded capacity
  (pages are interchangeable; fragmentation is structural-zero);
- paged attention matches the dense KVCache path numerically;
- a request admitted MID-DECODE joins the running batch without restarting
  in-flight sequences (bit-identical streams, step counter monotonic);
- KV HBM is bounded by the page pool, never by num_requests × max_len —
  pool pressure preempts + requeues instead of OOMing, with zero token
  loss/duplication;
- a chaos reset mid-SSE-stream degrades to the buffered result with every
  token delivered exactly once;
- the scheduler sizes serving replicas from pushed TTFT/tokens-per-s
  telemetry against the declared SLO targets.

Plus the pre-existing `modal-tpu serve` hot-reload e2e (reload.py).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one shared engine geometry for every test: the jitted paged executables
# (prefill buckets + the decode step) key on these shapes, so the whole
# module pays each compile once
SLOTS, PAGES, PAGE, PAGES_PER_SLOT = 4, 25, 16, 8


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(params, cfg, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    kwargs = dict(
        max_slots=SLOTS, num_pages=PAGES, page_size=PAGE,
        pages_per_slot=PAGES_PER_SLOT, prefill_chunk=32,
    )
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


# ---------------------------------------------------------------------------
# paged KV cache + block allocator
# ---------------------------------------------------------------------------


def test_page_allocator_alloc_free_churn():
    """Exact-fit under arbitrary fragmentation history: any free page serves
    any slot, so churn can never strand capacity."""
    from modal_tpu.serving.pages import PageAllocator, PagePoolExhausted

    alloc = PageAllocator(num_pages=9, page_size=16)  # 8 usable (page 0 reserved)
    assert alloc.free_pages == 8
    a = alloc.alloc(3)
    b = alloc.alloc(3)
    assert 0 not in a + b  # scratch page never handed out
    assert len(set(a + b)) == 6
    # fragment: free the middle allocation, then ask for more than any
    # contiguous run — a block allocator with a page table doesn't care
    alloc.free(b)
    c = alloc.alloc(5)
    assert len(c) == 5 and alloc.free_pages == 0
    with pytest.raises(PagePoolExhausted):
        alloc.alloc(1)
    with pytest.raises(ValueError):
        alloc.free([c[0], c[0]])  # double free in one call
    alloc.free(c)
    alloc.free(a)
    assert alloc.free_pages == 8
    with pytest.raises(ValueError):
        alloc.free([a[0]])  # double free across calls
    assert alloc.high_water == 8
    assert alloc.pages_for(1) == 1 and alloc.pages_for(16) == 1 and alloc.pages_for(17) == 2


def test_paged_prefill_matches_dense(tiny_model):
    """Paged attention == dense KVCache attention (logit-level; greedy token
    chains can diverge on exact bf16 ties, so the pin is numeric)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.llama import KVCache
    from modal_tpu.models.paged_kv import PagedKVCache, assign_pages, paged_decode_step, paged_prefill
    from modal_tpu.serving.pages import PageAllocator
    from modal_tpu.models.sampling import decode_step, prefill

    params, cfg = tiny_model
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, cfg.vocab_size).astype(jnp.int32)

    dense = KVCache.create(cfg, 1, PAGES_PER_SLOT * PAGE)
    dlogits, dense = prefill(params, cfg, prompt, dense)

    cache = PagedKVCache.create(cfg, SLOTS, PAGES, PAGE, PAGES_PER_SLOT)
    alloc = PageAllocator(PAGES, PAGE)
    pages = alloc.alloc(3)
    cache = assign_pages(cache, 0, 0, jnp.asarray(pages, jnp.int32))
    # chunked prefill (2 chunks) must agree with the dense whole-prompt pass
    padded1 = jnp.zeros((16,), jnp.int32).at[:6].set(prompt[0, :6])
    _l, _t, cache = paged_prefill(params, cfg, padded1, jnp.int32(6), cache, jnp.int32(0), jnp.int32(0))
    padded2 = jnp.zeros((16,), jnp.int32).at[:4].set(prompt[0, 6:])
    plogits, _tok, cache = paged_prefill(params, cfg, padded2, jnp.int32(4), cache, jnp.int32(0), jnp.int32(6))
    np.testing.assert_allclose(np.asarray(plogits), np.asarray(dlogits[0]), atol=3e-2, rtol=0)

    # one decode step, same token fed both paths
    tok = int(np.asarray(dlogits[0]).argmax())
    dlog2, dense = decode_step(params, cfg, jnp.asarray([[tok]], jnp.int32), dense)
    toks = jnp.zeros((SLOTS,), jnp.int32).at[0].set(tok)
    active = jnp.zeros((SLOTS,), bool).at[0].set(True)
    plog2, _n, cache = paged_decode_step(params, cfg, toks, cache, active)
    np.testing.assert_allclose(np.asarray(plog2[0]), np.asarray(dlog2[0]), atol=3e-2, rtol=0)
    assert int(cache.seq_lens[0]) == 11


# (query heads a KV head, pages a slot, pages a block, start_pos, length, S_pad): pages of 16. A
# block of `b` pages is 16*b positions; unassigned row entries are the scratch page 0
PREFILL_ATTENTION_CASES = {
    "first-chunk-one-block": (2, 8, 2, 0, 10, 16),
    "mid-page-start-ends-inside-block": (2, 8, 2, 21, 16, 16),
    "page-aligned-ends-at-block-end": (2, 8, 2, 32, 32, 32),
    "beyond-one-block-short-length": (2, 8, 2, 70, 9, 16),
    "block-does-not-divide-row-full-span": (2, 7, 2, 96, 16, 16),
    "block-does-not-divide-row-live-in-last": (2, 5, 3, 50, 7, 16),
    "block-clamped-to-row": (2, 4, None, 20, 12, 16),
    "one-page-blocks": (2, 8, 1, 37, 16, 16),
    "gqa-1": (1, 8, 2, 21, 16, 16),
    "gqa-4": (4, 8, 2, 21, 16, 16),
    "gqa-8": (8, 8, 2, 21, 16, 16),
    "gqa-8-row-not-divided": (8, 7, 2, 90, 20, 32),
}


@pytest.mark.parametrize("case", sorted(PREFILL_ATTENTION_CASES))
def test_prefill_attention_matches_the_gather_reference(case):
    """The prefill chunk's KV-block loop against the span-wide gather path
    (`_paged_attention`, what verify and the CPU's decode run) on the same pool
    and page row: the same arithmetic with the sum reassociated, so float32
    agrees to rounding and bfloat16 within the logit tolerance; every row that
    is read is finite though masked positions and the scratch page hold
    garbage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.paged_kv import PREFILL_KV_BLOCK, _paged_attention, _prefill_attention, prefill_kv_block_pages

    n_rep, pages_per_slot, block_pages, start_pos, length, s_pad = PREFILL_ATTENTION_CASES[case]
    if block_pages is None:
        block_pages = prefill_kv_block_pages(pages_per_slot, PAGE)
        assert block_pages == pages_per_slot < PREFILL_KV_BLOCK // PAGE
    n_kv, hd, pool = 2, 16, 12
    live = start_pos + length
    span = pages_per_slot * PAGE
    assert live <= span
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    held = -(-live // PAGE)
    # the slot holds `held` pages in no order; the rest of its row is the scratch page
    row = jnp.zeros((pages_per_slot,), jnp.int32).at[:held].set(jax.random.permutation(keys[0], pool - 1)[:held] + 1)
    q_pos = start_pos + jnp.arange(s_pad, dtype=jnp.int32)
    mask = jnp.where(jnp.arange(span)[None, :] <= q_pos[:, None], 0.0, -jnp.inf)[None, None].astype(jnp.float32)
    for dtype, atol in ((jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)):
        q = jax.random.normal(keys[1], (s_pad, n_kv * n_rep, hd), dtype)
        # page 0 (scratch) holds values that would swamp a row if a masked position leaked in
        k_pages = jax.random.normal(keys[2], (pool, PAGE, n_kv, hd), dtype).at[0].set(30.0)
        v_pages = jax.random.normal(keys[3], (pool, PAGE, n_kv, hd), dtype).at[0].set(1e4)
        got = _prefill_attention(q, k_pages, v_pages, row, q_pos, jnp.int32(live), block_pages)
        ref = _paged_attention(q[None], k_pages, v_pages, row[None], mask)[0]
        assert got.shape == ref.shape and got.dtype == ref.dtype
        got, ref = np.asarray(got[:length], np.float32), np.asarray(ref[:length], np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, atol=atol, rtol=0)


@pytest.mark.parametrize("chunk", ["first-chunk", "mid-page-beyond-one-block", "page-aligned-ends-at-block-end"])
@pytest.mark.parametrize("n_rep", [1, 4, 8])
def test_paged_prefill_logits_match_the_span_wide_path(n_rep, chunk):
    """`paged_prefill` (the KV-block loop, at the block size the code ships:
    512 positions of a 640-position row it does not divide) against
    `paged_verify_step` (the gather path over the whole span) on the same
    pool: the chunk's last logits within the dense test's tolerance."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.models.paged_kv import PagedKVCache, assign_pages, paged_prefill, paged_verify_step

    start_pos, length, s_pad = {
        "first-chunk": (0, 10, 16),
        "mid-page-beyond-one-block": (521, 20, 32),
        "page-aligned-ends-at-block-end": (480, 32, 32),
    }[chunk]
    heads = max(4, n_rep)
    cfg = dataclasses.replace(get_config("tiny"), n_heads=heads, n_kv_heads=heads // n_rep)
    params = init_params(cfg, jax.random.PRNGKey(n_rep))
    slots, pages_per_slot, pool = 2, 40, 48
    tokens = jax.random.randint(jax.random.PRNGKey(7), (start_pos + s_pad,), 0, cfg.vocab_size).astype(jnp.int32)
    cache = PagedKVCache.create(cfg, slots, pool, PAGE, pages_per_slot)
    held = -(-(start_pos + length) // PAGE)
    cache = assign_pages(cache, 0, 0, jnp.arange(pool - held, pool, dtype=jnp.int32))  # the rest of the row: scratch
    if start_pos:
        prefix = jnp.zeros((544,), jnp.int32).at[:start_pos].set(tokens[:start_pos])
        _l, _t, cache = paged_prefill(params, cfg, prefix, jnp.int32(start_pos), cache, jnp.int32(0), jnp.int32(0))
    chunk_tokens = tokens[start_pos:].at[length:].set(0)
    wide, _ = paged_verify_step(
        params, cfg, jnp.zeros((slots, s_pad), jnp.int32).at[0].set(chunk_tokens),
        jax.tree.map(jnp.copy, cache), jnp.asarray([True, False]),
    )
    logits, tok, cache = paged_prefill(params, cfg, chunk_tokens, jnp.int32(length), cache, jnp.int32(0), jnp.int32(start_pos))
    logits = np.asarray(logits)
    assert np.isfinite(logits).all() and int(tok) == logits.argmax() and int(cache.seq_lens[0]) == start_pos + length
    np.testing.assert_allclose(logits, np.asarray(wide[0, length - 1]), atol=3e-2, rtol=0)


# (program's config overrides, the program after the prefill, the decode step's attention):
# the layer loop carries a group's pool whole and shifts a layer's page ids by `layer * P`
CARRIED_POOL_CASES = {
    "uniform-two-layers": ({}, "decode", "gather"),
    "uniform-two-layers-kernel": ({}, "decode", "kernel_interpret"),
    # full, a group of THREE window layers, full: a window wider than anything written sees what
    # full attention sees, so the dense forward of five like layers is the reference
    "two-kinds-a-group-of-three": (dict(n_layers=5, attn_pattern=(0, 1, 1, 1, 0), window=64), "decode", "gather"),
    "two-kinds-a-group-of-three-kernel": (dict(n_layers=5, attn_pattern=(0, 1, 1, 1, 0), window=64), "decode", "kernel_interpret"),
    "verify-step": ({}, "verify", "gather"),
    # the slot beside the one that decodes holds a prompt and does not decode (half prefilled, as
    # the engine sees it): the kernel is told so and walks nothing for it
    "uniform-two-layers-kernel-beside-a-prompt-that-does-not-decode": ({}, "decode", "kernel_interpret"),
    "two-kinds-a-group-of-three-kernel-beside-a-prompt-that-does-not-decode": (
        dict(n_layers=5, attn_pattern=(0, 1, 1, 1, 0), window=64), "decode", "kernel_interpret",
    ),
}
BESIDE_A_PROMPT = {case for case in CARRIED_POOL_CASES if case.endswith("beside-a-prompt-that-does-not-decode")}


@pytest.mark.parametrize("case", sorted(CARRIED_POOL_CASES))
def test_the_carried_pool_takes_layer_l_s_rows_in_layer_l_s_pages_and_nowhere_else(case):
    """`paged_prefill` (two chunks), then `paged_decode_step` or
    `paged_verify_step`: after each program every row of every layer's pool
    is bit-equal to before except the slot's written positions and the
    layer's own scratch row, the written rows are the DENSE cache's rows of
    the same layer (a wrong `layer * P` shift passes a logits test on one
    layer and corrupts a deep model), and the logits are the dense ones."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.llama import KVCache, get_config, init_params
    from modal_tpu.models.paged_kv import (
        PagedKVCache, assign_entries, pack_entries, paged_decode_step, paged_prefill, paged_verify_step,
    )
    from modal_tpu.models.sampling import decode_step, prefill

    overrides, then, impl = CARRIED_POOL_CASES[case]
    cfg = get_config("tiny", **overrides)
    params = init_params(cfg, jax.random.PRNGKey(5))
    dense_cfg, dense_params = cfg, params
    if not cfg.uniform:  # the same weights as ONE stack of like layers
        dense_cfg = dataclasses.replace(cfg, attn_pattern=(), window=0)
        dense_params = {**params, "layers": jax.tree.map(lambda *a: jnp.concatenate(a), *params["layers"])}
    slot, n_prompt, n_new = 1, 21, 3 if then == "verify" else 1
    tokens = jax.random.randint(jax.random.PRNGKey(9), (n_prompt + n_new,), 0, cfg.vocab_size).astype(jnp.int32)

    dense = KVCache.create(dense_cfg, 1, PAGES_PER_SLOT * PAGE)
    dlogits, dense = prefill(dense_params, dense_cfg, tokens[None, :n_prompt], dense)
    want = [np.asarray(dlogits[0])]
    for j in range(n_new):
        step_logits, dense = decode_step(dense_params, dense_cfg, tokens[None, n_prompt + j : n_prompt + j + 1], dense)
        want.append(np.asarray(step_logits[0]))
    dense_k, dense_v = np.asarray(dense.k, np.float32)[:, 0], np.asarray(dense.v, np.float32)[:, 0]  # [layers, pos, n_kv, hd]
    assert np.abs(dense_k[0, :n_prompt] - dense_k[1, :n_prompt]).max() > 0.1  # layers are told apart by their rows

    # every pool starts as noise, so a row written where it should not be shows; the slot's rows
    # in the two tables name different pages, out of order
    cache = PagedKVCache.create(cfg, SLOTS, PAGES, PAGE, PAGES_PER_SLOT, window_num_pages=None if cfg.uniform else 12)
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    cache = cache._replace(
        k_pages=jax.tree.map(lambda a: jax.random.normal(next(noise), a.shape, a.dtype), cache.k_pages),
        v_pages=jax.tree.map(lambda a: jax.random.normal(next(noise), a.shape, a.dtype), cache.v_pages),
    )
    rows = {False: [7, 3], True: [9, 4]}  # by "is a window layer": page of positions 0-15, of 16-31
    def assign(cache, full, window):
        """[(slot, row index, page)] into the tables the cache has: a dense model's one, a window model's two"""
        return assign_entries(cache, pack_entries(SLOTS, 4, *([full] if cfg.uniform else [full, window])))

    cache = assign(cache, *([(slot, t, page) for t, page in enumerate(rows[windowed])] for windowed in (False, True)))

    def pools(c):
        """[(first layer, is a window group, K [n, P, page, n_kv, hd], V)] as float32 on the host"""
        ks, vs = (c.k_pages,), (c.v_pages,)
        if not cfg.uniform:
            ks, vs = c.k_pages, c.v_pages
        return [
            (first, bool(kind.window), np.asarray(k, np.float32), np.asarray(v, np.float32))
            for (kind, first, _n), k, v in zip(cfg.layer_groups, ks, vs)
        ]

    def check_written(before, after, positions):
        for (first, windowed, k0, v0), (_f, _w, k1, v1) in zip(before, after):
            untouched = np.ones(k0.shape[1:3], bool)
            untouched[0, 0] = False  # the layer's scratch row: padded positions, idle slots
            for pos in positions:
                untouched[rows[windowed][pos // PAGE], pos % PAGE] = False
            for j in range(k0.shape[0]):
                assert np.array_equal(k1[j][untouched], k0[j][untouched]) and np.array_equal(v1[j][untouched], v0[j][untouched]), (first + j, "a row outside the slot's written positions changed")
                for pos in positions:
                    page, offset = rows[windowed][pos // PAGE], pos % PAGE
                    np.testing.assert_allclose(k1[j, page, offset], dense_k[first + j, pos], atol=3e-2, rtol=0)
                    np.testing.assert_allclose(v1[j, page, offset], dense_v[first + j, pos], atol=3e-2, rtol=0)

    before = pools(cache)
    for start, length in ((0, 16), (16, n_prompt - 16)):
        chunk = jnp.zeros((16,), jnp.int32).at[:length].set(tokens[start : start + length])
        logits, _tok, cache = paged_prefill(params, cfg, chunk, jnp.int32(length), cache, jnp.int32(slot), jnp.int32(start))
        after = pools(cache)
        check_written(before, after, range(start, start + length))
        before = after
    got = [np.asarray(logits)]
    if case in BESIDE_A_PROMPT:
        # slot 0 holds ten positions of its own, in pages of its own, and is not active below
        cache = assign(cache, [(0, 0, 5)], [(0, 0, 6)])
        other = jnp.zeros((16,), jnp.int32).at[:10].set(tokens[:10] + 1)
        _l, _t, cache = paged_prefill(params, cfg, other, jnp.int32(10), cache, jnp.int32(0), jnp.int32(0))
        assert int(cache.seq_lens[0]) == 10
        before = pools(cache)
    active = jnp.zeros((SLOTS,), bool).at[slot].set(True)
    if then == "verify":
        fed = jnp.zeros((SLOTS, n_new), jnp.int32).at[slot].set(tokens[n_prompt:])
        step_logits, cache = paged_verify_step(params, cfg, fed, cache, active)
        got.extend(np.asarray(step_logits[slot]))
    else:
        fed = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(tokens[n_prompt])
        step_logits, _next, cache = paged_decode_step(params, cfg, fed, cache, active, impl)
        got.append(np.asarray(step_logits[slot]))
    check_written(before, pools(cache), range(n_prompt, n_prompt + n_new))
    np.testing.assert_allclose(np.stack(got), np.stack(want), atol=3e-2, rtol=0)


def test_total_kv_bytes_bounded_by_pool_not_requests(tiny_model):
    """The acceptance inequality: engine KV bytes are the POOL's, and the
    pool is smaller than dense per-request max_len caches for the same
    concurrent load."""
    from modal_tpu.models.llama import KVCache
    from modal_tpu.models.paged_kv import PagedKVCache

    params, cfg = tiny_model
    paged = PagedKVCache.create(cfg, SLOTS, PAGES, PAGE, PAGES_PER_SLOT)
    dense = KVCache.create(cfg, SLOTS, cfg.max_seq_len)
    dense_bytes = int(dense.k.size + dense.v.size) * dense.k.dtype.itemsize
    assert paged.pool_bytes() < dense_bytes / 2
    # and the pool does not grow with request count: shapes are fixed
    assert paged.k_pages.shape == (cfg.n_layers, PAGES, PAGE, cfg.n_kv_heads, cfg.head_dim)


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------


def test_mid_decode_admission_joins_without_restart(tiny_model):
    """THE continuous-batching pin: B admitted while A is mid-decode; A's
    token stream is bit-identical to its solo run, the engine's step counter
    never resets, and B's stream equals B's own solo run."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(1)
    prompt_a = rng.integers(0, cfg.vocab_size, size=9).tolist()
    prompt_b = rng.integers(0, cfg.vocab_size, size=14).tolist()

    eng = _engine(params, cfg).start()
    try:
        solo_a = eng.submit(prompt_a, max_new_tokens=30).result(timeout=120)
        solo_b = eng.submit(prompt_b, max_new_tokens=12).result(timeout=120)

        req_a = eng.submit(prompt_a, max_new_tokens=30)
        # wait until A is decoding (first token out), then join B mid-decode
        first, _done = req_a.wait_new(0, timeout=60)
        assert first, "A never produced a first token"
        steps_at_join = eng.step_count
        req_b = eng.submit(prompt_b, max_new_tokens=12)
        out_a = req_a.result(timeout=120)
        out_b = req_b.result(timeout=120)
    finally:
        eng.stop()
    assert out_a == solo_a, "in-flight sequence changed by a mid-decode admission"
    assert out_b == solo_b, "joining request decoded differently than solo"
    assert req_b.admitted_at > req_a.first_token_at, "B was not admitted mid-decode"
    assert eng.step_count > steps_at_join, "decode loop restarted instead of continuing"
    assert eng.requests_completed >= 4


def test_variable_length_admission_and_limits(tiny_model):
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(2)
    eng = _engine(params, cfg).start()
    try:
        lengths = [(3, 5), (40, 21), (17, 8), (60, 30), (1, 1), (25, 13)]
        reqs = [
            (gen, eng.submit(rng.integers(0, cfg.vocab_size, size=plen).tolist(), max_new_tokens=gen))
            for plen, gen in lengths
        ]
        for gen, r in reqs:
            assert len(r.result(timeout=120)) == gen
        # over-context and over-pool submissions fail loudly at submit
        with pytest.raises(ValueError, match="context limit"):
            eng.submit([1] * 100, max_new_tokens=PAGES_PER_SLOT * PAGE)
        with pytest.raises(ValueError):
            eng.submit([], max_new_tokens=1)
    finally:
        eng.stop()
    assert eng.pages.free_pages == PAGES - 1, "pages leaked across completions"


def test_pool_pressure_preempts_and_requeues_without_token_loss(tiny_model):
    """Eviction under pool exhaustion: more concurrent demand than pages —
    the youngest decoding request is preempted (pages freed, requeued with
    its generated prefix) and every stream still completes exactly-once,
    bounded by the pool the whole time."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(3)
    eng = _engine(params, cfg).start()
    try:
        # solo references first (deterministic regardless of preemption)
        prompts = [rng.integers(0, cfg.vocab_size, size=10).tolist() for _ in range(4)]
        solos = [eng.submit(p, max_new_tokens=100).result(timeout=240) for p in prompts]
        # 4 × (10 + 100 + 1) tokens needs 4×7=28 pages > 24 in the pool:
        # someone must be preempted mid-decode
        reqs = [eng.submit(p, max_new_tokens=100) for p in prompts]
        outs = [r.result(timeout=240) for r in reqs]
    finally:
        eng.stop()
    assert eng.preemptions > 0, "pool was never exhausted — test geometry wrong"
    for solo, out in zip(solos, outs):
        assert out == solo, "preemption changed or duplicated a token stream"
    assert eng.pages.allocator.high_water <= PAGES - 1
    assert eng.pages.free_pages == PAGES - 1


def test_engine_matches_direct_paged_loop(tiny_model):
    """Engine bookkeeping (chunked prefill, page growth, slot reuse) adds
    nothing to the math: its stream equals a hand-rolled single-slot
    paged_prefill + paged_decode_step loop."""
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.paged_kv import PagedKVCache, assign_pages, paged_decode_step, paged_prefill
    from modal_tpu.serving.pages import PageAllocator

    params, cfg = tiny_model
    prompt = np.random.default_rng(4).integers(0, cfg.vocab_size, size=7).tolist()
    gen = 20

    cache = PagedKVCache.create(cfg, SLOTS, PAGES, PAGE, PAGES_PER_SLOT)
    alloc = PageAllocator(PAGES, PAGE)
    pages = alloc.alloc(alloc.pages_for(len(prompt) + gen + 1))
    cache = assign_pages(cache, 0, 0, jnp.asarray(pages, jnp.int32))
    padded = jnp.zeros((16,), jnp.int32).at[: len(prompt)].set(jnp.asarray(prompt, jnp.int32))
    _l, tok, cache = paged_prefill(
        params, cfg, padded, jnp.int32(len(prompt)), cache, jnp.int32(0), jnp.int32(0)
    )
    reference = [int(tok)]
    cur = jnp.zeros((SLOTS,), jnp.int32).at[0].set(tok)
    active = jnp.zeros((SLOTS,), bool).at[0].set(True)
    for _ in range(gen - 1):
        _l, nxt, cache = paged_decode_step(params, cfg, cur, cache, active)
        reference.append(int(nxt[0]))
        cur = cur.at[0].set(nxt[0])

    eng = _engine(params, cfg).start()
    try:
        out = eng.submit(prompt, max_new_tokens=gen).result(timeout=120)
    finally:
        eng.stop()
    assert out == reference


# ---------------------------------------------------------------------------
# one decode step in flight (ISSUE 35): step n+1 is launched on the device's
# own tokens before step n is read
# ---------------------------------------------------------------------------


def _iterate(engine, drained: bool = False) -> None:
    """One iteration of the loop on the caller's thread (the engine is not
    started). `drained`: everything in flight is read before the next
    iteration, which is the loop that syncs every step."""
    engine._admit()
    engine._prefill_one()
    engine._decode_step()
    if drained:
        engine._drain()


def _serve_by_hand(engine, arrivals: dict, drained: bool, limit: int = 400) -> list:
    """arrivals: {iteration: [submit kwargs]}. Every request is submitted
    before its iteration, and the loop runs until all are done."""
    requests = []
    for it in range(limit):
        for kwargs in arrivals.get(it, ()):
            requests.append(engine.submit(**kwargs))
        if it > max(arrivals) and all(r.done for r in requests):
            break
        _iterate(engine, drained)
    if engine._flying is not None:  # the last stream ended by EOS: its overrun step is read as `_run` reads it
        _iterate(engine, drained)
    assert all(r.done and r.error is None for r in requests), "the loop did not finish every request"
    return requests


def _first_fresh_token(tokens: list, at_least: int):
    """Index of the first token from `at_least` on that none before it equals: as
    EOS it ends the stream exactly there. None if every one repeats an earlier."""
    return next((k for k in range(at_least, len(tokens)) if tokens[k] not in tokens[:k]), None)


@pytest.mark.parametrize("pool", ["roomy", "tight"])
@pytest.mark.parametrize("ends", ["by-count", "by-eos"])
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_streams_equal_a_loop_that_drains_after_every_step(tiny_model, mode, ends, pool):
    """Requests join and leave mid-flight, by count and by EOS, with and
    without preemption: the loop with a step in flight serves the drained
    loop's tokens to the last id."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(35)
    sampling = (lambda i: dict(temperature=0.6 + 0.1 * i, top_k=40, seed=500 + i)) if mode == "sampled" else (lambda i: {})
    shapes = [(9, 30), (40, 14), (14, 1), (33, 22), (5, 40), (21, 9), (70, 17)]
    if pool == "tight":
        shapes = [(10, 110), (12, 110), (9, 110), (11, 100), (30, 40)]  # the first three alone need the pool's 24 pages
    plan = [dict(prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(), max_new_tokens=m, **sampling(i)) for i, (n, m) in enumerate(shapes)]
    if ends == "by-eos":
        # what each stream would be left alone says where an EOS can end it
        alone = _serve_by_hand(_engine(params, cfg), {0: plan[:4], 1: plan[4:]}, drained=True)
        ended = 0
        for kwargs, req in list(zip(plan, alone))[slice(3, 4) if pool == "tight" else slice(None)]:  # tight: all but one run to their ends
            k = _first_fresh_token(req.tokens, len(req.tokens) // 2)
            if k is not None and k + 1 < len(req.tokens):
                kwargs["eos_token_id"], ended = req.tokens[k], ended + 1
        assert ended >= 1, "no stream has a token to end on"
    arrivals = {0: plan[:2], 3: plan[2:3], 4: plan[3:5], 11: plan[5:]}
    engines = {drained: _engine(params, cfg) for drained in (True, False)}
    served = {drained: _serve_by_hand(engine, arrivals, drained) for drained, engine in engines.items()}
    for want, got in zip(served[True], served[False]):
        assert got.tokens == want.tokens
        assert len(got.tokens) == got.max_new_tokens or got.tokens[-1] == got.eos_token_id
    piped, synced = engines[False].stats(), engines[True].stats()
    assert piped["tokens_generated"] == synced["tokens_generated"] == sum(len(r.tokens) for r in served[True])
    assert piped["loop"]["steps_overlapped"] > 0.8 * piped["steps"] and synced["loop"]["steps_overlapped"] == 0
    if ends == "by-eos":  # each EOS was seen a step late, and that step's token went nowhere
        assert piped["loop"]["tokens_discarded"] > 0 and synced["loop"]["tokens_discarded"] == 0
    else:
        assert piped["loop"]["tokens_discarded"] == 0
    if pool == "tight":  # a preemption reads what is in flight first
        assert piped["preemptions"] > 0 and piped["loop"]["drains"] >= piped["preemptions"]
    for engine in engines.values():
        assert engine._flying is None and engine._first is None
        engine.stop()
        assert engine.pages.free_pages == PAGES - 1


def test_an_eos_is_seen_one_step_late_and_its_overrun_token_reaches_no_stream(tiny_model):
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(36)
    prompt_a, prompt_b = rng.integers(0, cfg.vocab_size, size=11).tolist(), rng.integers(0, cfg.vocab_size, size=7).tolist()
    solo = _engine(params, cfg, max_slots=1)
    free_a, solo_b = (r.tokens for r in _serve_by_hand(solo, {0: [dict(prompt=prompt_a, max_new_tokens=20)], 1: [dict(prompt=prompt_b, max_new_tokens=8)]}, True))
    solo.stop()
    k = _first_fresh_token(free_a, 5)
    assert k is not None and k < 19
    engine = _engine(params, cfg, max_slots=1)  # b can only get a's slot index
    a = engine.submit(prompt_a, max_new_tokens=20, eos_token_id=free_a[k])
    b = engine.submit(prompt_b, max_new_tokens=8)
    while not a.done:
        _iterate(engine)
    slot_a = engine._flying.slots[0][1]
    assert a.tokens == free_a[: k + 1]  # nothing past the EOS
    # a's next step was launched before its EOS was read: it is in flight for a slot that is gone
    assert slot_a.request is a and engine.slots == [None] and engine.stats()["loop"]["tokens_discarded"] == 0
    _iterate(engine)  # b takes index 0; the overrun step is read and its token dropped
    assert engine.slots[0] is not None and engine.slots[0].request is b
    assert engine.stats()["loop"]["tokens_discarded"] == 1 and len(a.tokens) == k + 1
    while not b.done:
        _iterate(engine)
    assert b.tokens == solo_b  # the admitted request never saw a's overrun token
    stats = engine.stats()
    assert stats["loop"]["tokens_discarded"] == 1 and stats["tokens_generated"] == k + 1 + 8
    engine.stop()
    assert engine.pages.free_pages == PAGES - 1


def test_a_request_that_ends_by_count_is_in_no_further_step(tiny_model):
    params, cfg = tiny_model
    engine = _engine(params, cfg)
    short = engine.submit(list(range(3, 12)), max_new_tokens=3)
    long = engine.submit(list(range(40, 47)), max_new_tokens=6)
    in_steps = []
    while not (short.done and long.done):
        _iterate(engine)
        in_steps.append(sorted(s.request.id for _i, s in engine._flying.slots) if engine._flying is not None else [])
    # iteration 1 prefills `short` (its first token and step 1), iteration 2 `long`: short's third
    # token is step 2's, so it is in steps 1 and 2 and in no other, though its last token is unread
    # when step 3 is put together
    assert [short.id in ids for ids in in_steps] == [True, True] + [False] * (len(in_steps) - 2)
    assert [long.id in ids for ids in in_steps] == [False] + [True] * 5 + [False]
    assert len(short.tokens) == 3 and len(long.tokens) == 6
    stats = engine.stats()
    assert stats["loop"]["tokens_discarded"] == 0 and stats["steps"] == 6 and stats["loop"]["drains"] == 0
    engine.stop()


@pytest.mark.parametrize("how", ["stop", "stop-before-start", "fail_all"])
def test_a_stop_and_a_failure_with_a_step_in_flight_finish_every_request_and_leak_no_page(tiny_model, how):
    params, cfg = tiny_model
    engine = _engine(params, cfg, prefix_cache=False)
    prompts = [list(range(1 + 7 * i, 12 + 9 * i)) for i in range(6)]  # six over four slots: two wait
    if how == "stop":
        engine.start()
        requests = [engine.submit(p, max_new_tokens=90) for p in prompts]
        assert requests[0].wait_new(3, timeout=120)[0], "nothing decoded"
        engine.stop()
    else:
        requests = [engine.submit(p, max_new_tokens=90) for p in prompts]
        for _ in range(5):
            _iterate(engine)
        assert engine._flying is not None and len(engine._flying.slots) >= 2
        engine.stop() if how == "stop-before-start" else engine._fail_all("a step failed")
    assert all(r.done and r.error for r in requests[:4]) and engine._flying is None and engine._first is None
    assert engine.slots == [None] * SLOTS and engine.pages.free_pages == PAGES - 1 and engine.pages.pages == [[]] * SLOTS
    if how == "fail_all":
        # the loop lives on: the two that waited are served, to the tokens they get alone
        while not all(r.done for r in requests):
            _iterate(engine)
        alone = _engine(params, cfg, prefix_cache=False)
        want = [r.tokens for r in _serve_by_hand(alone, {0: [dict(prompt=p, max_new_tokens=90) for p in prompts[4:]]}, True)]
        assert [r.tokens for r in requests[4:]] == want and engine.stats()["loop"]["tokens_discarded"] == 0
        engine.stop()
        alone.stop()
        assert engine.pages.free_pages == PAGES - 1


# ---------------------------------------------------------------------------
# SSE surface + chaos degrade
# ---------------------------------------------------------------------------


@pytest.fixture()
def sse_server(tiny_model):
    """The serving ASGI app behind the real AsgiHttpServer on a private
    loop thread (exactly how a container serves it)."""
    import asyncio

    from modal_tpu.runtime.asgi import AsgiHttpServer
    from modal_tpu.serving.api import serving_asgi_app

    params, cfg = tiny_model
    engine = _engine(params, cfg).start()
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    server = AsgiHttpServer(serving_asgi_app(engine))
    asyncio.run_coroutine_threadsafe(server.start(), loop).result(30)
    try:
        yield server.port, engine
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()


def _http(port: int, method: str, path: str, body: dict | None = None) -> tuple[bytes, list[float]]:
    """Blocking HTTP/1.1 exchange; returns (raw_response, per-chunk arrival
    times) so tests can see WHEN bytes landed."""
    payload = json.dumps(body).encode() if body is not None else b""
    s = socket.create_connection(("127.0.0.1", port), timeout=120)
    try:
        s.sendall(
            f"{method} {path} HTTP/1.1\r\nhost: t\r\ncontent-length: {len(payload)}\r\n\r\n".encode()
            + payload
        )
        chunks, stamps = [], []
        while True:
            data = s.recv(65536)
            if not data:
                break
            chunks.append(data)
            stamps.append(time.monotonic())
        return b"".join(chunks), stamps
    finally:
        s.close()


def _json_body(raw: bytes) -> dict:
    return json.loads(raw.split(b"\r\n\r\n", 1)[1])


def test_sse_streams_tokens_before_completion(sse_server):
    """The TTFT point of streaming: token events arrive while generation is
    still running, and the streamed sequence equals the buffered one."""
    port, _engine_ = sse_server
    raw, stamps = _http(
        port, "POST", "/v1/generate",
        {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 16, "stream": True},
    )
    text = raw.decode()
    assert text.count("event: token") == 16
    assert "event: done" in text
    # bytes arrived incrementally (first token strictly before the last chunk)
    assert len(stamps) > 1 and stamps[0] < stamps[-1]
    streamed = [
        json.loads(line[6:])["token"]
        for line in text.splitlines()
        if line.startswith("data: ") and '"token"' in line
    ]
    done = [json.loads(line[6:]) for line in text.splitlines() if line.startswith("data: ") and '"tokens"' in line]
    assert streamed == done[0]["tokens"]
    assert done[0]["ttft_s"] is not None


def test_chaos_stream_reset_degrades_to_buffered_exactly_once(sse_server, monkeypatch):
    """ISSUE 9 chaos case: the SSE stream is killed mid-flight; the client
    falls back to the buffered read and sees every token exactly once."""
    from modal_tpu.serving import api as serving_api

    port, engine = sse_server
    monkeypatch.setenv(serving_api.STREAM_RESET_ENV, "1")
    serving_api._reset_chaos_for_tests()
    try:
        raw, _ = _http(
            port, "POST", "/v1/generate",
            {"prompt": [9, 8, 7, 6], "max_new_tokens": 12, "stream": True, "request_id": "chaos-sse"},
        )
        text = raw.decode()
        assert "event: done" not in text, "stream should have been reset mid-flight"
        streamed = [
            json.loads(line[6:])["token"]
            for line in text.splitlines()
            if line.startswith("data: ") and '"token"' in line
        ]
        assert len(streamed) >= 1, "reset fired before the first token"
        # degrade: buffered fetch returns the COMPLETE stream
        raw2, _ = _http(port, "GET", "/v1/result/chaos-sse")
        body = _json_body(raw2)
        assert len(body["tokens"]) == 12
        # exactly-once: what the broken stream delivered is a strict prefix
        # of the buffer — nothing lost, nothing duplicated
        assert body["tokens"][: len(streamed)] == streamed
        # generation itself was never disturbed
        req = engine.get("chaos-sse")
        assert req is not None and req.error is None and req.done
    finally:
        serving_api._reset_chaos_for_tests()


def test_api_validation_and_stats(sse_server):
    port, _ = sse_server
    raw, _ = _http(port, "POST", "/v1/generate", {"prompt": "nope"})
    assert b"400" in raw.split(b"\r\n")[0]
    raw, _ = _http(port, "POST", "/v1/generate", {"prompt": [999999], "max_new_tokens": 2})
    assert b"400" in raw.split(b"\r\n")[0]
    raw, _ = _http(port, "GET", "/v1/result/ghost")
    assert b"404" in raw.split(b"\r\n")[0]
    raw, _ = _http(port, "GET", "/v1/stats")
    stats = _json_body(raw)
    assert stats["kv_pages_total"] == PAGES - 1
    raw, _ = _http(port, "GET", "/healthz")
    assert _json_body(raw)["ok"] is True
    # byte-level text prompts round-trip (vocab 512 >= 256)
    raw, _ = _http(port, "POST", "/v1/generate", {"text": "hi", "max_new_tokens": 3})
    assert len(_json_body(raw)["tokens"]) == 3


# ---------------------------------------------------------------------------
# SLO autoscaling (scheduler)
# ---------------------------------------------------------------------------


def _serving_push_json(ttft_p95: float, tokens_per_s: float, queue: float = 0.0) -> str:
    return json.dumps(
        {
            "modal_tpu_serving_ttft_p95_seconds": {"kind": "gauge", "series": {"": ttft_p95}},
            "modal_tpu_serving_tokens_per_second": {"kind": "gauge", "series": {"": tokens_per_s}},
            "modal_tpu_serving_queue_depth": {"kind": "gauge", "series": {"": queue}},
        }
    )


def test_slo_autoscaler_desired_replicas(tmp_path):
    """Scheduler unit: desired replica count follows pushed serving
    telemetry against the declared SLO targets — up on TTFT violation or
    queueing, down on deep idle, one step per cooldown window."""
    from modal_tpu.proto import api_pb2
    from modal_tpu.server.scheduler import Scheduler
    from modal_tpu.server.state import FunctionState, ServerState, TaskState_

    state = ServerState(str(tmp_path / "state"))
    definition = api_pb2.Function(function_name="svc", webhook_type=api_pb2.WEB_ENDPOINT_TYPE_ASGI_APP)
    definition.autoscaler_settings.min_containers = 1
    definition.autoscaler_settings.max_containers = 8
    definition.autoscaler_settings.target_ttft_ms = 500.0
    definition.autoscaler_settings.target_tokens_per_replica = 1000.0
    fn = FunctionState(function_id="fu-slo", app_id="ap-1", tag="svc", definition=definition)
    state.functions["fu-slo"] = fn
    sched = Scheduler(state)

    def _task(tid: str, push: str) -> str:
        state.tasks[tid] = TaskState_(task_id=tid, function_id="fu-slo", app_id="ap-1")
        state.tasks[tid].telemetry_prev_json = push
        return tid

    # TTFT blown on one replica -> scale up one step
    live = [_task("ta-1", _serving_push_json(ttft_p95=2.0, tokens_per_s=900))]
    assert sched._slo_desired(fn, live) == 2
    assert fn.slo_last_scale_at > 0
    # cooldown: an immediate second evaluation holds at current size
    assert sched._slo_desired(fn, live) == 1
    fn.slo_last_scale_at = 0.0
    # queueing with healthy TTFT also scales up
    live = [_task("ta-2", _serving_push_json(ttft_p95=0.1, tokens_per_s=900, queue=3))]
    assert sched._slo_desired(fn, live) == 2
    fn.slo_last_scale_at = 0.0
    # deep idle (TTFT way under, throughput way under capacity) scales down
    live = [
        _task("ta-3", _serving_push_json(ttft_p95=0.05, tokens_per_s=100)),
        _task("ta-4", _serving_push_json(ttft_p95=0.04, tokens_per_s=80)),
    ]
    assert sched._slo_desired(fn, live) == 1
    fn.slo_last_scale_at = 0.0
    # healthy middle ground: hold
    live = [_task("ta-5", _serving_push_json(ttft_p95=0.3, tokens_per_s=800))]
    assert sched._slo_desired(fn, live) == 1
    # STALE violation: a past TTFT spike with zero current traffic must NOT
    # keep ratcheting the fleet up (the pushed p95 is last-window data)
    live = [_task("ta-6", _serving_push_json(ttft_p95=5.0, tokens_per_s=0.0, queue=0))]
    assert sched._slo_desired(fn, live) == 1
    # and a clamped no-op (already at the min floor, deep idle) must not
    # burn the cooldown window
    assert fn.slo_last_scale_at == 0.0
    # min_containers floor holds even with no telemetry yet
    assert sched._slo_desired(fn, []) == 1
    # no SLO targets declared -> backlog autoscaling (None)
    definition.autoscaler_settings.target_ttft_ms = 0.0
    definition.autoscaler_settings.target_tokens_per_replica = 0.0
    assert sched._slo_desired(fn, live) is None


def test_serving_families_ride_the_heartbeat_whitelist():
    """Observability parity: the SLO signals must actually be pushed (and
    the families must exist in the catalog so merges have a target)."""
    from modal_tpu.observability import METRIC_CATALOG
    from modal_tpu.observability.device_telemetry import PUSH_FAMILIES

    for family in (
        "modal_tpu_serving_ttft_seconds",
        "modal_tpu_serving_ttft_p95_seconds",
        "modal_tpu_serving_tokens_per_second",
        "modal_tpu_serving_queue_depth",
        "modal_tpu_serving_batch_occupancy",
        "modal_tpu_kv_pages_allocated",
        "modal_tpu_kv_pages_free",
    ):
        assert family in METRIC_CATALOG, family
        assert family in PUSH_FAMILIES, family


# ---------------------------------------------------------------------------
# ISSUE 12: batched sampling, Pallas paged attention, shared-prefix reuse,
# speculative decoding (docs/SERVING.md)
# ---------------------------------------------------------------------------


def test_page_allocator_refcounts_share_and_underflow():
    """CoW substrate: share() adds holders, free() drops one; the page
    returns only at zero, and over-freeing (underflow) fails loudly — the
    refcount IS the double-free detector."""
    from modal_tpu.serving.pages import PageAllocator

    alloc = PageAllocator(num_pages=9, page_size=16)
    a = alloc.alloc(2)
    alloc.share(a)  # second holder (e.g. a prefix-cache entry)
    assert alloc.refcount(a[0]) == 2 and alloc.shared(a[0])
    alloc.free(a)  # first holder lets go: still allocated
    assert alloc.free_pages == 6 and alloc.refcount(a[0]) == 1
    assert not alloc.shared(a[0])
    alloc.free(a)  # last holder: pages actually return
    assert alloc.free_pages == 8
    with pytest.raises(ValueError, match="double free"):
        alloc.free([a[0]])  # underflow detected
    with pytest.raises(ValueError, match="share of unallocated"):
        alloc.share([a[0]])


def test_pallas_paged_attention_interpret_parity(tiny_model):
    """ISSUE 12 acceptance: the Pallas page-streaming kernel (interpret mode
    on CPU CI) matches the dense KVCache path through chunked prefill +
    multiple decode steps — same numerics bar as the gather path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.llama import KVCache
    from modal_tpu.models.paged_kv import PagedKVCache, assign_pages, paged_decode_step, paged_prefill
    from modal_tpu.serving.pages import PageAllocator
    from modal_tpu.models.sampling import decode_step, prefill

    params, cfg = tiny_model
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 10), 0, cfg.vocab_size).astype(jnp.int32)

    dense = KVCache.create(cfg, 1, PAGES_PER_SLOT * PAGE)
    dlogits, dense = prefill(params, cfg, prompt, dense)

    cache = PagedKVCache.create(cfg, SLOTS, PAGES, PAGE, PAGES_PER_SLOT)
    alloc = PageAllocator(PAGES, PAGE)
    cache = assign_pages(cache, 0, 0, jnp.asarray(alloc.alloc(3), jnp.int32))
    padded1 = jnp.zeros((16,), jnp.int32).at[:6].set(prompt[0, :6])
    _l, _t, cache = paged_prefill(params, cfg, padded1, jnp.int32(6), cache, jnp.int32(0), jnp.int32(0))
    padded2 = jnp.zeros((16,), jnp.int32).at[:4].set(prompt[0, 6:])
    plogits, _t, cache = paged_prefill(params, cfg, padded2, jnp.int32(4), cache, jnp.int32(0), jnp.int32(6))
    np.testing.assert_allclose(np.asarray(plogits), np.asarray(dlogits[0]), atol=3e-2, rtol=0)

    # several decode steps through the KERNEL, pinned per-step to dense —
    # crosses a page boundary (positions 10..15 then 16: page 0 → page 1)
    tok = int(np.asarray(dlogits[0]).argmax())
    for step in range(8):
        dlog, dense = decode_step(params, cfg, jnp.asarray([[tok]], jnp.int32), dense)
        toks = jnp.zeros((SLOTS,), jnp.int32).at[0].set(tok)
        active = jnp.zeros((SLOTS,), bool).at[0].set(True)
        plog, _n, cache = paged_decode_step(params, cfg, toks, cache, active, "kernel_interpret")
        np.testing.assert_allclose(
            np.asarray(plog[0]), np.asarray(dlog[0]), atol=3e-2, rtol=0,
            err_msg=f"kernel diverged from dense at decode step {step}",
        )
        tok = int(np.asarray(dlog[0]).argmax())


def test_submit_sampling_validation(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg)  # not started: submit validates before queueing
    for bad in (float("nan"), -0.1, float("inf")):
        with pytest.raises(ValueError, match="temperature"):
            eng.submit([1, 2], max_new_tokens=2, temperature=bad)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1, 2], max_new_tokens=2, top_k=-1)
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="top_p"):
            eng.submit([1, 2], max_new_tokens=2, top_p=bad)


def test_sampled_streams_deterministic_under_joins(tiny_model):
    """THE ISSUE 12 sampling pin: a sampled stream is bit-reproducible for a
    fixed seed regardless of mid-decode joiners — per-slot keys are
    fold_in(PRNGKey(seed), token_index), never a function of the batch."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(12)
    pa = rng.integers(0, cfg.vocab_size, size=9).tolist()
    pb = rng.integers(0, cfg.vocab_size, size=13).tolist()
    eng = _engine(params, cfg).start()
    try:
        solo = eng.submit(pa, max_new_tokens=24, temperature=0.8, top_k=50, seed=42).result(timeout=120)
        greedy = eng.submit(pa, max_new_tokens=24).result(timeout=120)
        assert solo != greedy, "temperature 0.8 should diverge from greedy on a random-init model"
        # joined: a companion with a different seed/params lands mid-decode
        req_a = eng.submit(pa, max_new_tokens=24, temperature=0.8, top_k=50, seed=42)
        first, _ = req_a.wait_new(0, timeout=60)
        assert first, "no first token"
        req_b = eng.submit(pb, max_new_tokens=10, temperature=1.2, top_p=0.9, seed=7)
        joined = req_a.result(timeout=120)
        out_b = req_b.result(timeout=120)
        assert joined == solo, "mid-decode joiner perturbed a sampled stream"
        # and the joiner itself reproduces its own solo run
        solo_b = eng.submit(pb, max_new_tokens=10, temperature=1.2, top_p=0.9, seed=7).result(timeout=120)
        assert out_b == solo_b
    finally:
        eng.stop()


def test_sampled_streams_deterministic_under_preemption(tiny_model):
    """Preemption/re-prefill cannot perturb sampled streams: the re-admitted
    request re-derives the same fold_in(seed, index) keys for its remaining
    positions, so the continuation is the same tokens."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).tolist() for _ in range(4)]
    eng = _engine(params, cfg).start()
    try:
        solos = [
            eng.submit(p, max_new_tokens=100, temperature=0.7, seed=100 + i).result(timeout=240)
            for i, p in enumerate(prompts)
        ]
        reqs = [
            eng.submit(p, max_new_tokens=100, temperature=0.7, seed=100 + i)
            for i, p in enumerate(prompts)
        ]
        outs = [r.result(timeout=240) for r in reqs]
    finally:
        eng.stop()
    assert eng.preemptions > 0, "pool was never exhausted — test geometry wrong"
    for solo, out in zip(solos, outs):
        assert out == solo, "preemption/re-prefill changed a sampled stream"


def test_prefix_cache_share_cow_and_eviction(tiny_model):
    """Shared-prefix reuse: the second request with the same system prompt
    hits the content-keyed cache (prefilling only its suffix), CoW fires
    when a shared partial page is written, and completed flows leave zero
    leaked pages once the engine's cache is cleared."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(14)
    sysprompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    eng = _engine(params, cfg).start()
    try:
        a = eng.submit(sysprompt + [5, 6], max_new_tokens=12).result(timeout=120)
        st1 = eng.stats()
        assert st1["prefix_cache_entries"] == 1 and st1["prefix_cache_misses"] >= 1
        # the inserter itself decodes into the page its prompt was published
        # from → that page is refcount-shared → its write must have CoW'd
        assert st1["kv_pages_cow_copies"] >= 1
        b = eng.submit(sysprompt + [5, 6], max_new_tokens=12).result(timeout=120)
        st2 = eng.stats()
        assert st2["prefix_cache_hits"] >= 1, st2
        assert b == a, "follower reading shared prefix KV diverged from the inserter"
        # a different suffix still reuses the shared pages
        c = eng.submit(sysprompt + [9, 9, 9], max_new_tokens=8)
        assert len(c.result(timeout=120)) == 8
        assert eng.stats()["prefix_cache_hits"] >= 2
    finally:
        eng.stop()
    # stop() clears the cache: every page accounted for, no refcount leaks
    assert eng.pages.free_pages == PAGES - 1


def test_prefix_hit_with_cow_streams_the_tokens_of_a_cold_prefill(tiny_model):
    """A follower whose prompt hits the prefix cache mid-page, past the first
    KV block of its row (its one prefill chunk starts at position 524 of a
    640-position row, in a page it must copy before writing), streams what
    the same prompt streams on an engine without the cache."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(27)
    sysprompt = rng.integers(0, cfg.vocab_size, size=523).tolist()
    follower = sysprompt + [5, 9, 9, 9, 9]
    geometry = dict(pages_per_slot=40, num_pages=100, prefill_chunk=128)
    eng = _engine(params, cfg, prefix_cache=True, **geometry).start()
    try:
        eng.submit(sysprompt + [5, 6], max_new_tokens=4).result(timeout=120)
        before = eng.stats()
        warm = eng.submit(follower, max_new_tokens=12).result(timeout=120)
        after = eng.stats()
    finally:
        eng.stop()
    assert after["prefix_cache_hits"] == before["prefix_cache_hits"] + 1
    assert after["kv_pages_cow_copies"] > before["kv_pages_cow_copies"]
    # the hit covered 524 tokens: one chunk of 4 was computed, its loop walked both blocks of 512
    assert after["prompt_tokens_prefilled"] - before["prompt_tokens_prefilled"] == 4
    assert after["prefill_kv_attended"] - before["prefill_kv_attended"] == 1024
    cold_eng = _engine(params, cfg, prefix_cache=False, **geometry).start()
    try:
        cold = cold_eng.submit(follower, max_new_tokens=12).result(timeout=120)
        assert cold_eng.stats()["prefix_cache_hits"] == 0
    finally:
        cold_eng.stop()
    assert warm == cold and len(warm) == 12


def test_stats_count_what_the_prefill_loop_attends_against_the_span(tiny_model):
    """`/v1/stats` `prefill_kv_attended` / `prefill_kv_span`: whole KV blocks
    the chunks' attention walked (from the live prefix, on the host) and
    `max_context` a chunk. A prompt of 530 tokens in chunks of 512 and 18 on
    rows of 1,024 positions walks one block of 512, then two."""
    from modal_tpu.models.paged_kv import PREFILL_KV_BLOCK, prefill_kv_attended

    assert PREFILL_KV_BLOCK == 512
    assert [prefill_kv_attended(n, 64, PAGE) for n in (1, 512, 513, 1024)] == [512, 512, 1024, 1024]
    assert prefill_kv_attended(100, PAGES_PER_SLOT, PAGE) == PAGES_PER_SLOT * PAGE  # a block is at most the row
    params, cfg = tiny_model
    eng = _engine(params, cfg, pages_per_slot=64, num_pages=80, prefill_chunk=512, prefix_cache=False).start()
    try:
        before = eng.stats()
        assert before["prefill_kv_attended"] == 0 and before["prefill_kv_span"] == 0
        eng.submit([i % 500 + 1 for i in range(530)], max_new_tokens=2).result(timeout=120)
        after = eng.stats()
    finally:
        eng.stop()
    assert after["prefill_chunks"] == 2
    assert after["prefill_kv_attended"] == 512 + 1024
    assert after["prefill_kv_span"] == 2 * 1024 == 2 * eng.max_context


def test_prefix_cache_cow_refcounts_under_preemption(tiny_model):
    """ISSUE 12 CoW-correctness pin: requests sharing prefix pages survive
    pool-pressure preemption — a shared page freed by one holder stays valid
    for the others, refcounts never underflow (any underflow raises inside
    the engine loop and would fail every stream), and streams stay exact."""
    import numpy as np

    params, cfg = tiny_model
    rng = np.random.default_rng(15)
    sysprompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    prompts = [sysprompt + [i] for i in range(4)]
    # 16-usable-page pool: 4 concurrent requests each growing toward
    # pages_for(41+85+1) = 8 (minus 3 shared prefix pages each) must
    # overflow it mid-decode → eviction, then preemption
    eng = _engine(params, cfg, num_pages=17).start()
    try:
        solos = [eng.submit(p, max_new_tokens=85).result(timeout=240) for p in prompts]
        reqs = [eng.submit(p, max_new_tokens=85) for p in prompts]
        outs = [r.result(timeout=240) for r in reqs]
    finally:
        eng.stop()
    assert eng.preemptions > 0, "pool was never exhausted — test geometry wrong"
    for solo, out in zip(solos, outs):
        assert out == solo, "preemption over shared pages corrupted a stream"
    # nothing leaked and nothing double-freed (an underflow would have
    # raised in the loop and error-finished every request above)
    assert eng.pages.free_pages == 16
    # the allocator still detects over-frees after all this churn
    with pytest.raises(ValueError, match="double free"):
        eng.pages.allocator.free([1])


def test_speculative_decoding_exact_vs_nonspec():
    """ISSUE 12 acceptance: speculative decoding is token-identical to the
    non-speculative engine at temperature 0 — and with sampling too, since
    emitted tokens are always the TARGET's (seed, index)-keyed chain; the
    draft only controls how many land per round.

    Pinned on an fp32 config: the multi-token verify executable and the
    single-token decode executable agree to ~1e-6 in fp32, but differ by
    ~2e-3 under bf16 KV — enough to flip argmax on the near-ties a
    random-init model produces constantly (same caveat the dense-vs-paged
    pin documents; a trained bf16 model's top-2 gaps dwarf this noise)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny", dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(16)
    prompt = rng.integers(0, cfg.vocab_size, size=9).tolist()

    eng = _engine(params, cfg, prefix_cache=False).start()
    try:
        base_greedy = eng.submit(prompt, max_new_tokens=24).result(timeout=240)
        base_sampled = eng.submit(prompt, max_new_tokens=24, temperature=0.9, seed=3).result(timeout=240)
    finally:
        eng.stop()

    # self-draft: acceptance ~1, so the exactness pin covers the all-accept
    # path AND the per-round bookkeeping; a smaller real draft only lowers
    # the accept ratio, never changes emitted tokens
    spec = _engine(params, cfg, draft=(params, cfg), spec_k=3).start()
    try:
        spec_greedy = spec.submit(prompt, max_new_tokens=24).result(timeout=240)
        spec_sampled = spec.submit(prompt, max_new_tokens=24, temperature=0.9, seed=3).result(timeout=240)
        st = spec.stats()
    finally:
        spec.stop()
    assert spec_greedy == base_greedy, "speculative greedy chain diverged"
    assert spec_sampled == base_sampled, "speculative sampled chain diverged"
    assert st["spec_rounds"] > 0 and st["spec_accept_ratio"] is not None
    assert st["spec_accept_ratio"] > 0.8, f"self-draft should accept nearly all: {st}"
    # fewer engine steps than tokens: speculation actually batched them
    assert st["steps"] < st["tokens_generated"]
    assert spec.pages.free_pages == PAGES - 1
    assert spec.draft_pages.free_pages == PAGES - 1

    # context-boundary pin: spec mode reserves spec_k slack (a verify round
    # on the final token still writes k positions past it; without the
    # reservation the page table would clamp an out-of-range index onto a
    # live entry and corrupt that slot's KV)
    max_ctx = PAGES_PER_SLOT * PAGE
    spec2 = _engine(params, cfg, draft=(params, cfg), spec_k=3).start()
    try:
        with pytest.raises(ValueError, match="context limit"):
            spec2.submit([1] * 10, max_new_tokens=max_ctx - 10)  # fits non-spec, not spec
        at_limit = spec2.submit([1] * 10, max_new_tokens=max_ctx - 3 - 10)
        assert len(at_limit.result(timeout=240)) == max_ctx - 3 - 10
    finally:
        spec2.stop()


def test_api_sampling_params_end_to_end(sse_server):
    """Satellite: POST /v1/generate accepts temperature/top_k/top_p/seed
    (validated), echoes them in the SSE start event, and a fixed seed
    reproduces the same tokens over HTTP."""
    port, _engine_ = sse_server
    # validation 400s
    for bad_body in (
        {"prompt": [1, 2], "temperature": float("nan")},
        {"prompt": [1, 2], "temperature": -1.0},
        {"prompt": [1, 2], "top_k": -2},
        {"prompt": [1, 2], "top_p": 0.0},
        {"prompt": [1, 2], "top_p": 1.5},
        {"prompt": [1, 2], "seed": "abc"},
    ):
        raw, _ = _http(port, "POST", "/v1/generate", bad_body)
        assert b"400" in raw.split(b"\r\n")[0], (bad_body, raw[:200])
    # SSE start event echoes the effective sampling params
    raw, _ = _http(
        port, "POST", "/v1/generate",
        {"prompt": [3, 1, 4], "max_new_tokens": 6, "stream": True,
         "temperature": 0.8, "top_k": 40, "top_p": 0.95, "seed": 11},
    )
    text = raw.decode()
    start_line = next(
        line for line in text.splitlines() if line.startswith("data: ") and '"request_id"' in line
    )
    start = json.loads(start_line[6:])
    assert start["temperature"] == 0.8 and start["top_k"] == 40
    assert start["top_p"] == 0.95 and start["seed"] == 11
    # seed-reproducible over HTTP (non-stream)
    body = {"prompt": [3, 1, 4], "max_new_tokens": 8, "temperature": 0.9,
            "top_k": 25, "top_p": 0.8, "seed": 5}
    out1 = _json_body(_http(port, "POST", "/v1/generate", body)[0])
    out2 = _json_body(_http(port, "POST", "/v1/generate", body)[0])
    assert out1["tokens"] == out2["tokens"]
    # non-stream echo carries the same effective params as the start event
    assert out1["temperature"] == 0.9 and out1["seed"] == 5
    assert out1["top_k"] == 25 and out1["top_p"] == 0.8


def test_serving_depth_observability_parity():
    """New ISSUE 12 families exist in the catalog, ride the heartbeat push
    whitelist (prefix-hit + accept-ratio per replica in `modal_tpu top`),
    and the spec_verify span is declared."""
    from modal_tpu.observability import METRIC_CATALOG
    from modal_tpu.observability.catalog import SPAN_CATALOG
    from modal_tpu.observability.device_telemetry import PUSH_FAMILIES

    for family in (
        "modal_tpu_serving_prefix_cache_hits_total",
        "modal_tpu_serving_prefix_cache_misses_total",
        "modal_tpu_kv_pages_cow_copies_total",
        "modal_tpu_serving_spec_accept_ratio",
        "modal_tpu_serving_sampled_tokens_total",
    ):
        assert family in METRIC_CATALOG, family
        assert family in PUSH_FAMILIES, family
    assert "serving.spec_verify" in SPAN_CATALOG


# ---------------------------------------------------------------------------
# e2e: the @app.cls serving service through the real stack (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_llm_service_cls_end_to_end(supervisor):
    """llm_service → @app.cls with @enter-built engine + @asgi_app method →
    real container → web URL → tokens. The cls web-endpoint path and the
    serving tier, one hop each."""
    import urllib.request

    import modal_tpu

    app = modal_tpu.App("serving-e2e-cls")
    Service = modal_tpu.serving.llm_service(
        app, model="tiny", max_slots=4, num_pages=41, page_size=16,
        name="TinyLLM", timeout=300,
    )
    with app.run():
        url = Service.get_web_url(timeout=120)
        body = json.dumps({"prompt": [1, 2, 3, 4, 5], "max_new_tokens": 8}).encode()
        req = urllib.request.Request(
            url + "/v1/generate", data=body, headers={"content-type": "application/json"}
        )
        out = json.loads(urllib.request.urlopen(req, timeout=240).read())
        assert len(out["tokens"]) == 8
        # globally-unique request ids (ISSUE 11): the auto-minted id carries
        # the replica's container task id, so a buffered-degrade refetch on
        # a DIFFERENT replica can never collide with a local request
        assert out["request_id"].startswith("gr-ta-"), out["request_id"]
        stats = json.loads(urllib.request.urlopen(url + "/v1/stats", timeout=30).read())
        assert stats["requests_completed"] >= 1
        # `modal_tpu top` renders live against the running serving app
        # (ISSUE 11 acceptance): the replica's pushed telemetry reaches the
        # supervisor over heartbeats, the sampler folds it into history, and
        # the dashboard shows the replica row + fleet TTFT
        from click.testing import CliRunner

        from modal_tpu.cli.entry_point import cli

        deadline = time.time() + 60
        frame = ""
        while time.time() < deadline:
            supervisor.state.timeseries.sample()  # don't wait the 10 s cadence
            result = CliRunner().invoke(
                cli, ["top", "--once", "--state-dir", supervisor.state_dir],
                catch_exceptions=False,
            )
            assert result.exit_code == 0, result.output
            frame = result.output
            if "ta-" in frame and "TTFT" in frame:
                break
            time.sleep(1.0)
        assert "ta-" in frame, f"no replica row in top frame:\n{frame}"


# ---------------------------------------------------------------------------
# `modal-tpu serve` hot reload (pre-existing contract, serving/reload.py)
# ---------------------------------------------------------------------------


def _script(version: str) -> str:
    return textwrap.dedent(
        f"""
        import modal_tpu

        app = modal_tpu.App("serve-e2e")

        @app.function(serialized=True, name="echo")
        def echo():
            return "{version}"
        """
    )


def test_serve_hot_reload(supervisor, tmp_path):
    import modal_tpu

    script = tmp_path / "served_app.py"
    script.write_text(_script("v1"))
    env = dict(os.environ)
    env.update(
        {
            "MODAL_TPU_SERVER_URL": f"grpc://127.0.0.1:{supervisor.port}",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        }
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "modal_tpu.cli", "serve", f"{script}::app"],
        env=env,
        # DEVNULL: an unread PIPE would deadlock the child once its deploy/
        # watcher chatter exceeds the OS pipe buffer
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )

    def _remote_value(timeout: float) -> str:
        deadline = time.monotonic() + timeout
        last_exc = None
        while time.monotonic() < deadline:
            try:
                fn = modal_tpu.Function.from_name("serve-e2e", "echo")
                fn.hydrate()
                return fn.remote()
            except Exception as exc:  # noqa: BLE001 — deploy may not have landed
                last_exc = exc
                time.sleep(0.5)
        raise AssertionError(f"deployed function never answered: {last_exc}")

    try:
        assert _remote_value(60) == "v1"
        # edit the source; the watcher polls mtimes at 1 Hz
        time.sleep(1.2)  # ensure a distinct mtime on coarse filesystems
        script.write_text(_script("v2"))
        deadline = time.monotonic() + 60
        value = "v1"
        while time.monotonic() < deadline and value != "v2":
            value = _remote_value(30)
            if value != "v2":
                time.sleep(1)
        assert value == "v2", "redeploy after file change never took effect"
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
