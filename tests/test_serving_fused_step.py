"""A prefill chunk inside the decode step (`paged_decode_step(chunk=...)`):
the program the serving engine launches in an iteration that has a chunk,
against the two it replaces, `paged_prefill` then `paged_decode_step`, on
the same state, at `tiny` sizes in float32. Each layer kind the step
dispatches on: the dense block; a window pool beside the full one, with
sinks, and routed experts (`tiny-mimo`); the gate on attention's output
and a shared expert (`tiny-laguna`); the latent pool under a grouped
router (`tiny-axk1`); gated convolutions with a state a slot (`tiny-lfm2`),
where a half-prefilled slot's state must come out bit for bit; and a chunk
in a step where no slot decodes. Then the served engine through the
benchmark's warm-up mixes: `paged_prefill` is never dispatched, and the
step programs it readies are the decode step and one a chunk bucket."""

import dataclasses

import numpy as np
import pytest

PAGE, PAGES_PER_SLOT, SLOTS, BUCKET = 4, 16, 3, 16
# float32 both ways, the same operations over more rows at once: sums that
# differ by a rounding; a row routed to another slot's page, a mask off by
# one or a state carried from the wrong slot reads 1e-2 and more
ATOL = 1e-5

# (preset, decoding rows): the chunk fills slot 2 while slot 0 decodes and slot 1 holds half a prompt
CASES = {
    "dense": ("tiny", True),
    "window-sink-experts": ("tiny-mimo", True),
    "head-gate-shared-expert": ("tiny-laguna", True),
    "latent-grouped-experts": ("tiny-axk1", True),
    "conv-state": ("tiny-lfm2", True),
    "no-decoding-row": ("tiny", False),
}
# (chunk's tokens, tokens of its prompt already in the slot): a prompt's first chunk, padded, and a later one
CHUNKS = {"first-chunk": (13, 0), "later-chunk": (11, 16)}


def program(preset):
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config(preset)
    params = init_params(cfg, jax.random.PRNGKey(3))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params), dataclasses.replace(cfg, dtype=jnp.float32)


def tokens_of(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, size=n)]


def prefill(params, cfg, cache, slot, tokens, start, stop):
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    for at in range(start, stop, BUCKET):
        chunk = tokens[at : min(at + BUCKET, stop)]
        padded = np.zeros((BUCKET,), np.int32)
        padded[: len(chunk)] = chunk
        _l, _t, cache = pk.paged_prefill(params, cfg, jnp.asarray(padded), jnp.int32(len(chunk)), cache, jnp.int32(slot), jnp.int32(at))
    return cache


def state_before(params, cfg, decoding, done):
    """Slot 0: 20 tokens prefilled and two decoded; slot 1: 16 of 30
    prefilled; slot 2: `done` of its prompt. Every slot's rows whole from the
    start, in both pools. Returns (cache, the step's tokens and active,
    slot 2's prompt)."""
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    window = 1 + SLOTS * PAGES_PER_SLOT if cfg.has_window else None
    cache = pk.PagedKVCache.create(cfg, SLOTS, 1 + SLOTS * PAGES_PER_SLOT, PAGE, PAGES_PER_SLOT, window)
    rows = [(s, i, 1 + s * PAGES_PER_SLOT + i) for s in range(SLOTS) for i in range(PAGES_PER_SLOT)]
    cache = pk.assign_entries(cache, jnp.asarray(pk.pack_entries(SLOTS, len(rows), *[rows] * (2 if window else 1))))
    a, b, c = tokens_of(1, 23), tokens_of(2, 30), tokens_of(3, 27)
    cache = prefill(params, cfg, cache, 0, a, 0, 20)
    for pos in (20, 21):
        _l, _t, cache = pk.paged_decode_step(params, cfg, jnp.asarray([a[pos], 0, 0], jnp.int32), cache, jnp.asarray([True, False, False]))
    cache = prefill(params, cfg, cache, 1, b, 0, 16)
    cache = prefill(params, cfg, cache, 2, c, 0, done)
    return cache, jnp.asarray([a[22], 0, 0], jnp.int32), jnp.asarray([decoding, False, False]), c


def copy(cache):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, cache)


def slot_pages(pool, slot):
    """A slot's pages of a pool [layers, pages, ...], in its row's order."""
    first = 1 + slot * PAGES_PER_SLOT
    return np.asarray(pool[:, first : first + PAGES_PER_SLOT])


@pytest.mark.parametrize("chunk_at", sorted(CHUNKS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunk_inside_the_step_equals_the_chunk_then_the_step(case, chunk_at):
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    preset, decoding = CASES[case]
    length, done = CHUNKS[chunk_at]
    params, cfg = program(preset)
    base, tokens, active, prompt = state_before(params, cfg, decoding, done)
    padded = np.zeros((BUCKET,), np.int32)
    padded[:length] = prompt[done : done + length]
    chunk = (jnp.asarray(padded), jnp.int32(length), jnp.int32(2), jnp.int32(done))

    # the parent's two programs: the chunk, then the step
    want_last, want_tok, apart = pk.paged_prefill(params, cfg, *chunk[:2], copy(base), *chunk[2:])
    want_logits, want_next, apart = pk.paged_decode_step(params, cfg, tokens, apart, active)
    # one program
    got_logits, got_next, fused, got_last, got_tok = pk.paged_decode_step(params, cfg, tokens, copy(base), active, chunk=chunk)

    assert got_logits.shape == want_logits.shape and got_next.shape == (SLOTS,)
    if decoding:
        np.testing.assert_allclose(np.asarray(got_logits[0]), np.asarray(want_logits[0]), atol=ATOL, rtol=0)
        assert int(got_next[0]) == int(want_next[0])
    assert np.abs(np.asarray(want_last)).max() > 0.1
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last), atol=ATOL, rtol=0)
    assert int(got_tok) == int(want_tok)
    assert np.asarray(fused.seq_lens).tolist() == np.asarray(apart.seq_lens).tolist() == [22 + decoding, 16, done + length]
    # what every pool holds after: the same rows, and the untouched slot's bit for bit
    for k_fused, k_apart, k_base in zip(*(jax.tree_util.tree_leaves((c.k_pages, c.v_pages)) for c in (fused, apart, base))):
        np.testing.assert_allclose(np.asarray(k_fused[:, 1:]), np.asarray(k_apart[:, 1:]), atol=ATOL, rtol=0)
        assert np.array_equal(slot_pages(k_fused, 1), slot_pages(k_base, 1))
    if cfg.has_conv:
        for st_fused, st_apart, st_base in zip(fused.conv_state, apart.conv_state, base.conv_state):
            if st_base is None:
                continue
            # slot 1 is half prefilled and not in the step: its state, bit for bit
            assert np.array_equal(np.asarray(st_fused[:, 1]), np.asarray(st_base[:, 1]))
            np.testing.assert_allclose(np.asarray(st_fused), np.asarray(st_apart), atol=ATOL, rtol=0)
            assert not np.array_equal(np.asarray(st_fused[:, 2]), np.asarray(st_base[:, 2]))  # the chunk's moved
    if cfg.has_experts:
        # the same (token, expert) pairs; an expert two calls touched is touched once in one
        assert int(fused.moe_pairs) == int(apart.moe_pairs) > 0
        assert 0 < int(fused.moe_touched) <= int(apart.moe_touched)


WARMUPS = {  # the benchmark's warm-up mixes (benchmark/traffic/*.json) and the chunk its configurations run
    "chat-mix-chunk-128": ([9, 20, 40, 100, 300], 128, [16, 32, 64, 128]),
    "long-mix-chunk-256": ([9, 20, 40, 100, 200, 600], 256, [16, 32, 64, 128, 256]),
}


@pytest.mark.parametrize("warmup", sorted(WARMUPS))
def test_the_served_engine_runs_every_chunk_in_a_step_and_readies_a_step_a_bucket(warmup, monkeypatch):
    """The warm-up's requests arrive together: the first chunks run where
    no slot decodes yet, later ones beside decoding slots. Neither ever
    dispatches `paged_prefill`, and what is readied is the decode step and
    one program a bucket: as many as the decode step and a `paged_prefill`
    a bucket were."""
    import jax

    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    prompts, chunk, buckets = WARMUPS[warmup]
    launched = []
    real_step = paged_kv.paged_decode_step

    def step(params, cfg, tokens, cache, active, attn_impl, chunk=None):
        launched.append((None if chunk is None else int(chunk[0].shape[0]), bool(np.asarray(active).any())))
        if chunk is None:
            return real_step(params, cfg, tokens, cache, active, attn_impl)
        return real_step(params, cfg, tokens, cache, active, attn_impl, chunk=chunk)

    def no_prefill(*_args, **_kwargs):
        raise AssertionError("paged_prefill dispatched on the served path")

    monkeypatch.setattr(paged_kv, "paged_decode_step", step)
    monkeypatch.setattr(paged_kv, "paged_prefill", no_prefill)
    cfg = get_config("tiny")
    executables = real_step._cache_size()  # this geometry is the test's own: each entry below is new
    longest = max(prompts) + 24
    engine = ServingEngine(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=8, page_size=16, pages_per_slot=-(-longest // 16),
        num_pages=1 + len(prompts) * -(-longest // 16), prefill_chunk=chunk,
    ).start()
    try:
        requests = [engine.submit(tokens_of(n, n), max_new_tokens=24) for n in prompts]
        assert all(len(r.result(timeout=300)) == 24 for r in requests)
        stats = engine.stats()
    finally:
        engine.stop()
    chunks = sum(-(-n // chunk) for n in prompts)
    assert stats["prefill_chunks"] == stats["prefill_chunks_fused"] == chunks
    assert stats["step_programs"] == sorted(["paged_decode_step"] + [f"paged_decode_step/{b}" for b in buckets])
    programs = {bucket for bucket, _rows in launched}
    assert stats["step_programs_ready"] == 1 + len(buckets) == len(programs) == real_step._cache_size() - executables
    assert sorted(b for b in programs if b is not None) == buckets
    # a chunk ran where no slot decoded, and one beside decoding slots
    assert {(True, False), (True, True)} <= {(bucket is not None, rows) for bucket, rows in launched}
    assert stats["steps"] == sum(rows for _b, rows in launched) and stats["loop"]["phase_seconds"]["prefill_dispatch"] > 0
