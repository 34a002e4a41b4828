"""A model whose layers are not all alike in the serving engine: two kinds of
KV cache under one page manager (a pool that grows with the context, a pool
bounded by a window), the decode kernel with a window, a sink and keys wider
than values, and what the engine refuses, by mechanism; and a third pool
form, one array of latent rows a layer group with no value pool
(`tiny-axk1`), with its own decode kernel. `tiny-mimo` on the
CPU; the logits against the plain reference are in
tests/benchmark/test_bench_mimo_v2.py."""

import math

import numpy as np
import pytest

PAGE, CHUNK, WINDOW = 4, 16, 8
WINDOW_SLOT_PAGES = math.ceil(WINDOW / PAGE) + 1  # the window and the page being written


@pytest.fixture(scope="module")
def model():
    import jax

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny-mimo")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def models(model):
    """By preset: `tiny-mimo` (a share of the experts, sinks, keys wider than
    values) and `tiny-laguna` (every expert held, a shared one, gated
    attention, two query-head counts): what two pools refuse, they refuse for
    both, by mechanism."""
    import jax

    from modal_tpu.models.llama import get_config, init_params

    both = {"tiny-mimo": model}
    for preset in ("tiny-laguna", "tiny-axk1"):  # the third: one pool of latent rows, a router with a group limit
        cfg = get_config(preset)
        both[preset] = (init_params(cfg, jax.random.PRNGKey(0)), cfg)
    return both


def engine_of(model, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    params, cfg = model
    kwargs = dict(max_slots=3, page_size=PAGE, prefill_chunk=CHUNK, num_pages=120)
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


def prompts_of(seed, lengths):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(0, 512, size=n)] for n in lengths]


def pages_of(model, **overrides):
    """The page manager alone (serving/pages.py): no engine, no loop thread."""
    from modal_tpu.serving.pages import ModelPages

    kwargs = dict(max_slots=3, page_size=PAGE, prefill_chunk=CHUNK, num_pages=120)
    kwargs.update(overrides)
    return ModelPages(model[1], **kwargs)


def test_a_decoding_slot_s_window_row_never_exceeds_the_window_and_one_page(model):
    engine = engine_of(model)
    pages = engine.pages
    seen = {"decode": 0, "prefill": 0}
    decode_step = engine._decode_step

    def watched():
        decode_step()  # the launch: the decoding slots' next page taken, a chunk's window trimmed
        for i, s in enumerate(engine.slots):
            if s is not None:
                seen[s.state] = max(seen[s.state], len(pages.window_pages[i]))

    engine._decode_step = watched
    engine.start()
    try:
        handles = [engine.submit(p, 40) for p in prompts_of(1, (70, 5, 30, 100, 17))]
        for h in handles:
            assert len(h.result(timeout=300)) == 40
        stats = engine.stats()
    finally:
        engine.stop()
    assert 0 < seen["decode"] <= WINDOW_SLOT_PAGES
    # between its chunks a slot in prefill holds no more than the window either
    assert seen["prefill"] <= WINDOW_SLOT_PAGES
    assert stats["kv_window_pages_released"] > 0
    assert stats["kv_window_pages_high_water"] <= stats["kv_window_pages_total"]
    # the window pool is bounded by the window, not by the context: 5 layers x 14 pages (3 slots x 3 + a chunk's 4 + scratch)
    assert stats["kv_window_pool_bytes"] == 5 * 14 * PAGE * 4 * (24 + 16) * 2
    assert stats["kv_pool_bytes"] == 2 * 120 * PAGE * 2 * (24 + 16) * 2
    assert pages.window_allocator.free_pages == pages.window_allocator.num_pages - 1  # everything came back
    assert pages.free_pages == pages.total_pages
    assert pages.pages == [[], [], []] and pages.window_pages == [[], [], []]


def test_a_page_behind_the_window_goes_to_another_slot_at_once(model):
    pages = pages_of(model)  # the bookkeeping alone
    a, b = 0, 1
    assert pages.reserve([(a, 0, 15)]) and len(pages.window_pages[a]) == 4  # positions 0..15
    first_pages = list(pages.window_pages[a])
    pages.trim(a, 16)  # the next query, at 16, sees 9..16: pages 0 and 1 are dead
    assert (pages.window_first[a], pages.window_pages[a]) == (2, first_pages[2:]) and pages.window_pages_released == 2
    free_before = pages.window_allocator.free_pages
    assert pages.reserve([(b, 0, 7)])
    assert sorted(pages.window_pages[b]) == sorted(first_pages[:2])  # the very pages a gave back
    assert pages.window_allocator.free_pages == free_before - 2
    # the device's table: a's stale entries stay (never addressed), b's row points at the same pages
    table = np.asarray(pages.cache.window_table)
    assert list(table[a, :4]) == first_pages and list(table[b, :2]) == pages.window_pages[b]
    # a keeps decoding: one more page at 16, and never more than the bound; the
    # pool that grows with the context keeps every page meanwhile
    for pos in range(16, 60):
        assert pages.reserve([(a, pos, pos)])  # gives back what the query at pos no longer sees, then takes its page
        assert len(pages.window_pages[a]) <= WINDOW_SLOT_PAGES
        assert len(pages.pages[a]) == pos // PAGE + 1
    assert list(np.asarray(pages.cache.page_table)[a, :15]) == pages.pages[a]
    pages.release(a)
    pages.release(b)
    assert pages.window_allocator.free_pages == pages.window_allocator.num_pages - 1
    assert pages.free_pages == pages.total_pages
    assert not np.asarray(pages.cache.window_table).any() and not np.asarray(pages.cache.page_table).any()


def iterate(engine, drained):
    engine._admit()
    engine._prefill_one()
    engine._decode_step()
    if drained:
        engine._drain()  # the loop that reads every step before the next


@pytest.mark.parametrize("preset", ["tiny-mimo", "tiny-laguna"])
def test_positions_counted_at_launch_turn_the_window_pool_over_as_a_loop_that_reads_every_step(models, preset, monkeypatch):
    """ISSUE 35: with a step in flight the window pool's `trim` / `reserve`
    see a position the host counted at launch, not one it read back. The
    streams are the drained loop's, every step's window row is the window and
    the page being written, and the expert counters that ride with a step's
    tokens add up the same. (That these streams lie by the plain reference is
    tests/test_serving_two_pools_reference.py, through the same loop.)"""
    from modal_tpu.models import paged_kv

    prompts = prompts_of(7, (70, 5, 30, 100, 17))
    served, stats, launches = {}, {}, {}
    step = paged_kv.paged_decode_step

    def counted(*args, **kwargs):
        launches[drained].append(bool(np.asarray(args[4]).any()))  # whether a slot decodes in it
        return step(*args, **kwargs)

    monkeypatch.setattr(paged_kv, "paged_decode_step", counted)
    for drained in (True, False):
        launches[drained] = []
        engine = engine_of(models[preset])
        pages = engine.pages
        handles = [engine.submit(p, 36) for p in prompts]  # 36 tokens cross a window of 8 four times
        rows = []
        while not all(h.done for h in handles):
            iterate(engine, drained)
            for i, s in enumerate(engine.slots):
                if s is not None and s.state == "decode":
                    # what the pool holds for the slot follows the position the NEXT step writes
                    first = max(0, s.pos - (WINDOW - 1)) // PAGE
                    assert pages.window_first[i] <= first + 1 and len(pages.window_pages[i]) <= WINDOW_SLOT_PAGES
                    rows.append(len(pages.window_pages[i]))
        assert max(rows) == WINDOW_SLOT_PAGES
        served[drained], stats[drained] = [h.tokens for h in handles], engine.stats()
        assert engine._flying is None and engine._first is None
        engine.stop()
        assert pages.window_allocator.free_pages == pages.window_allocator.num_pages - 1 and pages.free_pages == pages.total_pages
    assert served[False] == served[True] and all(len(t) == 36 for t in served[False])
    piped, synced = stats[False], stats[True]
    # the (token, expert) pairs add up the same
    same = ("assignments", "local_assignments")
    assert {k: piped["moe"][k] for k in same} == {k: synced["moe"][k] for k in same}
    # a launch is one call an expert layer and held expert, and a launch is a step or a chunk where no slot
    # decodes: a loop that reads every step frees an ended slot and admits an iteration sooner, so how many
    # chunks ran alone (and the experts a call touched) is the loop's own
    calls = engine.moe_expert_layers * engine.cfg.experts_held[1]
    for drained, counts in ((False, piped), (True, synced)):
        alone = launches[drained].count(False)
        assert counts["moe"]["expert_calls"] == calls * len(launches[drained]) == calls * (counts["steps"] + alone)
        assert 0 < alone <= counts["prefill_chunks"] == counts["prefill_chunks_fused"]
    assert piped["kv_window_pages_released"] == synced["kv_window_pages_released"] > 0
    assert piped["loop"]["steps_overlapped"] > 0.9 * piped["steps"] and synced["loop"]["steps_overlapped"] == 0
    # nothing forced a read of what was in flight: the only drains are the periodic ones
    assert piped["loop"]["tokens_discarded"] == piped["preemptions"] == 0 and piped["loop"]["drains"] == piped["steps"] // 16


@pytest.mark.parametrize("pool", ["full", "window"])
def test_admission_waits_while_either_pool_lacks_room(model, pool):
    engine = engine_of(model, max_slots=2)  # not started: _admit by hand
    pages = engine.pages
    allocator = pages.allocator if pool == "full" else pages.window_allocator
    taken = allocator.alloc(allocator.free_pages)  # the pool is dry
    req = engine.submit(prompts_of(2, (20,))[0], 8)
    engine._admit()
    assert engine.slots == [None, None] and list(engine.waiting) == [req]
    other = pages.window_allocator if pool == "full" else pages.allocator
    assert other.free_pages == other.num_pages - 1  # nothing was taken from the pool that had room
    allocator.free(taken)
    engine._admit()
    assert engine.slots[0] is not None and not engine.waiting
    assert len(pages.pages[0]) == 6 and pages.window_pages[0] == []  # its chunk's pages come when its chunk runs
    engine._decode_step()  # the chunk made ready and launched
    assert len(pages.window_pages[0]) == WINDOW // PAGE  # 16 of 20 tokens in: what the query at 16 still sees
    pages.release(0)


@pytest.mark.parametrize("pool", ["full", "window"])
def test_the_manager_alone_says_whether_an_admission_fits(model, pool):
    pages = pages_of(model, max_slots=2)
    allocator, other = (pages.allocator, pages.window_allocator)[:: 1 if pool == "full" else -1]
    taken = allocator.alloc(allocator.free_pages - 1)  # one page left: no prompt of 20 tokens, no first chunk
    assert pages.lookup(list(range(20))) is None  # window layers: no prefix is cached
    assert not pages.can_admit(20, None)
    assert other.free_pages == other.num_pages - 1 and pages.pages == [[], []]
    allocator.free(taken)
    assert pages.can_admit(20, None) and pages.admit(1, 20, None) == 0
    assert len(pages.pages[1]) == 6 and pages.window_pages[1] == []  # 20 tokens and the first new one's position
    assert list(np.asarray(pages.cache.page_table)[1, :7]) == pages.pages[1] + [0]
    assert pages.window_allocator.free_pages == pages.window_allocator.num_pages - 1  # asked, not taken
    pages.release(1)
    assert pages.free_pages == pages.total_pages


@pytest.mark.parametrize("dry", ["full", "window"])
def test_reserve_is_all_or_none_over_both_pools_and_every_slot(model, dry):
    pages = pages_of(model, max_slots=2)
    assert pages.admit(0, 8, None) == 0 and pages.admit(1, 8, None) == 0  # 3 pages each: positions 0..11
    wants = [(0, 0, 15), (1, 0, 15)]  # each: a 4th page of the pool that grows, 4 of the window pool
    allocator = pages.allocator if dry == "full" else pages.window_allocator
    taken = allocator.alloc(allocator.free_pages - (1 if dry == "full" else 7))  # room for one slot's, not for both
    free = (pages.free_pages, pages.window_allocator.free_pages)
    assert not pages.reserve(wants)
    assert (pages.free_pages, pages.window_allocator.free_pages) == free  # no page was handed out, in either pool
    assert [len(p) for p in pages.pages] == [3, 3] and pages.window_pages == [[], []]
    assert pages.reserve(wants[:1])  # one slot's does fit
    assert len(pages.pages[0]) == 4 and len(pages.window_pages[0]) == 4 and pages.window_pages[1] == []
    allocator.free(taken)
    assert pages.reserve(wants)
    assert [len(p) for p in pages.pages] == [4, 4] and [len(p) for p in pages.window_pages] == [4, 4]
    table = np.asarray(pages.cache.window_table)
    assert [list(table[i, :4]) for i in (0, 1)] == pages.window_pages


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_preemption_frees_both_pools_and_the_streams_do_not_change(model, dtype):
    """A preempted request re-prefills its prompt and the tokens it kept
    through both pools and loses no token: up to where it was preempted its
    stream is the one it had unpreempted, and from there the one a fresh
    request of those tokens gets. In float32 that is the unpreempted stream
    throughout. In bfloat16 the re-prefill attends, in chunks, positions
    that decode steps wrote a row at a time, and the two round apart: a
    greedy token can flip after a re-prefill, here as on an engine that
    launches the chunk on its own (`paged_prefill`) and preempts the same
    request at the same token."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    if dtype == "float32":
        params, cfg = model
        model = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params), dataclasses.replace(cfg, dtype=jnp.float32)
    prompts = prompts_of(3, (60, 44, 70, 25))
    roomy = engine_of(model).start()
    try:
        want = [h.result(timeout=300) for h in [roomy.submit(p, 50) for p in prompts]]
    finally:
        roomy.stop()
    # a full pool of 44 pages = 176 positions cannot hold three of these to their ends
    tight = engine_of(model, num_pages=45)
    kept: dict = {}  # request id -> the tokens it kept at each preemption
    preempt = tight._preempt_youngest

    def noted():
        if not preempt():
            return False
        victim = tight.waiting[0]  # requeued at the front
        kept.setdefault(victim.id, []).append(len(victim.tokens))
        return True

    tight._preempt_youngest = noted
    tight.start()
    try:
        handles = [tight.submit(p, 50) for p in prompts]
        got = [h.result(timeout=300) for h in handles]
        stats = tight.stats()
    finally:
        tight.stop()
    assert stats["preemptions"] == sum(map(len, kept.values())) > 0
    fresh = engine_of(model).start()
    try:
        for prompt, handle, stream, unpreempted in zip(prompts, handles, got, want):
            marks = kept.get(handle.id, [])
            first = marks[0] if marks else len(stream)
            assert stream[:first] == unpreempted[:first]
            for at, until in zip(marks, marks[1:] + [len(stream)]):
                again = fresh.submit(prompt + stream[:at], 50 - at).result(timeout=300)
                assert stream[at:until] == again[: until - at]
    finally:
        fresh.stop()
    if dtype == "float32":
        assert got == want
    assert tight.pages.free_pages == 44
    assert tight.pages.window_allocator.free_pages == tight.pages.window_allocator.num_pages - 1


def test_a_dry_window_pool_preempts_and_still_finishes_every_request(model):
    # room for one chunk and the window before it, and little else
    smallest = math.ceil((CHUNK + WINDOW - 1) / PAGE) + 1
    engine = engine_of(model, window_num_pages=smallest + 2).start()
    try:
        handles = [engine.submit(p, 30) for p in prompts_of(4, (40, 33, 50))]
        assert all(len(h.result(timeout=300)) == 30 for h in handles)
        assert engine.stats()["preemptions"] > 0
    finally:
        engine.stop()
    assert engine.pages.window_allocator.free_pages == smallest + 1
    with pytest.raises(ValueError, match="cannot hold one prefill chunk"):
        engine_of(model, window_num_pages=smallest)


REFUSALS = {
    "a draft over window layers and experts": (dict(draft="self"), "speculative decoding"),
    "a prefill role over two pools": (dict(role="prefill"), "ships KV pages"),
    "a decode role over two pools": (dict(role="decode"), "ships KV pages"),
    "a prefix cache across a window layer": (dict(prefix_cache=True), "prefix_cache=True with window layers"),
    "int8 over expert weights": (dict(quantized=True), "quantize_int8 over routed-expert weights"),
}


# a model of latent layers has no window: what a window refuses it is not refused (its prefix cache is served below)
REFUSED = [(preset, case) for preset in ("tiny-mimo", "tiny-laguna", "tiny-axk1") for case in sorted(REFUSALS) if preset != "tiny-axk1" or "window" not in case]


@pytest.mark.parametrize("preset,case", REFUSED)
def test_the_engine_refuses_by_mechanism(models, preset, case):
    import jax
    import jax.numpy as jnp

    from modal_tpu.serving.engine import ServingEngine

    params, cfg = models[preset]
    kwargs, message = REFUSALS[case]
    kwargs = dict(kwargs)
    if kwargs.pop("draft", None):
        kwargs["draft"] = (params, cfg)
    if kwargs.pop("quantized", None):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.int8) if a.ndim == 4 else a, params)  # the experts' stacks
    with pytest.raises(ValueError, match=message) as refused:
        ServingEngine(params, cfg, page_size=PAGE, prefill_chunk=CHUNK, **kwargs)
    assert not {"mimo", "laguna", "axk1", "a.x"} & set(str(refused.value).lower().replace("-", " ").split())  # the mechanism, never a model's name


def test_the_trainer_s_switch_layer_is_refused_in_the_paged_path_and_as_a_draft():
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    moe = get_config("tiny-moe")
    with pytest.raises(ValueError, match="top-1 routing with a capacity that drops tokens"):
        ServingEngine({}, moe)
    tiny = get_config("tiny")
    params = init_params(tiny, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="mesh trainer's switch layer"):
        ServingEngine(params, tiny, draft=({}, moe))


def test_shipping_pages_and_the_verify_step_say_why_they_cannot(model):
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv as pk

    params, cfg = model
    engine = engine_of(model)
    with pytest.raises(ValueError, match="ONE pool"):
        engine.prefill_export([1, 2, 3])
    with pytest.raises(ValueError, match="ONE pool"):
        engine.submit_prefilled([1, 2, 3], {"prompt": [1, 2, 3]})
    with pytest.raises(ValueError, match="one pool of one layer kind"):
        pk.paged_verify_step(params, cfg, jnp.zeros((3, 2), jnp.int32), engine.pages.cache, jnp.ones((3,), bool))
    with pytest.raises(ValueError, match="needs window_num_pages"):
        pk.PagedKVCache.create(cfg, 2, 16, PAGE)


def test_a_depth_cut_keeps_the_first_layers_of_the_published_pattern():
    from modal_tpu.models.llama import get_config

    cut = get_config({"name": "mimo-v2-flash", "n_layers": 7, "n_experts_held": 16, "vocab_size": 19072})
    assert cut.attn_pattern == (0, 1, 1, 1, 1, 0, 1) and cut.ffn_pattern == (0, 1, 1, 1, 1, 1, 1)
    kinds = [(k.n_kv_heads, k.window, k.sink, k.rope_theta, k.experts, k.attn_name) for k in cut.layer_kinds]
    assert kinds[0] == (4, 0, False, 5_000_000.0, False, "full")
    assert kinds[1] == (8, 128, True, 10_000.0, True, "swa") and kinds[5] == (4, 0, False, 5_000_000.0, True, "full")
    assert [(first, n) for _k, first, n in cut.layer_groups] == [(0, 1), (1, 4), (5, 1), (6, 1)]
    assert (cut.head_dim, cut.v_dim, cut.rope_dim, cut.experts_held) == (192, 128, 64, (0, 16))
    assert get_config("mimo-v2-flash").param_count() == 308_778_780_864  # whole, as published
    # the dense presets are the case "every layer alike" of the same description
    dense = get_config("llama3-8b")
    assert dense.uniform and len(dense.layer_groups) == 1 and dense.layer_kinds[0].attn_name == ""
    assert (dense.head_dim, dense.v_dim, dense.rope_dim) == (128, 128, 128)
    with pytest.raises(ValueError, match="names 3 layers"):
        get_config("tiny-mimo", attn_pattern=(0, 1, 1))
    with pytest.raises(ValueError, match="outside the 32 routed experts"):
        get_config("tiny-mimo", experts_held_start=30)


def test_a_depth_cut_of_per_layer_lists_and_rotary_rules_as_config_files_publish_them():
    """The lists a config.json names its layers by (strings) and its rotary
    rules by layer type (a nested object) are taken as published: the preset,
    and the same keys handed over as a benchmark configuration does."""
    from modal_tpu.models.llama import get_config

    cut = get_config({"name": "laguna-xs.2", "n_layers": 5, "max_seq_len": 8192})
    assert cut.attn_pattern == (0, 1, 1, 1, 0) and cut.ffn_pattern == (0, 1, 1, 1, 1) and cut.n_heads_per_layer == (48, 64, 64, 64, 48)
    kinds = [(k.n_heads, k.n_kv_heads, k.window, k.rope_theta, k.rope_dim, bool(k.yarn), k.gated, k.experts, k.attn_name) for k in cut.layer_kinds]
    assert kinds[0] == (48, 8, 0, 500_000.0, 64, True, True, False, "full")
    assert kinds[1] == kinds[2] == kinds[3] == (64, 8, 512, 10_000.0, 128, False, True, True, "swa")
    assert kinds[4] == (48, 8, 0, 500_000.0, 64, True, True, True, "full")
    assert [(first, n) for _k, first, n in cut.layer_groups] == [(0, 1), (1, 3), (4, 1)]  # one compiled body a group
    assert cut.experts_held == (0, 256) and (cut.shared_expert_dim, cut.routed_scale, cut.router_bias) == (512, 2.5, False)
    assert cut.param_count() == 3_869_857_792  # 7.74 GB in bf16: every expert of five layers and the whole vocabulary
    assert hash(cut) == hash(get_config({"name": "laguna-xs.2", "n_layers": 5, "max_seq_len": 8192}))  # a jit key
    published = get_config({
        "name": "tiny-laguna", "attn_pattern": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
        "ffn_pattern": ["dense", "sparse", "sparse", "sparse", "sparse"], "n_heads_per_layer": [12, 16, 16, 16, 12],
        "rope_parameters": {
            "full_attention": {"rope_theta": 100, "rope_type": "yarn", "factor": 4, "original_max_position_embeddings": 64, "beta_slow": 1, "beta_fast": 8, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
            "original_max_position_embeddings": 64,
        },
    })
    assert published == get_config("tiny-laguna") and published.layer_kinds == get_config("tiny-laguna").layer_kinds
    # MiMo's description reads as it did: one head count, rotary on a third of a head on two bases, no gate
    mimo = get_config("tiny-mimo")
    assert {(k.n_heads, k.rope_dim, k.yarn, k.gated) for k in mimo.layer_kinds} == {(8, 8, (), False)}
    with pytest.raises(ValueError, match="rope_type 'linear'"):
        get_config("tiny-laguna", rope_parameters={"full_attention": {"rope_type": "linear"}})
    with pytest.raises(ValueError, match="13 query heads over 2 KV heads"):
        get_config("tiny-laguna", n_heads_per_layer=(13, 16, 16, 16, 12))


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("window", [0, 8, 5], ids=["full", "window-8", "window-5"])
@pytest.mark.parametrize("heads", [8, 12], ids=["4-heads-a-kv-head", "6-heads-a-kv-head"])
def test_the_decode_kernel_matches_the_gather_path_with_a_window_a_sink_and_two_widths(heads, window, sink):
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.paged_kv import _paged_attention

    slots, n_kv, hd, vd, pages_per_slot, pool = 3, 2, 24, 16, 6, 20  # 6 query heads a KV head: no power of two
    keys = jax.random.split(jax.random.PRNGKey(window + 10 * sink), 5)
    q = jax.random.normal(keys[0], (slots, 1, heads, hd), jnp.float32)
    k_pages = jax.random.normal(keys[1], (pool, PAGE, n_kv, hd), jnp.float32)
    v_pages = jax.random.normal(keys[2], (pool, PAGE, n_kv, vd), jnp.float32)
    table = jax.random.permutation(keys[3], jnp.arange(1, pool))[: slots * pages_per_slot].reshape(slots, pages_per_slot)
    positions = jnp.asarray([0, 9, 22], jnp.int32)  # inside the first page, across pages, near the row's end
    sinks = jax.random.normal(keys[4], (heads,), jnp.float32) if sink else None
    kv_pos = jnp.arange(pages_per_slot * PAGE)[None, None, None, :]
    seen = kv_pos <= positions[:, None, None, None]
    if window:
        seen = seen & (kv_pos > positions[:, None, None, None] - window)
    mask = jnp.where(seen, 0.0, -jnp.inf)
    if window:
        # pages behind a window are stale in a served row: the kernel must never address them
        first_live = jnp.maximum(positions - (window - 1), 0) // PAGE
        stale = jnp.arange(pages_per_slot)[None, :] < first_live[:, None]
        table_kernel = jnp.where(stale, 10_000, table)  # an id far outside the pool
    else:
        table_kernel = table
    args = dict(window=window, sink=sinks, scale=0.2)
    want = _paged_attention(q, k_pages, v_pages, table, mask, positions, "gather", **args)
    got = _paged_attention(q, k_pages, v_pages, table_kernel, mask, positions, "kernel_interpret", kernel_name="k", **args)
    assert got.shape == (slots, 1, heads, vd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)


@pytest.mark.parametrize("stored", [24, 128], ids=["row-as-wide-as-the-model", "row-stored-padded"])
@pytest.mark.parametrize("heads", [4, 16])
def test_the_latent_decode_kernel_reads_its_values_from_the_key_block_and_matches_the_gather_path(heads, stored):
    """`paged_decode_attention_mla` (the interpreter runs its body) against
    the gather path over the same rows: one copy a page, the first 16 columns
    of a row its value, every head over every row; a slot that does not decode
    gets zeros, a dead page is never walked (its table entry is out of range)."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.paged_kv import _paged_attention

    slots, pool, pages_per_slot, latent, width = 5, 40, 6, 16, 24
    keys = jax.random.split(jax.random.PRNGKey(heads + stored), 3)
    rows = jax.random.normal(keys[0], (pool, PAGE, 1, stored), jnp.float32).at[..., width:].set(0.0)
    q = jax.random.normal(keys[1], (slots, 1, heads, stored), jnp.float32).at[..., width:].set(0.0)
    table = jax.random.permutation(keys[2], pool - 1)[: slots * pages_per_slot].reshape(slots, pages_per_slot).astype(jnp.int32) + 1
    positions = jnp.asarray([0, 5, 23, -1, 11], jnp.int32)  # a first token, inside a page, the row's last position, idle, a page's last
    live = jnp.arange(pages_per_slot)[None, :] <= positions[:, None] // PAGE
    table_kernel = jnp.where(live, table, 10**6)  # the kernel must not touch a page past the live ones
    kv_pos = jnp.arange(pages_per_slot * PAGE)[None, None, None, :]
    mask = jnp.where(kv_pos <= positions[:, None, None, None], 0.0, -jnp.inf).astype(jnp.float32)
    args = dict(scale=0.21, latent=latent)
    want = _paged_attention(q, rows, None, table, mask, positions, "gather", **args)
    got = _paged_attention(q, rows, None, table_kernel, mask, positions, "kernel_interpret", kernel_name="paged_decode_attention_mla", **args)
    assert got.shape == (slots, 1, heads, latent)
    decoding = np.asarray(positions) >= 0
    np.testing.assert_allclose(np.asarray(got)[decoding], np.asarray(want)[decoding], atol=2e-5, rtol=0)
    assert not np.asarray(got)[~decoding].any() and np.abs(np.asarray(want)[decoding]).max() > 0.1
    # the first token sees one row: its output IS that row's latent
    first = rows[table[0, 0], 0, 0, :latent]
    np.testing.assert_allclose(np.asarray(got)[0, 0], np.broadcast_to(np.asarray(first), (heads, latent)), atol=1e-6)


# what /v1/stats says of pages, a model: the benchmark's readers and `modal_tpu top` go by these names
PAGE_KEYS = {
    "kv_pages_total", "kv_pages_allocated", "kv_pages_free", "kv_pages_high_water", "kv_pool_bytes", "kv_bytes_per_token",
    "kv_pages_cow_copies", "kv_table_writes", "kv_table_entries", "kv_pages_shipped", "kv_ship_drops",
    "prefix_cache_entries", "prefix_cache_pages", "prefix_cache_hits", "prefix_cache_misses",
    "draft_prefix_cache_entries", "draft_prefix_cache_hits",
}
WINDOW_KEYS = {"kv_window_pages_total", "kv_window_pages_high_water", "kv_window_pages_released", "kv_window_pool_bytes"}


@pytest.mark.parametrize("preset", ["tiny", "tiny-mimo", "tiny-axk1"])
def test_stats_of_a_dense_model_carry_no_second_pool(model, models, preset):
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    if preset == "tiny":
        cfg = get_config("tiny")
        stats = ServingEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg).stats()
        assert not [k for k in stats if k.startswith("kv_window") or k == "moe"]
        assert stats["kv_pool_bytes"] > 0
        assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 2  # keys and values, 2 layers, 2 KV heads of 32, bfloat16
    elif preset == "tiny-axk1":  # one pool of latent rows: 5 layers x (16 + 8) x 2 bytes a token, no window pool
        stats = engine_of(models[preset]).stats()
        assert not [k for k in stats if k.startswith("kv_window")] and "moe" in stats
        assert stats["kv_bytes_per_token"] == 5 * 24 * 2 and stats["kv_pool_bytes"] == 120 * PAGE * 5 * 24 * 2
    else:
        stats = engine_of(model).stats()
        assert WINDOW_KEYS | {"moe"} <= set(stats)
        assert stats["moe"] == {"assignments": 0, "local_assignments": 0, "expert_calls": 0, "experts_touched": 0}
    paged = {k for k in stats if k.startswith(("kv_", "prefix_cache_", "draft_prefix_cache_"))}
    assert paged == PAGE_KEYS | (WINDOW_KEYS if preset == "tiny-mimo" else set())
    if preset == "tiny-mimo":  # both pools: 2 full layers of 4 KV heads, 5 window layers of 8, keys 24 and values 16 wide
        assert stats["kv_bytes_per_token"] == (2 * 2 + 5 * 4) * (24 + 16) * 2
    assert {"preemptions", "requests_admitted", "loop", "spec_k", "attn_impl"} <= set(stats) and "spec_overlap" not in stats


def test_llm_service_takes_the_second_pool_s_size_and_hands_it_to_the_engine():
    import inspect

    from modal_tpu.serving import llm_service
    from modal_tpu.serving.engine import ServingEngine

    assert inspect.signature(llm_service).parameters["window_num_pages"].default is None
    assert inspect.signature(ServingEngine.__init__).parameters["window_num_pages"].default is None
    # the bound on waiting requests is the engine's own, handed through (a deployment's queue depth)
    assert inspect.signature(llm_service).parameters["max_waiting"].default == inspect.signature(ServingEngine.__init__).parameters["max_waiting"].default == 1024
    with open(inspect.getsourcefile(llm_service)) as f:
        source = f.read()
    assert "window_num_pages=window_num_pages" in source and "max_waiting=max_waiting" in source


def test_llm_service_refuses_a_mechanism_it_does_not_list_before_anything_is_registered():
    """`requires` names what the caller's model needs of the served path: an
    unlisted name is refused in the calling process (no container boots on a
    preset it lacks), a listed one changes nothing."""
    import inspect

    import modal_tpu
    from modal_tpu.serving import llm_service, service

    assert {"latent_kv", "router_groups", "window_kv", "routed_experts"} <= set(service.MECHANISMS)
    assert inspect.signature(llm_service).parameters["requires"].default == ()
    app = modal_tpu.App("requires-refused")
    with pytest.raises(ValueError, match="has no 'no_such_mechanism'"):
        llm_service(app, model="tiny", requires=("latent_kv", "no_such_mechanism"))
    assert not app.registered_classes and not app.registered_functions  # refused before anything was registered
    plain, asked = modal_tpu.App("requires-a"), modal_tpu.App("requires-b")
    llm_service(plain, model="tiny")
    llm_service(asked, model="tiny", requires=["latent_kv", "router_groups"])  # a list, as a configuration file gives it
    assert sorted(plain.registered_classes) == sorted(asked.registered_classes) == ["LLMService"]
    assert sorted(plain.registered_functions) == sorted(asked.registered_functions)
    with open(inspect.getsourcefile(llm_service)) as f:
        source = f.read()
    assert "import jax" not in source.split("def llm_service")[0] and "requires=requires" not in source  # no container sees it


def test_a_prefix_hit_over_the_latent_pool_copies_a_shared_page_in_every_layer_group(models):
    """One pool that grows with the context and no window: the prefix cache
    serves a model of latent layers, and copy-on-write duplicates the page in
    each group's array (a latent group has rows and no values)."""
    engine = engine_of(models["tiny-axk1"], prefix_cache=True).start()
    try:
        shared = prompts_of(3, [30])[0]
        a, b = shared + [7, 8, 9], shared + [11, 12]
        first = engine.submit(a, 6).result(timeout=300)
        again = engine.submit(a, 6).result(timeout=300)  # the whole prompt is a cached prefix: its last page is shared, then written
        other = engine.submit(b, 6).result(timeout=300)
        stats = engine.stats()
    finally:
        engine.stop()
    assert again == first and len(other) == 6
    assert stats["prefix_cache_hits"] >= 2 and stats["kv_pages_cow_copies"] >= 1
    cold = engine_of(models["tiny-axk1"], prefix_cache=False).start()
    try:
        assert cold.submit(b, 6).result(timeout=300) == other  # a hit changes no token
    finally:
        cold.stop()
