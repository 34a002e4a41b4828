"""The page manager alone (serving/pages.py `ModelPages`), a dense model: what
it answers the engine's loop, with no engine and no loop thread. `tiny` on the
CPU; the two-pool cases are in tests/test_serving_two_pools.py, the engine's
streams over these paths in tests/test_serving.py."""

import numpy as np
import pytest

PAGE = 4


def pages_of(**overrides):
    from modal_tpu.models.llama import get_config
    from modal_tpu.serving.pages import ModelPages

    kwargs = dict(max_slots=3, num_pages=9, page_size=PAGE, pages_per_slot=12, prefill_chunk=16, prefix_cache=True)
    kwargs.update(overrides)
    return ModelPages(get_config("tiny"), **kwargs)


def test_importing_the_manager_s_module_leaves_jax_out():
    import subprocess
    import sys

    code = "import sys, modal_tpu.serving.pages; assert 'jax' not in sys.modules"
    assert subprocess.run([sys.executable, "-c", code], capture_output=True, timeout=120).returncode == 0


def test_copy_on_write_leaves_the_shared_page_untouched():
    import jax.numpy as jnp

    pages = pages_of(num_pages=20)
    prompt = list(range(10))  # two full pages and two tokens of a third
    assert pages.admit(0, len(prompt), pages.lookup(prompt)) == 0  # a miss: 3 pages (10 tokens and position 10)
    ids = list(pages.pages[0])
    # every page's keys say which page they are
    marks = jnp.asarray(ids, pages.cache.k_pages.dtype)[None, :, None, None, None]
    pages.cache = pages.cache._replace(k_pages=pages.cache.k_pages.at[:, jnp.asarray(ids)].set(marks))
    pages.publish(0, prompt)
    follower = prompt + [99, 98]
    hit = pages.lookup(follower)
    assert (hit.pages, hit.covered) == (ids, 10)  # token-granular: into the entry's partial page
    assert pages.can_admit(len(follower), hit) and pages.admit(1, len(follower), hit) == 10
    shared = ids[2]
    assert pages.allocator.refcount(shared) == 3  # the inserter, the entry, the follower
    assert pages.reserve([(1, 10, 11)])  # the follower writes 10 and 11: into the shared page
    private = pages.pages[1][2]
    assert private != shared and pages.cow_copies == 1 and pages.allocator.refcount(shared) == 2
    k = np.asarray(pages.cache.k_pages, np.float32)
    assert (k[:, private] == shared).all() and (k[:, shared] == shared).all()  # copied, and the original as it was
    table = np.asarray(pages.cache.page_table)
    assert list(table[1, :4]) == pages.pages[1] and list(table[0, :3]) == ids
    # the inserter decodes into the page its own prompt was published from: it copies too
    assert pages.reserve([(0, 10, 10)]) and pages.cow_copies == 2
    assert pages.allocator.refcount(shared) == 1 and pages.prefix_cache._entries[tuple(prompt)].pages == ids
    assert pages.reserve([(0, 11, 11)]) and pages.cow_copies == 2  # private now: nothing to copy
    stats = pages.stats()
    assert (stats["kv_pages_cow_copies"], stats["prefix_cache_hits"], stats["prefix_cache_misses"]) == (2, 1, 1)
    pages.release(0)
    pages.release(1)
    assert pages.free_pages == pages.total_pages - 3  # the entry's pages outlive both
    pages.clear_prefixes()
    assert pages.free_pages == pages.total_pages


def test_full_pages_only_shares_no_page_that_is_written_and_copies_nothing():
    pages = pages_of(num_pages=20, partial_pages=False)  # a draft's pool
    prompt = list(range(10))
    pages.admit(0, len(prompt), pages.lookup(prompt))
    ids = list(pages.pages[0])
    pages.publish(0, prompt)
    assert pages.prefix_cache._entries[tuple(prompt[:8])].pages == ids[:2]  # the partial page stays the slot's own
    hit = pages.lookup(prompt + [99, 98])
    assert (hit.pages, hit.covered) == (ids[:2], 8)  # ends at the page boundary
    assert pages.admit(1, 12, hit) == 8
    assert pages.reserve([(1, 8, 11), (0, 10, 10)]) and pages.cow_copies == 0
    assert pages.pages[1][:2] == ids[:2] and pages.pages[1][2] != ids[2]


def test_cached_prefixes_are_evicted_before_an_admission_is_refused():
    pages = pages_of()  # 8 pages
    old = list(range(16))
    pages.admit(0, len(old), pages.lookup(old))  # 5 pages
    pages.publish(0, old)
    pages.release(0)
    assert pages.free_pages == 4 and len(pages.prefix_cache) == 1  # the entry holds the prompt's 4
    new = list(range(100, 124))  # 7 pages: more than are free, not more than the pool
    hit = pages.lookup(new)
    assert hit.entry is None and pages.can_admit(len(new), hit)
    assert len(pages.prefix_cache) == 0 and pages.free_pages == 8  # the prefix went, not the admission
    assert pages.admit(1, len(new), hit) == 0 and pages.free_pages == 1
    assert pages.stats()["prefix_cache_misses"] == 2 and pages.stats()["prefix_cache_hits"] == 0
    assert not pages.can_admit(8, pages.lookup(list(range(8))))  # 3 pages, 1 free, nothing left to evict


def test_a_dry_pool_retry_counts_no_hit_and_holds_no_page():
    pages = pages_of()
    prompt = list(range(12))
    pages.admit(0, len(prompt), pages.lookup(prompt))  # 4 pages, and the one miss
    pages.publish(0, prompt)
    taken = pages.allocator.alloc(pages.free_pages)  # dry
    follower = prompt + [7, 7, 7]  # 4 pages, 3 of them shared: 1 fresh
    hit = pages.lookup(follower)
    assert hit.covered == 12 and [pages.allocator.refcount(p) for p in hit.pages] == [3, 3, 3]
    assert not pages.can_admit(len(follower), hit)  # every cached prefix went for it, the one it hit too
    pages.drop(hit)
    assert [pages.allocator.refcount(p) for p in pages.pages[0]] == [1, 1, 1, 1]  # the lookup's refs went back
    for _retry in range(3):
        hit = pages.lookup(follower)
        assert hit.covered == 0 and not pages.can_admit(len(follower), hit)
        pages.drop(hit)
    assert (pages.prefix_cache.hits, pages.prefix_cache.misses) == (0, 1)  # counted at the commit alone
    pages.allocator.free(taken)
    hit = pages.lookup(follower)
    assert pages.can_admit(len(follower), hit) and pages.admit(1, len(follower), hit) == 0
    assert (pages.prefix_cache.hits, pages.prefix_cache.misses) == (0, 2)


def test_cached_prefixes_are_evicted_before_a_write_is_refused_and_a_refusal_hands_out_nothing():
    pages = pages_of()
    old = list(range(8))
    pages.admit(0, len(old), pages.lookup(old))
    pages.publish(0, old)
    pages.release(0)
    assert pages.free_pages == 6  # the entry holds the prompt's 2 full pages
    pages.admit(1, 20, None)  # 6 pages: positions 0..23
    assert pages.free_pages == 0 and len(pages.prefix_cache) == 1
    assert pages.reserve([(1, 24, 24)])  # the 7th page: the cached prefix pays for it
    assert len(pages.prefix_cache) == 0 and len(pages.pages[1]) == 7 and pages.free_pages == 1
    assert not pages.reserve([(1, 25, 40)])  # 4 more, 1 free, nothing to evict: the engine's cue to preempt
    assert len(pages.pages[1]) == 7 and pages.free_pages == 1
    assert pages.reserve([(1, 25, 31)]) and pages.free_pages == 0  # the one page there is
    assert list(np.asarray(pages.cache.page_table)[1, :8]) == pages.pages[1]


# what one `reserve` sends to the device's tables: (preset, the manager's geometry, [(slot, prompt tokens)]
# admitted and prefilled, the step's wants, the `assign_entries` calls it makes, the entries they carry)
TABLE_WRITE_CASES = {
    # every slot holds positions 0..7; the five at position 8 cross into a third page
    "dense-5-of-8-slots-cross": (
        "tiny", dict(max_slots=8, num_pages=40), [(i, 7) for i in range(8)],
        [(i, 8, 8) for i in (0, 2, 3, 5, 7)] + [(i, 6, 6) for i in (1, 4, 6)], 1, 5,
    ),
    # window 8, pages of 4: at position 12 slot 0's first window page falls behind and goes back (the
    # turn-over), slot 1 at 8 keeps both of its; each takes a page of either pool, in ONE call
    "two-pools-growth-and-turn-over": (
        "tiny-mimo", dict(max_slots=3, num_pages=40, prefix_cache=False), [(0, 11), (1, 7)],
        [(0, 12, 12), (1, 8, 8)], 1, 4,
    ),
    # a speculative round of k = 5 writes positions 7..12: two more pages a slot
    "speculative-ahead-crosses-two-pages": (
        "tiny", dict(max_slots=8, num_pages=40), [(i, 7) for i in range(3)], [(i, 7, 12) for i in range(3)], 1, 6,
    ),
    # the fixed length is max(3 slots, a chunk's 16 / 4 + 1) = 5: six entries a table go in two calls
    "more-entries-than-the-fixed-length": (
        "tiny-mimo", dict(max_slots=3, num_pages=40, prefix_cache=False), [], [(0, 0, 15), (1, 0, 7)], 2, 12,
    ),
    "no-slot-crosses": ("tiny", dict(max_slots=8, num_pages=40), [(i, 7) for i in range(8)], [(i, 6, 6) for i in range(8)], 0, 0),
}


@pytest.mark.parametrize("case", sorted(TABLE_WRITE_CASES))
def test_a_reserve_writes_what_it_handed_out_in_one_call_and_a_refusal_writes_nothing(case, monkeypatch):
    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config
    from modal_tpu.serving.pages import ModelPages

    preset, geometry, admitted, wants, calls, entries = TABLE_WRITE_CASES[case]
    pages = ModelPages(get_config(preset), page_size=PAGE, pages_per_slot=12, prefill_chunk=16, **geometry)
    made = []
    assign_entries = paged_kv.assign_entries
    monkeypatch.setattr(paged_kv, "assign_entries", lambda cache, packed: made.append(packed) or assign_entries(cache, packed))

    def tables():
        return [np.asarray(t) for t in (pages.cache.page_table, pages.cache.window_table) if t is not None]

    def host():
        return ([list(p) for p in pages.pages], [list(p) for p in pages.window_pages], list(pages.window_first))

    def check_tables_are_the_host_s():
        on_device = tables()
        for i, held in enumerate(pages.pages):
            assert list(on_device[0][i, : len(held)]) == held
        for i, held in enumerate(pages.window_pages if pages.window_allocator is not None else []):
            first = pages.window_first[i]  # live from there on; what lies before is stale
            assert list(on_device[1][i, first : first + len(held)]) == held

    for i, n in admitted:
        pages.admit(i, n, pages.lookup(list(range(n))))
        assert pages.reserve([(i, 0, n - 1)])  # its prefill chunk: window pages where there is a window pool
    check_tables_are_the_host_s()
    before, stats = len(made), pages.stats()
    assert pages.reserve(wants)
    assert len(made) - before == calls and all(m.dtype == np.int32 and m.shape == made[0].shape for m in made)
    after = pages.stats()
    assert after["kv_table_writes"] - stats["kv_table_writes"] == calls
    assert after["kv_table_entries"] - stats["kv_table_entries"] == entries
    for i, _first, last in wants:
        assert len(pages.pages[i]) >= last // PAGE + 1
        if pages.window_allocator is not None:
            assert pages.window_first[i] + len(pages.window_pages[i]) == last // PAGE + 1
    check_tables_are_the_host_s()
    if case == "two-pools-growth-and-turn-over":
        assert pages.window_first[:2] == [1, 0] and pages.window_pages_released == 1
    # more than the pool has: no page of either pool handed out, no call made, the tables as they were
    i, _first, last = wants[0]
    held, free, on_device, n_made = host(), pages.free_pages, tables(), len(made)
    assert not pages.reserve([(i, last + 1, (len(pages.pages[i]) + free + 1) * PAGE)])
    assert host() == held and pages.free_pages == free and len(made) == n_made
    assert pages.stats()["kv_table_writes"] == after["kv_table_writes"] and pages.stats()["kv_table_entries"] == after["kv_table_entries"]
    assert all(np.array_equal(a, b) for a, b in zip(on_device, tables()))


def test_a_shipment_lands_in_another_manager_s_pages_and_two_pools_refuse():
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config
    from modal_tpu.serving.pages import ModelPages

    src, dst = pages_of(prefix_cache=False), pages_of(prefix_cache=False)
    src.admit(2, 10, None)
    rng = np.random.default_rng(0)
    k = rng.standard_normal(src.cache.k_pages.shape).astype(np.float32)
    src.cache = src.cache._replace(k_pages=jnp.asarray(k, src.cache.k_pages.dtype))
    data, n = src.export_shipment(2, 10)
    assert n == 3 and data["k"].shape[:3] == (src.cfg.n_layers, 3, PAGE)
    assert dst.shipment_fits(10, data) and not dst.shipment_fits(13, data) and not dst.shipment_fits(10, {"k": data["k"]})
    dst.allocator.alloc(2)  # other page ids on this side
    dst.admit(0, 10, None)
    assert dst.import_shipment(0, 10, data) == 3
    got = np.asarray(dst.cache.k_pages, np.float32)[:, dst.pages[0]]
    np.testing.assert_array_equal(got, np.asarray(src.cache.k_pages, np.float32)[:, src.pages[2]])
    two = ModelPages(get_config("tiny-mimo"), max_slots=2, num_pages=20, page_size=PAGE, prefill_chunk=16)
    with pytest.raises(ValueError, match="ONE pool"):
        two.check_ships("prefill_export")
    with pytest.raises(ValueError, match="prefix_cache=True with window layers"):
        ModelPages(get_config("tiny-mimo"), max_slots=2, num_pages=20, page_size=PAGE, prefill_chunk=16, prefix_cache=True)


@pytest.fixture(scope="module")
def engine_window():
    """`/v1/stats` of a live `tiny` engine around three requests that decode
    side by side, 40 tokens each over pages of 4: every slot crosses a page
    boundary every fourth step, the three mostly in the same step."""
    import jax

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    cfg = get_config("tiny")
    engine = ServingEngine(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=4, num_pages=60, page_size=PAGE, pages_per_slot=16, prefill_chunk=16
    ).start()
    try:
        engine.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
        ctx = {"stats_start": engine.stats()}
        for req in [engine.submit(list(range(60 + i, 70 + i)), max_new_tokens=40) for i in range(3)]:
            req.result(timeout=120)
        ctx["stats_end"] = engine.stats()
    finally:
        engine.stop()
    return ctx


@pytest.mark.parametrize("metric", ["kv_table_writes_per_step", "kv_table_entries_per_write"])
def test_the_table_write_metrics_read_a_live_engine_through_the_benchmark_s_reader(engine_window, metric):
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        from readers import stats_delta_ratio
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "stats_delta_ratio" and spec["layer"] == "page manager"
    grew = {k: engine_window["stats_end"][k] - engine_window["stats_start"][k] for k in ("steps", "kv_table_writes", "kv_table_entries")}
    # 3 slots x 40 tokens cross 3 x 10 page boundaries; a step writes once, whatever crossed in it
    assert grew["kv_table_entries"] == 30 and 10 <= grew["kv_table_writes"] <= min(30, grew["steps"])
    value = stats_delta_ratio.read(engine_window, **spec["args"])
    assert value == pytest.approx(
        {"kv_table_writes_per_step": grew["kv_table_writes"] / grew["steps"], "kv_table_entries_per_write": 30 / grew["kv_table_writes"]}[metric]
    )
    assert 0 < value <= 1 if metric == "kv_table_writes_per_step" else 1 <= value <= 3
    parent = {"steps": 3, "kv_pages_cow_copies": 0}  # the parent commit's `/v1/stats` lacks the counters: silent, never a 0
    assert stats_delta_ratio.read({"stats_start": parent, "stats_end": {**parent, "steps": 50}}, **spec["args"]) is None
    assert stats_delta_ratio.read({"stats_start": engine_window["stats_end"], "stats_end": engine_window["stats_end"]}, **spec["args"]) is None
