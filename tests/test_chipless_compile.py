"""Compiles for the chip without the chip: the kernel of the served path and
the two jitted steps at the benchmark's real shapes, for a TPU v5e that is
described and not attached (the TPU's compiler is installed where the tests
run). Nothing runs, so this says nothing about results or times: it guards
that Mosaic still takes the kernel as written, that the kernel and the two
programs keep their stable names, that nothing of the slot span's size is
left in a prefill chunk, that nothing of a KV pool's or of one layer's
pool's size is made in either step but the in-place scatter of the new rows
(the layer loop carries the pool: `paged_kv._run_layers`), and that the
decode step holds one small Mosaic kernel a layer group (a kernel unrolled
over pages or heads costs seconds at every boot: PERF.md section 6, PR 33).

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, each xdist worker imports every
test file, and only the worker that runs THIS file may make the call. Keep
every such compile in this one file.
"""

import math
import os
import re

import pytest

# (slots, pages a slot, KV heads, query heads a KV head, pool pages): the engine geometry of the
# benchmark's two configurations (benchmark/configs/*.json), pages of 16 tokens, heads of 128
CELL_SHAPES = {
    "mistral-7b-v0.3-serve-1chip": (32, 512, 8, 4, 3072),
    "yi-1.5-6b-serve-1chip": (24, 256, 4, 8, 6144),
}
# (vocabulary, FFN width) of the same two; hidden 4096, 16 layers, chunks of 128 tokens
CELL_WIDTHS = {
    "mistral-7b-v0.3-serve-1chip": (32768, 14336),
    "yi-1.5-6b-serve-1chip": (64000, 11008),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — whatever keeps the compiler from describing the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip: keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("config", sorted(CELL_SHAPES))
def test_paged_decode_attention_compiles_for_v5e_under_its_name(config, one_chip, no_compile_cache):
    import jax
    import jax.numpy as jnp

    from modal_tpu.ops.paged_attention import paged_decode_attention

    slots, pages_per_slot, n_kv, n_rep, pool_pages = CELL_SHAPES[config]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q = shape((slots, n_kv, n_rep, 128), jnp.bfloat16)
    pool = shape((pool_pages, 16, n_kv, 128), jnp.bfloat16)
    page_table = shape((slots, pages_per_slot), jnp.int32)
    seq_lens = shape((slots,), jnp.int32)

    def program(*args):  # jit names the program after this, so the kernel's name below is its own
        return paged_decode_attention(*args)

    compiled = jax.jit(program).lower(q, pool, pool, page_table, seq_lens).compile()
    # the Mosaic kernel is in the program, as an instruction of the name the profiler's trace shows
    # (without `name=` on the pallas_call XLA calls it `closed_call.<n>`)
    calls = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and re.search(r"%paged_decode_attention(\.\d+)? = ", calls[0]), calls


PAGE = 16
# the third configuration's engine (benchmark/configs/mimo-v2-flash-serve-1chip-ep16.json): 128 slots,
# a pool a layer group, the four consecutive window layers' stacked as [4, 1169, ...]
MIMO_CELL = dict(
    model=dict(name="mimo-v2-flash", n_layers=7, vocab_size=19072, max_seq_len=8192, n_experts_held=16),
    slots=128, pool_pages=16385, window_pool_pages=1169,
)


# the fourth configuration's (benchmark/configs/laguna-xs.2-serve-1chip.json): every expert of a layer held
LAGUNA_CELL = dict(
    model=dict(name="laguna-xs.2", n_layers=5, max_seq_len=8192), slots=128, pool_pages=8193, window_pool_pages=4241,
)
# the fifth configuration's (benchmark/configs/a.x-k1-serve-1chip-ep16.json): one pool of latent rows, stored 640 wide
AXK1_CELL = dict(
    model=dict(name="a.x-k1", n_layers=7, vocab_size=20480, max_seq_len=8192, n_experts_held=12), slots=64, pool_pages=20481,
    window_pool_pages=None,
)
TWO_POOL_CELLS = {"mimo-v2-flash-serve-1chip-ep16": MIMO_CELL, "laguna-xs.2-serve-1chip": LAGUNA_CELL, "a.x-k1-serve-1chip-ep16": AXK1_CELL}


def lower_step(program, config, sharding):
    """`paged_prefill` (one bucket: a chunk of 128) or `paged_decode_step`
    (the Pallas kernel) of a cell, lowered for the described chip over shapes
    alone: what `llm_service` would hold there, nothing allocated. Returns
    (cfg, params, cache, lowered)."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models import paged_kv
    from modal_tpu.models.llama import get_config, init_params

    if config in TWO_POOL_CELLS:
        cell = TWO_POOL_CELLS[config]
        cfg = get_config(cell["model"])
        slots, pool_pages, window_pool_pages = cell["slots"], cell["pool_pages"], cell["window_pool_pages"]
    else:
        slots, pages_per_slot, n_kv, n_rep, pool_pages = CELL_SHAPES[config]
        vocab, ffn = CELL_WIDTHS[config]
        cfg = get_config(
            "llama3-8b", vocab_size=vocab, n_layers=16, n_heads=n_kv * n_rep, n_kv_heads=n_kv, ffn_dim=ffn,
            max_seq_len=pages_per_slot * PAGE,
        )
        window_pool_pages = None

    def shaped(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def described(make):
        return jax.tree.map(lambda a: shaped(a.shape, a.dtype), jax.eval_shape(make))

    params = described(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = described(lambda: paged_kv.PagedKVCache.create(cfg, slots, pool_pages, PAGE, window_num_pages=window_pool_pages))
    if program == "paged_prefill":
        lowered = paged_kv.paged_prefill.lower(params, cfg, shaped((128,)), shaped(()), cache, shaped(()), shaped(()))
    else:
        lowered = paged_kv.paged_decode_step.lower(params, cfg, shaped((slots,)), cache, shaped((slots,), jnp.bool_), attn_impl="kernel")
    return cfg, params, cache, lowered


@pytest.mark.parametrize("config", sorted(CELL_SHAPES))
def test_paged_prefill_compiles_for_v5e_with_nothing_of_the_span_s_size(config, one_chip, no_compile_cache):
    import jax

    slots, pages_per_slot, n_kv, n_rep, pool_pages = CELL_SHAPES[config]
    vocab, _ffn = CELL_WIDTHS[config]
    s_pad, page = 128, PAGE
    kv_span = pages_per_slot * page
    cfg, params, cache, lowered = lower_step("paged_prefill", config, one_chip)
    assert cache.page_table.shape == (slots, pages_per_slot)
    text = lowered.compile().as_text()
    # the name `prefill_chunk_ms` and the breakdown find the program by
    assert text.startswith("HloModule jit_paged_prefill,")
    # every array an instruction makes or a computation takes: `type[dims]`. The scores are float32
    # and a mask float32, integer or boolean: no such array may be as large as the span-wide ones
    # were, but a weight where a fusion reads it converted (the pool and the weights are bfloat16)
    weights = {a.shape[skip:] for a in jax.tree.leaves(params) for skip in (0, 1)}
    shapes = {
        (dtype, tuple(int(d) for d in dims.split(",")))
        for dtype, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]+)\]", text)
    }
    assert len(shapes) > 50 and ("bf16", (s_pad, cfg.dim)) in shapes and ("f32", (vocab,)) in shapes
    scores = cfg.n_heads * s_pad * kv_span
    for dtype, shape in shapes:
        assert dtype == "bf16" or math.prod(shape) < scores or shape in weights, (dtype, shape)
    # the pool is gathered a block of pages at a time, never a row's whole span
    pool_gathers = [
        int(pages)
        for pages in re.findall(rf"= bf16\[(\d+),{page},{n_kv},128\]\S* gather\([^\n]*slice_sizes={{1,{page},{n_kv},128}}", text)
    ]
    assert len(pool_gathers) == 2 and all(pages * page <= 1024 < kv_span for pages in pool_gathers), pool_gathers
    # the loop over KV blocks is there, with a trip count that is data: a `while` inside the layers' `while`
    assert len(re.findall(r" while\(", text)) >= 2


# what may have a result as large as a KV pool, or as one layer's share of one: the program's own
# arguments and the loop's plumbing (no bytes move), and the scatter of the new rows, in place
POOL_SIZED_MAY_BE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while", "scatter", "fusion"}
CARRIED_POOL_CASES = [
    (config, program) for config in sorted(CELL_SHAPES) for program in ("paged_decode_step", "paged_prefill")
] + [("mimo-v2-flash-serve-1chip-ep16", "paged_decode_step"), ("laguna-xs.2-serve-1chip", "paged_decode_step"), ("laguna-xs.2-serve-1chip", "paged_prefill")] + [
    ("a.x-k1-serve-1chip-ep16", "paged_decode_step"), ("a.x-k1-serve-1chip-ep16", "paged_prefill"),
]


@pytest.mark.parametrize("config,program", CARRIED_POOL_CASES)
def test_the_jitted_steps_compile_for_v5e_with_no_pool_sized_copy(config, program, one_chip, no_compile_cache):
    """The layer loop carries the KV pool and a layer's pages are addressed
    in it (`paged_kv._run_layers`): the compiled step holds no copy, slice or
    update-slice of a pool's or of one layer's pool's size, fused or not, only
    the scatter of the new rows on the donated buffer; its temporaries are a
    sliver of a pool and its outputs alias the pools. Scanned as inputs and
    stacked as outputs, the pools cost four whole-pool operations a program
    and a second pool of temporaries (PERF.md section 6, PR 31). The third
    configuration has a group of four window layers among single ones, the
    fourth a group of three with 1.6 GB of expert matrices a layer, which
    XLA's dots read in place from the scanned stack; the fifth one pool of
    latent rows (no value pool), stored 640 wide so that it goes into its
    kernel as it lies."""
    import jax

    cfg, _params, cache, lowered = lower_step(program, config, one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    # (c) the names the benchmark's readers find the program and the kernel by
    assert text.startswith(f"HloModule jit_{program},")
    if program == "paged_decode_step":
        calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
        names = {re.search(r"%([a-z_]+)[.\d]* = ", line).group(1) for line in calls}
        assert names == {"paged_decode_attention" + ("_" + k.attn_name if k.attn_name else "") for k in cfg.layer_kinds}, names
        assert names in ({"paged_decode_attention"}, {"paged_decode_attention_full", "paged_decode_attention_swa"}, {"paged_decode_attention_mla"})

    # (a) every instruction, in fusions too: `%name = type[dims]{layout} opcode(`, or a tuple of such types
    pools = jax.tree.leaves((cache.k_pages, cache.v_pages))
    pool_sized = {math.prod(a.shape) for a in pools} | {math.prod(a.shape[1:]) for a in pools}
    pool_bytes = sum(math.prod(a.shape) * a.dtype.itemsize for a in pools)
    seen = set()
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z\-]+)\(", line)
        if not found:
            continue
        name, result, opcode = found.groups()
        sizes = {math.prod(int(d) for d in dims.split(",")) for dims in re.findall(r"\w+\[([\d,]+)\]", result)}
        if not sizes & pool_sized:
            continue
        seen.add(opcode)
        assert opcode in POOL_SIZED_MAY_BE, f"{name}: a {opcode} as large as a KV pool or one layer of it: {line[:200]}"
        if opcode == "fusion":  # the row scatter and nothing else: named by its scope, in place on its first operand
            assert "kv_write/scatter" in line and '"aliasing_operands":{"lists":[{"indices":["0"' in line, line[:400]
    assert {"parameter", "while", "scatter"} <= seen, seen  # the pools were found at all

    # (b) no second pool among the temporaries, and the outputs are the donated pools
    memory = compiled.memory_analysis()
    one_pool = max(math.prod(a.shape) * a.dtype.itemsize for a in pools)
    if cfg.uniform:
        assert memory.temp_size_in_bytes < one_pool / 100, memory.temp_size_in_bytes
    else:  # XLA still transposes MiMo's wq a layer (PERF.md section 7): 100 MB, a pool is 268 MB; Laguna's chunk
        # holds 256 experts' activations (35 MB at 128 rows), a pool is 417 MB and a layer's expert matrices 537 MB each
        assert memory.temp_size_in_bytes < 0.2e9, memory.temp_size_in_bytes
    assert memory.alias_size_in_bytes >= pool_bytes, (memory.alias_size_in_bytes, pool_bytes)


# (KV heads, query heads a KV head, pool pages, window, sink, name): the two decode kernels of the
# configuration with window and full attention side by side (benchmark/configs/
# mimo-v2-flash-serve-1chip-ep16.json): 128 slots, 512 pages a slot, values 128 wide
TWO_WIDTH_KERNELS = {
    "full": (4, 16, 16385, 0, False, "paged_decode_attention_full"),
    "swa": (8, 8, 1169, 128, True, "paged_decode_attention_swa"),
}


@pytest.mark.parametrize("stored", [256, 192], ids=["keys-stored-256", "keys-192-as-published"])
@pytest.mark.parametrize("kind", sorted(TWO_WIDTH_KERNELS))
def test_the_two_width_decode_kernels_compile_for_v5e_and_only_padded_keys_go_in_as_they_lie(kind, stored, one_chip, no_compile_cache):
    """Mosaic takes keys wider than values, a window and a sink as written,
    and stored 256 wide (`paged_kv.k_cache_dim`) the pool goes in as it lies:
    no copy of it in the program. Stored 192 wide a page is no whole number
    of 128-lane tiles: the kernel's page copies are refused (the grid kernel
    before PR 33 compiled, and XLA handed it a transposed COPY of the whole
    pool at every call): why keys are stored padded."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config
    from modal_tpu.models.paged_kv import k_cache_dim
    from modal_tpu.ops.paged_attention import paged_decode_attention

    n_kv, n_rep, pool, window, sink, name = TWO_WIDTH_KERNELS[kind]
    assert k_cache_dim(get_config("mimo-v2-flash")) == 256 and k_cache_dim(get_config("llama3-8b")) == 128

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        shaped((128, n_kv, n_rep, stored)), shaped((pool, 16, n_kv, stored)), shaped((pool, 16, n_kv, 128)),
        shaped((128, 512), jnp.int32), shaped((128,), jnp.int32),
    ]
    if sink:
        args.append(shaped((n_kv, n_rep), jnp.float32))

    def call(q, k, v, table, lens, sinks=None):
        return paged_decode_attention(q, k, v, table, lens, window=window, sink=sinks, scale=192**-0.5, name=name)

    if stored == 192:
        with pytest.raises(Exception, match=r"must be aligned to tiling \(128\), but is 192"):
            jax.jit(call).lower(*args).compile()
        return
    text = jax.jit(call).lower(*args).compile().as_text()
    # the kernel takes and gives the query heads as one dimension (the wrapper's reshapes are bitcasts)
    assert re.search(rf"%{name}[.\d]* = bf16\[128,{n_kv * n_rep},128\]", text), "the kernel lost its name or its output width"
    assert not re.findall(rf"= bf16\[{pool},16,{n_kv},{stored}\]\S* copy\(", text)


# the serialized Mosaic module of one kernel call in the lowered step, in characters of its line: the
# kernel as written reads 20,808 (dense) to 23,916 (a window and a sink); the grid kernel before it
# read 9,962 to 12,768. Loops over blocks, pages and chunks are rolled (`fori_loop`); unrolled over the
# 16 pages of a block or the 8 KV heads the module grows several times over, and every boot pays for
# tracing, lowering and compiling it, cache or no cache (PERF.md section 6, PR 33, step 1)
MOSAIC_CALL_CHARS_AT_MOST = 48_000


@pytest.mark.parametrize("config", sorted(CELL_SHAPES) + sorted(TWO_POOL_CELLS))
def test_the_decode_step_holds_one_small_mosaic_kernel_a_layer_group(config, one_chip):
    """`paged_decode_step` lowered at a cell's real shapes (nothing compiled):
    one Mosaic call a layer group (the scan's body is traced once), each under
    the size the finished kernel reads with headroom, so a rewrite that unrolls
    its pages or heads in Python, or compiles a variant a bucket of live
    length, fails here and not as seconds of a boot on the chip."""
    cfg, _params, _cache, lowered = lower_step("paged_decode_step", config, one_chip)
    calls = [line for line in lowered.as_text().splitlines() if "@tpu_custom_call" in line]
    assert len(calls) == len(cfg.layer_groups), (len(calls), cfg.layer_groups)
    assert all(5_000 < len(line) < MOSAIC_CALL_CHARS_AT_MOST for line in calls), [len(line) for line in calls]


def test_the_decode_kernel_compiles_for_v5e_at_six_query_heads_a_kv_head(one_chip, no_compile_cache):
    """48 query heads over 8 KV heads: the `[heads, rows]` score tile and the
    mask of a head's own KV rows at a head count that is no power of two."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.ops.paged_attention import paged_decode_attention

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(q, k, v, table, lens):
        return paged_decode_attention(q, k, v, table, lens, name="paged_decode_attention_full")

    text = jax.jit(call).lower(
        shape((128, 8, 6, 128)), shape((8193, 16, 8, 128)), shape((8193, 16, 8, 128)), shape((128, 512), jnp.int32), shape((128,), jnp.int32)
    ).compile().as_text()
    assert re.search(r"%paged_decode_attention_full[.\d]* = bf16\[128,48,128\]", text)


@pytest.mark.parametrize("stored", [640, 576], ids=["row-stored-640", "row-576-as-the-model-has-it"])
def test_the_latent_decode_kernel_compiles_for_v5e_and_only_a_padded_row_goes_in_as_it_lies(stored, one_chip, no_compile_cache):
    """`paged_decode_attention_mla` at the fifth configuration's shapes (64
    slots, 64 heads, 20,481 pages of 16 rows): Mosaic takes it as written with
    5.2 + 4.2 MB of queries and outputs resident, under its name, and the pool
    goes in with no copy. A row 576 wide (512 + 64, the model's own) is no
    whole number of 128-lane tiles: the page copies are refused, as for keys of
    192: why `paged_kv.k_cache_dim` stores a latent row 640 wide."""
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config
    from modal_tpu.models.paged_kv import k_cache_dim
    from modal_tpu.ops.paged_attention import paged_decode_attention_mla

    assert k_cache_dim(get_config("a.x-k1")) == 640

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [shaped((64, 64, stored)), shaped((20481, 16, stored)), shaped((64, 512), jnp.int32), shaped((64,), jnp.int32)]

    def call(q, rows, table, lens):
        return paged_decode_attention_mla(q, rows, table, lens, latent=512, scale=0.130861)

    if stored == 576:
        with pytest.raises(Exception, match=r"aligned to tiling \(128\)"):
            jax.jit(call).lower(*args).compile()
        return
    text = jax.jit(call).lower(*args).compile().as_text()
    assert re.search(r"%paged_decode_attention_mla[.\d]* = bf16\[64,64,512\]", text), "the kernel lost its name or its output width"
    assert not re.findall(rf"= bf16\[20481,16,{stored}\]\S* copy\(", text)
