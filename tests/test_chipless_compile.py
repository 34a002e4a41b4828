"""Compiles for the chip without the chip: the kernel of the served path at
the benchmark's real shapes, for a TPU v5e that is described and not attached
(the TPU's compiler is installed where the tests run). Nothing runs, so this
says nothing about results or times: it guards that Mosaic still takes the
kernel as written and that the kernel keeps its stable name.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, each xdist worker imports every
test file, and only the worker that runs THIS file may make the call. Keep
every such compile in this one file.
"""

import os
import re

import pytest

# (slots, pages a slot, KV heads, query heads a KV head, pool pages): the engine geometry of the
# benchmark's two configurations (benchmark/configs/*.json), pages of 16 tokens, heads of 128
CELL_SHAPES = {
    "mistral-7b-v0.3-serve-1chip": (32, 512, 8, 4, 3072),
    "yi-1.5-6b-serve-1chip": (24, 256, 4, 8, 6144),
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
