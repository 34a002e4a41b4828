"""Pallas kernel tests (interpret mode on CPU; compiled on real TPU)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modal_tpu.ops.attention import flash_attention_pallas
from modal_tpu.parallel.ring_attention import full_causal_attention


@pytest.mark.parametrize("shape", [(2, 256, 4, 64), (1, 128, 2, 32)])
def test_flash_attention_causal_matches_reference(shape):
    B, S, H, D = shape
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in jax.random.split(key, 3))
    ref = full_causal_attention(q, k, v)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


def test_flash_attention_noncausal():
    B, S, H, D = 1, 256, 2, 64
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in jax.random.split(key, 3))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    B, S, H, D = 1, 128, 2, 64
    key = jax.random.PRNGKey(2)
    q, k, v = (
        jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in jax.random.split(key, 3)
    )
    ref = full_causal_attention(q, k, v)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), rtol=5e-2, atol=5e-2
    )


def test_flash_attention_rejects_nondivisible():
    q = jnp.zeros((1, 192, 2, 32))  # 192 % 128 != 0 after clamping
    with pytest.raises(ValueError, match="divide"):
        flash_attention_pallas(q, q, q, block_q=128, block_k=128, interpret=True)


@pytest.mark.parametrize("shape", [(2, 256, 2, 64), (1, 128, 4, 32)])
def test_flash_attention_backward_matches_reference(shape):
    """The pallas backward (dq/dkv kernels via custom_vjp) must match the
    einsum attention's autodiff gradients."""
    from modal_tpu.ops.attention import flash_attention_causal

    B, S, H, D = shape
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in jax.random.split(key, 3))
    w = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_causal(q, k, v, 128, 128, True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(full_causal_attention(q, k, v) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} mismatch",
        )


def test_flash_attention_backward_bf16():
    from modal_tpu.ops.attention import flash_attention_causal

    B, S, H, D = 1, 128, 2, 64
    key = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in jax.random.split(key, 3))

    def loss(q, k, v):
        return jnp.sum(flash_attention_causal(q, k, v, 128, 128, True).astype(jnp.float32))

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(full_causal_attention(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    for gf, grr in zip(g, gr):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(grr, np.float32), rtol=1e-1, atol=1e-1
        )


@pytest.mark.slow  # re-tier (ISSUE 11): ~15 s; kernel numerics stay in the fast flash tests
def test_flash_attention_in_training_step():
    """flash attention as attn_impl in the full train step: loss finite,
    grads flow (the kernel is differentiable end-to-end)."""
    from functools import partial

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.ops.attention import flash_attention_causal
    from modal_tpu.parallel.train import loss_fn

    cfg = get_config("debug-1l", max_seq_len=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size, jnp.int32)

    def attn_impl(q, k, v, mask):
        assert mask is None  # training path passes the causal contract
        return flash_attention_causal(q, k, v, 128, 128, True)

    loss, grads = jax.value_and_grad(loss_fn)(params, cfg, tokens, False, attn_impl)
    assert float(loss) > 0 and np.isfinite(float(loss))
    gnorm = float(jax.tree_util.tree_reduce(lambda a, b: a + jnp.sum(jnp.abs(b)), grads, 0.0))
    assert np.isfinite(gnorm) and gnorm > 0


# The three tests below need a chip. tests/conftest.py holds every test run
# to the CPU, so on the machine with the chip they are run without it:
#   PYTHONPATH=. python -m pytest --noconftest tests/test_ops.py -q -k tpu_compiled


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="TPU-compiled path needs a real chip")
def test_flash_attention_tpu_compiled_equivalence():
    """Numeric equivalence of the COMPILED (non-interpret) kernels on real
    TPU hardware."""
    from modal_tpu.ops.attention import flash_attention_causal

    B, S, H, D = 2, 256, 4, 64
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in jax.random.split(key, 3))
    ref = full_causal_attention(q, k, v)
    out = jax.jit(lambda q, k, v: flash_attention_causal(q, k, v, 128, 128, False))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), rtol=5e-2, atol=5e-2
    )
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention_causal(q, k, v, 128, 128, False).astype(jnp.float32)))(q, k, v)
    assert np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="TPU-compiled path needs a real chip")
def test_flash_attention_tpu_compiled_at_vmem_budget_edge():
    """The longest sequence `_fits_vmem_budget` admits at Llama head width
    must compile forward AND backward: the dispatch sends every admitted
    shape to the kernels, so a budget above Mosaic's real limit is a crash."""
    from modal_tpu.ops.attention import (
        VMEM_STAGED_BUDGET_BYTES,
        _fits_vmem_budget,
        flash_attention_causal,
    )

    d = 128
    s = VMEM_STAGED_BUDGET_BYTES // (2 * d * 2 + 8) // 128 * 128
    q = jax.random.normal(jax.random.PRNGKey(3), (1, s, 1, d), jnp.bfloat16)
    assert _fits_vmem_budget(q, q) and not _fits_vmem_budget(jnp.zeros((1, s + 128, 1, d), q.dtype), q)
    out = jax.jit(lambda q: flash_attention_causal(q, q, q, 128, 128, False))(q)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    g = jax.jit(jax.grad(lambda q: jnp.sum(flash_attention_causal(q, q, q, 128, 128, False).astype(jnp.float32))))(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()


PAGE = 16


def paged_problem(seed, slots, pages_per_slot, n_kv, n_rep, hd, vd, dtype, sink=False):
    """(q, k_pages, v_pages, table, sinks): a pool of noise, every slot's row a
    run of pages of its own in no order (page 0, the scratch page, in no row)."""
    pool = slots * pages_per_slot + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (slots, n_kv, n_rep, hd), dtype)
    k_pages = jax.random.normal(keys[1], (pool, PAGE, n_kv, hd), dtype)
    v_pages = jax.random.normal(keys[2], (pool, PAGE, n_kv, vd), dtype)
    table = (jax.random.permutation(keys[3], pool - 1) + 1).reshape(slots, pages_per_slot).astype(jnp.int32)
    sinks = jax.random.normal(keys[4], (n_kv, n_rep), jnp.float32) if sink else None
    return q, k_pages, v_pages, table, sinks


def paged_reference(q, k_pages, v_pages, table, positions, window=0, sinks=None, scale=None):
    """The dense float32 reference of one decode step's attention, a slot at a
    time on the host: the slot's live positions gathered through its row, one
    softmax (a sink: one more column in the denominator); zeros for a slot
    that does not decode (a negative position)."""
    q, k_pages, v_pages = (np.asarray(a.astype(jnp.float32)) for a in (q, k_pages, v_pages))
    table, positions = np.asarray(table), np.asarray(positions)
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    out = np.zeros(q.shape[:-1] + (v_pages.shape[-1],), np.float32)
    for s, pos in enumerate(positions):
        if pos < 0:
            continue
        live = np.arange(max(pos - window + 1, 0) if window else 0, pos + 1)
        k = k_pages[table[s, live // PAGE], live % PAGE]  # [T, n_kv, hd]
        v = v_pages[table[s, live // PAGE], live % PAGE]
        logits = np.einsum("knd,tkd->knt", q[s], k) * scale
        top = logits.max(-1, keepdims=True) if sinks is None else np.maximum(logits.max(-1, keepdims=True), np.asarray(sinks)[..., None])
        weights = np.exp(logits - top)
        total = weights.sum(-1, keepdims=True) + (0.0 if sinks is None else np.exp(np.asarray(sinks)[..., None] - top))
        out[s] = np.einsum("knt,tkd->knd", weights / total, v)
    return out


# a block of 4 pages (the module's size cut to these widths: 2 KV heads, float32), so a row of 12
# pages is three blocks and block 0 holds positions 0-63.
# (positions a slot; negative: the slot does not decode), with what the case adds
PAGED_DECODE_CASES = {
    "ragged-one-slot-far-longer-than-the-rest": dict(positions=[3, 190, 17, 40, 9]),
    "on-page-and-block-boundaries": dict(positions=[15, 16, 31, 32, 63, 64, 127, 128, 191]),
    "a-slot-that-does-not-decode": dict(positions=[70, -1, 100, 5], idle_slot=1),
    "every-slot-idle": dict(positions=[-1, -1, -1]),
    "window-whose-first-live-page-is-not-the-first-with-a-sink": dict(positions=[190, 100, 39, -1, 64, 47], window=40, sink=True),
    "window-shorter-than-a-page-with-a-sink": dict(positions=[0, 22, 96], window=5, sink=True),
    "keys-wider-than-values": dict(positions=[5, 77, 130], hd=48, vd=16),
    "eight-query-heads-a-kv-head": dict(positions=[64, 1, 150], n_rep=8),
}


@pytest.mark.parametrize("case", sorted(PAGED_DECODE_CASES))
def test_paged_decode_attention_matches_the_float32_reference(case, monkeypatch):
    """The paged decode kernel (interpret mode) against the dense float32
    reference: blocks smaller than a row, so slots end inside a block, on a
    block's last position and on the next one's first; a page behind a window
    or past a slot's position is never addressed (its table entry names no
    page of the pool)."""
    from modal_tpu.ops import paged_attention

    spec = dict(n_rep=2, hd=24, vd=24, window=0, sink=False, idle_slot=None) | PAGED_DECODE_CASES[case]
    n_kv, pages_per_slot = 2, 12
    page_bytes = PAGE * n_kv * (spec["hd"] + spec["vd"]) * 4
    monkeypatch.setattr(paged_attention, "BLOCK_BYTES", 4 * page_bytes)
    positions = np.asarray(spec["positions"], np.int32)
    q, k_pages, v_pages, table, sinks = paged_problem(
        len(case), len(positions), pages_per_slot, n_kv, spec["n_rep"], spec["hd"], spec["vd"], jnp.float32, spec["sink"]
    )
    # what a served row holds outside a slot's live pages is stale: name no page of the pool there
    first_live = np.maximum(positions - (spec["window"] - 1), 0) // PAGE if spec["window"] else np.zeros_like(positions)
    pages = np.arange(pages_per_slot)[None, :]
    dead = (pages < first_live[:, None]) | (pages > positions[:, None] // PAGE) | (positions[:, None] < 0)
    table_served = jnp.where(jnp.asarray(dead), 1_000_000, table)

    def run(rows, at):
        return np.asarray(paged_attention.paged_decode_attention(
            q, k_pages, v_pages, rows, jnp.asarray(at), window=spec["window"], sink=sinks, scale=0.2, interpret=True,
        ))

    got = run(table_served, positions)
    want = paged_reference(q, k_pages, v_pages, table, positions, spec["window"], sinks, 0.2)
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert not got[positions < 0].any()  # a slot that does not decode: zeros, whatever its row holds
    if spec["idle_slot"] is not None:
        # its neighbours' rows are the same to the bit whether it decodes or not
        other = positions.copy()
        other[spec["idle_slot"]] = 150
        decoding = np.arange(len(positions)) != spec["idle_slot"]
        assert np.array_equal(run(table, other)[decoding], got[decoding])


def test_paged_decode_attention_at_a_cell_s_page_bytes_in_bfloat16():
    """The sizes the module ships with, at the dense cells' head geometry (8 KV
    heads x 4 query heads of 128, bfloat16 pages of 64 KB): blocks of 16 pages
    over a row of 40, slots that end inside the first block, on a block's last
    position, on the next one's first and in the third block, one idle."""
    from modal_tpu.ops.paged_attention import paged_decode_attention

    positions = np.asarray([9, 255, -1, 639, 256], np.int32)
    q, k_pages, v_pages, table, _ = paged_problem(3, len(positions), 40, 8, 4, 128, 128, jnp.bfloat16)
    got = np.asarray(paged_decode_attention(q, k_pages, v_pages, table, jnp.asarray(positions), interpret=True).astype(jnp.float32))
    want = paged_reference(q, k_pages, v_pages, table, positions)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=0)
    assert not got[2].any()


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="TPU-compiled path needs a real chip")
def test_paged_decode_attention_tpu_compiled_equivalence():
    """The COMPILED paged decode kernel against the dense float32 reference at
    a benchmark cell's real geometry (mistral-7b.chat-saturated: 32 slots x 512
    pages of 16 bf16 rows, 8 KV heads x 4 query heads of 128, a pool of 3,072
    pages), over a shuffled page table, ragged lengths from one token to most
    of the row, on page and block boundaries, and some slots idle."""
    from modal_tpu.ops.paged_attention import paged_decode_attention

    slots, pps, n_kv, n_rep, hd, pool = 32, 512, 8, 4, 128, 3072
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (slots, n_kv, n_rep, hd), jnp.bfloat16)
    k_pages = jax.random.normal(kk, (pool, PAGE, n_kv, hd), jnp.bfloat16)
    v_pages = jax.random.normal(kv, (pool, PAGE, n_kv, hd), jnp.bfloat16)
    rng = np.random.default_rng(5)
    positions = rng.integers(0, 900, size=(slots,)).astype(np.int32)
    positions[:8] = [0, 15, 16, 255, 256, 6000, -1, 511]
    positions[rng.permutation(np.arange(8, slots))[:5]] = -1  # six of 32 do not decode
    # the live pages of all slots fit the pool; every other entry of a row is stale
    table = np.full((slots, pps), 1_000_000, np.int32)
    free = iter(rng.permutation(pool - 1) + 1)
    for s, pos in enumerate(positions):
        for p in range(pos // PAGE + 1 if pos >= 0 else 0):
            table[s, p] = next(free)
    out = jax.jit(paged_decode_attention)(q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(positions))
    got = np.asarray(out.astype(jnp.float32))
    want = paged_reference(q, k_pages, v_pages, table, positions)
    assert np.isfinite(got).all() and not got[positions < 0].any()
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def test_flash_attention_partial_diagonal_block():
    """block_k > block_q: the partial diagonal K block must still be visited
    (ceiling division), forward and backward."""
    from modal_tpu.ops.attention import flash_attention_causal

    B, S, H, D = 1, 256, 2, 32
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in jax.random.split(key, 3))
    ref = full_causal_attention(q, k, v)
    out = flash_attention_causal(q, k, v, 128, 256, True)  # block_k > block_q
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)
    g = jax.grad(lambda q: jnp.sum(flash_attention_causal(q, k, v, 128, 256, True)))(q)
    gr = jax.grad(lambda q: jnp.sum(full_causal_attention(q, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=2e-3, atol=2e-3)


def test_flash_attention_causal_rejects_mismatched_seq():
    from modal_tpu.ops.attention import flash_attention_causal

    q = jnp.zeros((1, 128, 2, 32))
    k = jnp.zeros((1, 256, 2, 32))
    with pytest.raises(ValueError, match="Sq == Sk"):
        flash_attention_causal(q, k, k, 128, 128, True)


def test_flash_vmem_budget_guard():
    """Sequences whose staged K/V would blow VMEM must take the einsum
    fallback instead of failing to compile (advisor r2)."""
    import jax.numpy as jnp

    from modal_tpu.ops import attention as att

    q_small = jnp.zeros((1, 1024, 4, 128), jnp.bfloat16)
    assert att._fits_vmem_budget(q_small, q_small)
    # 64k tokens × 128 dim × bf16 × (K+V) = 32 MiB > 24 MiB budget
    q_huge = jnp.zeros((1, 65536, 4, 128), jnp.bfloat16)
    assert not att._fits_vmem_budget(q_huge, q_huge)
