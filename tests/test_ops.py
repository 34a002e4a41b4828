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


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="TPU-compiled path needs a real chip")
def test_paged_decode_attention_tpu_compiled_equivalence():
    """The COMPILED paged decode kernel against the gather reference at
    Llama-3-8B's head geometry (8 KV heads x 4 query heads of 128, pages of
    16 bf16 rows), over a shuffled page table and ragged slot lengths."""
    from modal_tpu.models.paged_kv import _paged_attention
    from modal_tpu.ops.paged_attention import paged_decode_attention

    slots, pps, n_kv, n_rep, hd, page = 8, 64, 8, 4, 128, 16
    pages = slots * pps + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (slots, n_kv, n_rep, hd), jnp.bfloat16)
    k_pages = jax.random.normal(kk, (pages, page, n_kv, hd), jnp.bfloat16)
    v_pages = jax.random.normal(kv, (pages, page, n_kv, hd), jnp.bfloat16)
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.permutation(pages - 1).reshape(slots, pps).astype(np.int32) + 1)
    lens = jnp.asarray(rng.integers(0, pps * page - 1, size=(slots,)).astype(np.int32))
    out = jax.jit(paged_decode_attention)(q, k_pages, v_pages, table, lens)
    kv_pos = jnp.arange(pps * page, dtype=jnp.int32)[None, None, None, :]
    mask = jnp.where(kv_pos <= lens[:, None, None, None], 0.0, -jnp.inf).astype(jnp.float32)
    ref = _paged_attention(q.reshape(slots, 1, n_kv * n_rep, hd), k_pages, v_pages, table, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(ref.shape), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


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
