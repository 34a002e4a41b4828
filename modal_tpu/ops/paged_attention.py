"""Pallas paged-attention decode kernel: stream KV pages HBM→VMEM.

The gather path in models/paged_kv.py materializes every slot's whole page
span (`k_pages[page_table]` → `[S, pages_per_slot × page, n_kv, hd]`) in HBM
before attending — for decode (one query token per slot) that is a full copy
of the attended KV per step. This kernel instead walks the page table with
**scalar prefetch** (`pltpu.PrefetchScalarGridSpec`): the grid is
`(slots, pages_per_slot)` and each step's BlockSpec index map reads
`page_table[s, p]` to DMA exactly one `[page, n_kv, hd]` KV page into VMEM,
accumulating online-softmax statistics (running max / sum / weighted value,
fp32) in VMEM scratch — the flash-attention trade applied to the paged
layout, and no `[S, K]` score or gathered-KV intermediate ever exists in HBM.

GQA: q arrives `[slots, n_kv, n_rep, hd]` (grouped by kv head) so one grid
cell contracts one kv head's page against its `n_rep` query heads.

Pages past the slot's live length are skipped (`pl.when` on the page's base
position vs `seq_lens[s]`), so a slot 3 pages into a 64-page span pays 3
page DMAs, not 64. Positions inside the last live page are masked by global
position exactly like the dense reference.

The same kernel runs `interpret=True` on CPU CI, pinned against the dense
`KVCache` reference in tests/test_serving.py, and compiled under Mosaic on a
TPU, where it matches the gather path (tests/test_ops.py TPU-gated test;
`chip_smoke.py` compares decode-step logits). Selection lives in
models/paged_kv.py (`MODAL_TPU_PAGED_KERNEL`); this module only provides
the op.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(
    # scalar prefetch (available to index maps before the body runs)
    page_table_ref,  # [S, pages_per_slot] int32
    seq_lens_ref,  # [S] int32
    # blocks
    q_ref,  # [1, n_kv, n_rep, hd] — this slot's single query token
    k_ref,  # [1, page, n_kv, hd] — the page the index map DMA'd in
    v_ref,  # [1, page, n_kv, hd]
    o_ref,  # [1, n_kv, n_rep, hd]
    # VMEM scratch (persist across the page-dimension grid steps)
    m_ref,  # [n_kv, n_rep, 1] running max
    l_ref,  # [n_kv, n_rep, 1] running sum
    acc_ref,  # [n_kv, n_rep, hd] weighted-value accumulator
    *,
    page: int,
    pages_per_slot: int,
):
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = seq_lens_ref[s]  # the decode token's position (kv <= q_pos attended)

    @pl.when(p * page <= q_pos)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [n_kv, n_rep, hd]
        k = k_ref[0].astype(jnp.float32)  # [page, n_kv, hd]
        v = v_ref[0].astype(jnp.float32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s_log = jnp.einsum("knd,pkd->knp", q, k) * scale  # [n_kv, n_rep, page]
        kv_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        s_log = jnp.where(kv_pos <= q_pos, s_log, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_log, axis=-1, keepdims=True))
        p_exp = jnp.exp(s_log - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p_exp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum("knp,pkd->knd", p_exp, v)
        m_ref[...] = m_new

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # [S, n_kv, n_rep, hd]
    k_pages: jax.Array,  # [P, page, n_kv, hd]
    v_pages: jax.Array,
    page_table: jax.Array,  # [S, pages_per_slot] int32
    seq_lens: jax.Array,  # [S] int32 — each slot's decode position
    *,
    interpret: bool = False,
) -> jax.Array:
    """One decode step's attention over paged KV. Returns [S, n_kv, n_rep, hd]
    (same layout as q). Numerics match the dense gather+softmax reference
    (fp32 statistics); inactive/scratch slots produce garbage that callers
    must not read — identical contract to the gather path."""
    s, n_kv, n_rep, hd = q.shape
    page = k_pages.shape[1]
    pages_per_slot = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, pages_per_slot),
        in_specs=[
            pl.BlockSpec((1, n_kv, n_rep, hd), lambda si, pi, pt, lens: (si, 0, 0, 0)),
            # the paged part: the index map dereferences the prefetched page
            # table, so the pipeline DMAs page `page_table[s, p]` and only
            # that page for grid step (s, p)
            pl.BlockSpec((1, page, n_kv, hd), lambda si, pi, pt, lens: (pt[si, pi], 0, 0, 0)),
            pl.BlockSpec((1, page, n_kv, hd), lambda si, pi, pt, lens: (pt[si, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_kv, n_rep, hd), lambda si, pi, pt, lens: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, n_rep, 1), jnp.float32),
            pltpu.VMEM((n_kv, n_rep, 1), jnp.float32),
            pltpu.VMEM((n_kv, n_rep, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, pages_per_slot=pages_per_slot),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_kv, n_rep, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",  # the kernel's name in HLO metadata and profiler traces
    )(page_table, seq_lens, q, k_pages, v_pages)
