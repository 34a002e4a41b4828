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

Keys and values may differ in width (`k_pages` [.., hd], `v_pages` [.., vd]),
a window layer walks only the pages its window can touch, starting at its
first live position, and a layer with a learned sink adds one logit a head
to the softmax's denominator; the dense models use none of the three and
compile to the kernel they always had.

Pages past the slot's live length are skipped (`pl.when` on the page's base
position vs `seq_lens[s]`), so a slot 3 pages into a 64-page span pays 3
page DMAs, not 64. Positions inside the last live page are masked by global
position exactly like the dense reference.

The same kernel runs `interpret=True` on CPU CI, pinned against the dense
`KVCache` reference in tests/test_serving.py, and compiled under Mosaic on a
TPU, where it matches the gather path (tests/test_ops.py TPU-gated test;
`chip_smoke.py` compares decode-step logits).

Who calls it, and over what: only `paged_decode_step`, through
`paged_kv._paged_attention`, where its static `attn_impl` says "kernel" or
"kernel_interpret" (the serving engine asks `paged_kv.resolve_attn_impl()`
once at construction: the kernel on a TPU, the gather path elsewhere); a
prefill chunk and the verify step never run it. The layer loop hands it a
layer GROUP's pool whole,
`[layers * P, page, n_kv, hd]`, and a page table whose ids are already
shifted to the layer's place in it (`paged_kv._run_layers`): to this module
that is a pool and a table like any other, so nothing here knows of layers
and no layer's pages are sliced out for a call. This module only provides
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
    v_ref,  # [1, page, n_kv, vd] — values may be narrower than keys
    *rest,  # (sink_ref [n_kv, n_rep, 1] where the layer has one,) o_ref, m_ref, l_ref, acc_ref
    page: int,
    pages_walked: int,
    window: int,
    scale: float,
):
    # o_ref [1, n_kv, n_rep, vd]; VMEM scratch (persists across the page-
    # dimension grid steps): m_ref, l_ref [n_kv, n_rep, 1] running max and
    # sum, acc_ref [n_kv, n_rep, vd] weighted-value accumulator
    sink_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = seq_lens_ref[s]  # the decode token's position (kv <= q_pos attended)
    walked = p
    if window:
        p = _first_live_page(q_pos, window, page) + p  # the p-th page of the window

    @pl.when(p * page <= q_pos)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [n_kv, n_rep, hd]
        k = k_ref[0].astype(jnp.float32)  # [page, n_kv, hd]
        v = v_ref[0].astype(jnp.float32)
        s_log = jnp.einsum("knd,pkd->knp", q, k) * scale  # [n_kv, n_rep, page]
        kv_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        seen = kv_pos <= q_pos
        if window:
            seen = seen & (kv_pos > q_pos - window)
        s_log = jnp.where(seen, s_log, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_log, axis=-1, keepdims=True))
        p_exp = jnp.exp(s_log - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p_exp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum("knp,pkd->knd", p_exp, v)
        m_ref[...] = m_new

    @pl.when(walked == pages_walked - 1)
    def _finalize():
        if sink_ref is None:
            l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        else:
            # the sink joins the denominator and takes no value (l > 0 with it)
            l_safe = l_ref[...] + jnp.exp(sink_ref[...] - m_ref[...])
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _first_live_page(q_pos, window: int, page: int):
    """The page that holds the oldest key a window layer's query at q_pos
    sees (position q_pos - window + 1, or 0)."""
    return jnp.maximum(q_pos - (window - 1), 0) // page


def paged_decode_attention(
    q: jax.Array,  # [S, n_kv, n_rep, hd]
    k_pages: jax.Array,  # [P, page, n_kv, hd]
    v_pages: jax.Array,  # [P, page, n_kv, vd]
    page_table: jax.Array,  # [S, pages_per_slot] int32
    seq_lens: jax.Array,  # [S] int32 — each slot's decode position
    *,
    window: int = 0,  # > 0: the query sees its last `window` positions only
    sink: jax.Array | None = None,  # [n_kv, n_rep] float32: a logit a head in the denominator
    scale: float | None = None,  # 1/sqrt(hd) unless q and the keys are stored padded past the model's width
    name: str = "paged_decode_attention",  # the kernel's name in HLO metadata and profiler traces
    interpret: bool = False,
) -> jax.Array:
    """One decode step's attention over paged KV. Returns [S, n_kv, n_rep, vd]
    (q's layout at the values' width). Numerics match the dense
    gather+softmax reference (fp32 statistics); inactive/scratch slots
    produce garbage that callers must not read — identical contract to the
    gather path.

    A window layer's grid walks the pages its window can touch (the window
    and the page being written: `paged_kv.window_pages_per_slot`), starting
    at the page of its first live position, and not the slot's whole row: pages behind the window may have gone back to
    the pool (the table entry is stale) and are never addressed."""
    s, n_kv, n_rep, hd = q.shape
    page, vd = k_pages.shape[1], v_pages.shape[-1]
    pages_per_slot = page_table.shape[1]
    pages_walked = min(pages_per_slot, -(-window // page) + 1) if window else pages_per_slot

    def page_of(si, pi, pt, lens):
        if window:
            pi = jnp.minimum(_first_live_page(lens[si], window, page) + pi, pages_per_slot - 1)
        return (pt[si, pi], 0, 0, 0)

    def whole(si, pi, pt, lens):
        return (si, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, n_kv, n_rep, hd), whole),
        # the paged part: the index map dereferences the prefetched page
        # table, so the pipeline DMAs page `page_table[s, p]` and only
        # that page for grid step (s, p)
        pl.BlockSpec((1, page, n_kv, hd), page_of),
        pl.BlockSpec((1, page, n_kv, vd), page_of),
    ]
    operands = [q, k_pages, v_pages]
    if sink is not None:
        in_specs.append(pl.BlockSpec((n_kv, n_rep, 1), lambda si, pi, pt, lens: (0, 0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(n_kv, n_rep, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, pages_walked),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv, n_rep, vd), whole),
        scratch_shapes=[
            pltpu.VMEM((n_kv, n_rep, 1), jnp.float32),
            pltpu.VMEM((n_kv, n_rep, 1), jnp.float32),
            pltpu.VMEM((n_kv, n_rep, vd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page=page, pages_walked=pages_walked, window=window,
            scale=scale or 1.0 / math.sqrt(hd),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_kv, n_rep, vd), q.dtype),
        interpret=interpret,
        name=name,
    )(page_table, seq_lens, *operands)
