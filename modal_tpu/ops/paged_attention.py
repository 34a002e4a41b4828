"""Pallas paged-attention decode kernel: work in proportion to the live KV of
the slots that decode.

One call is one decode step's attention of one layer over the paged pool
`[P, page, n_kv, hd]` (values `[P, page, n_kv, vd]`, possibly narrower). The
pool stays in HBM as it lies; the page table and each slot's decode position
are scalar-prefetched, and ONE invocation (no grid) walks a flat list of work:
for every slot that decodes, its live pages in blocks of `block_pages`.

- **What is walked.** A slot at position `q_pos` walks pages
  `0 .. q_pos // page` (a window layer: from the page of its first live
  position, `_first_live_page`, at most `pages_walked`). A slot whose position
  is negative does not decode this step (`paged_decode_step` passes
  `where(active, positions, -1)`: a half-prefilled prompt has a length but no
  query) and walks nothing; its output row is zeros no caller reads. Nothing
  is paid for a dead page or an idle slot but a few scalar instructions.
- **How a block arrives.** Pages of a slot are not adjacent in the pool, so a
  block is brought into VMEM by one async copy a page for keys and one for
  values (a page is one contiguous run), issued from a `fori_loop` over the
  pages the block really has. Two buffers: block n+1's copies are started
  before block n is waited for, and the block after a slot's last is the next
  decoding slot's first, so a short slot does not drain the pipe.
- **The arithmetic.** A block's keys are read as the 2-D matrix
  `[tokens * n_kv, hd]` they already are in memory (a ref reshape, no
  relayout), and q.k is ONE matmul of all `n_kv * n_rep` query heads against
  all rows, in the pool's dtype with float32 accumulation: a row holds one KV
  head of one token, so a query head's scores against the other KV heads'
  rows are masked away with the dead positions. That spends `n_kv` times the
  needed multiply-adds on an MXU the kernel leaves idle anyway (it is bound
  by bytes), and spares every transposition of `[tokens, n_kv, hd]`. Running
  max, sum and accumulator are float32 loop carries (online softmax);
  probabilities stay float32 into the second matmul.
- **One program.** Lengths are data: the same compiled kernel serves every
  batch; the block size comes from the operands' shapes alone (a page's
  bytes). No grid, no bucket, nothing tuned at import.

GQA: q arrives `[slots, n_kv, n_rep, hd]` (grouped by kv head). Keys and
values may differ in width, a window layer walks only the pages its window
can touch, and a layer with a learned sink adds one logit a head to the
softmax's denominator.

The same kernel runs `interpret=True` on CPU CI, pinned against the dense
float32 reference (tests/test_serving.py, tests/test_serving_two_pools.py,
tests/test_ops.py), and compiled under Mosaic on a TPU, where it matches the
gather path (tests/test_ops.py TPU-gated test; `chip_smoke.py`).

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# keys + values of one block in VMEM (there are two such buffers): the kernel's one size, from
# which pages a block follow by a page's bytes (16 pages of 64 KB at 8 KV heads of 128 in bf16)
BLOCK_BYTES = 1 << 20


def _paged_decode_kernel(
    # scalar prefetch
    page_table_ref,  # [S, pages_per_slot] int32
    seq_lens_ref,  # [S] int32: the decode token's position; negative where the slot does not decode
    # operands
    q_ref,  # [S, n_kv * n_rep, hd] in VMEM
    k_hbm,  # [P, page, n_kv, hd], where it lies
    v_hbm,  # [P, page, n_kv, vd]
    *rest,  # (sink_ref [n_kv * n_rep, 1] where the layer has one,) o_ref, k_buf, v_buf, sems
    page: int,
    n_kv: int,
    n_rep: int,
    block_pages: int,
    pages_walked: int,
    window: int,
    scale: float,
):
    # o_ref [S, n_kv * n_rep, vd]; k_buf [2, block_pages * page, n_kv, hd] and v_buf alike: the two
    # blocks in flight; sems [2, 2]: keys / values by buffer
    sink_ref = rest[0] if len(rest) == 5 else None
    o_ref, k_buf, v_buf, sems = rest[-4:]
    slots, heads, hd = q_ref.shape
    vd = o_ref.shape[-1]
    rows = block_pages * page * n_kv  # (token, KV head) rows of a block

    def span(s):
        """(first page, pages) slot s walks: none where it does not decode."""
        q_pos = seq_lens_ref[s]
        first = _first_live_page(q_pos, window, page) if window else 0
        return first, jnp.where(q_pos >= 0, jnp.minimum(q_pos // page - first + 1, pages_walked), 0)

    def next_decoding(s):
        return lax.while_loop(lambda i: (i < slots) & (span(jnp.minimum(i, slots - 1))[1] == 0), lambda i: i + 1, s)

    def block_copies(s, b, buf, act):
        """Start, or wait for, the copies of block b of slot s into buffer buf: a page at a time
        through the table, only the pages the block has."""
        first, n = span(s)

        def one(i, carry):
            page_id = page_table_ref[s, first + b * block_pages + i]
            act(pltpu.make_async_copy(k_hbm.at[page_id], k_buf.at[buf, pl.ds(i * page, page)], sems.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[page_id], v_buf.at[buf, pl.ds(i * page, page)], sems.at[1, buf]))
            return carry

        lax.fori_loop(0, jnp.minimum(block_pages, n - b * block_pages), one, 0)

    start_block = functools.partial(block_copies, act=lambda copy: copy.start())
    wait_block = functools.partial(block_copies, act=lambda copy: copy.wait())

    # a block's tail may hold pages never fetched: their probabilities are 0, and 0 x what the
    # buffer held must be 0 (keys need no such care: a masked score is replaced, not multiplied)
    v_buf[...] = jnp.zeros_like(v_buf)
    first_slot = next_decoding(0)

    @pl.when(first_slot < slots)
    def _prime():
        start_block(first_slot, 0, 0)

    # a row of a block is (token, KV head); a query head sees the rows of its own KV head
    col = lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    own_head = (col % n_kv) == lax.broadcasted_iota(jnp.int32, (heads, rows), 0) // n_rep
    col_token = col // n_kv

    def slot_body(s, buf):
        first, n = span(s)
        n_blocks = pl.cdiv(n, block_pages)
        q_pos = seq_lens_ref[s]
        q = q_ref[s]  # [heads, hd]

        def block_body(b, carry):
            m_prev, l_prev, acc, buf = carry
            # the block after this one: this slot's next, or the next decoding slot's first
            last = b == n_blocks - 1
            next_s = lax.cond(last, lambda: next_decoding(s + 1), lambda: s)

            @pl.when(next_s < slots)
            def _prefetch():
                start_block(next_s, jnp.where(last, 0, b + 1), 1 - buf)

            wait_block(s, b, buf)
            k = k_buf.at[buf].reshape(rows, hd)[...]
            v = v_buf.at[buf].reshape(rows, vd)[...]
            s_log = lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            kv_pos = (first + b * block_pages) * page + col_token
            seen = own_head & (kv_pos <= q_pos)
            if window:
                seen = seen & (kv_pos > q_pos - window)
            s_log = jnp.where(seen, s_log, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s_log, axis=-1, keepdims=True))
            p_exp = jnp.where(seen, jnp.exp(s_log - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p_exp, axis=-1, keepdims=True)
            acc = acc * corr + jnp.dot(p_exp, v.astype(jnp.float32), preferred_element_type=jnp.float32)
            return m_new, l_new, acc, 1 - buf

        init = (
            jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, vd), jnp.float32),
            buf,
        )
        m, l, acc, buf = lax.fori_loop(0, n_blocks, block_body, init)
        if sink_ref is None:
            l = jnp.where(l == 0.0, 1.0, l)  # a slot that does not decode: zeros
        else:
            # the sink joins the denominator and takes no value (l > 0 with it)
            l = l + jnp.exp(sink_ref[...] - m)
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return buf

    lax.fori_loop(0, slots, slot_body, 0)


def _first_live_page(q_pos, window: int, page: int):
    """The page that holds the oldest key a window layer's query at q_pos
    sees (position q_pos - window + 1, or 0)."""
    return jnp.maximum(q_pos - (window - 1), 0) // page


def paged_decode_attention(
    q: jax.Array,  # [S, n_kv, n_rep, hd]
    k_pages: jax.Array,  # [P, page, n_kv, hd]
    v_pages: jax.Array,  # [P, page, n_kv, vd]
    page_table: jax.Array,  # [S, pages_per_slot] int32
    seq_lens: jax.Array,  # [S] int32 — each slot's decode position; negative: the slot does not decode
    *,
    window: int = 0,  # > 0: the query sees its last `window` positions only
    sink: jax.Array | None = None,  # [n_kv, n_rep] float32: a logit a head in the denominator
    scale: float | None = None,  # 1/sqrt(hd) unless q and the keys are stored padded past the model's width
    name: str = "paged_decode_attention",  # the kernel's name in HLO metadata and profiler traces
    interpret: bool = False,
) -> jax.Array:
    """One decode step's attention over paged KV. Returns [S, n_kv, n_rep, vd]
    (q's layout at the values' width). Numerics match the dense
    gather+softmax reference (fp32 statistics); a slot whose position is
    negative walks nothing and gets zeros, and inactive/scratch slots that
    are given a position produce garbage that callers must not read —
    identical contract to the gather path.

    A window layer walks the pages its window can touch (the window
    and the page being written: `paged_kv.window_pages_per_slot`), starting
    at the page of its first live position, and not the slot's whole row: pages behind the window may have gone back to
    the pool (the table entry is stale) and are never addressed."""
    s, n_kv, n_rep, hd = q.shape
    page, vd = k_pages.shape[1], v_pages.shape[-1]
    pages_per_slot = page_table.shape[1]
    pages_walked = min(pages_per_slot, -(-window // page) + 1) if window else pages_per_slot
    heads = n_kv * n_rep
    # from the operands' shapes: a block of about BLOCK_BYTES of keys and values, no longer than a slot's walk
    page_bytes = page * n_kv * (hd * k_pages.dtype.itemsize + vd * v_pages.dtype.itemsize)
    block_pages = max(1, min(BLOCK_BYTES // page_bytes, pages_walked))

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = [in_vmem, in_place, in_place]
    operands = [q.reshape(s, heads, hd), k_pages, v_pages]
    if sink is not None:
        in_specs.append(in_vmem)
        operands.append(sink.astype(jnp.float32).reshape(heads, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=in_specs,
        out_specs=in_vmem,
        scratch_shapes=[
            pltpu.VMEM((2, block_pages * page, n_kv, hd), k_pages.dtype),
            pltpu.VMEM((2, block_pages * page, n_kv, vd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page=page, n_kv=n_kv, n_rep=n_rep, block_pages=block_pages, pages_walked=pages_walked,
            window=window, scale=scale or 1.0 / math.sqrt(hd),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, heads, vd), q.dtype),
        interpret=interpret,
        name=name,
    )(page_table, jnp.minimum(seq_lens, pages_per_slot * page - 1), *operands)
    return out.reshape(s, n_kv, n_rep, vd)
