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
  decoding slot's first, so a short slot does not drain the pipe. A pool of
  one row a token, a token's KV heads side by side (`[P, page, n_kv x hd]`,
  where a head is narrower than a lane tile: `paged_kv.packs_heads`), is one
  KV head to this kernel, met by queries that hold a head in its own KV
  head's columns and zeros elsewhere.
- **The arithmetic.** A block's keys are read as the 2-D matrix
  `[tokens * n_kv, hd]` they already are in memory (a ref reshape, no
  relayout), and q.k is ONE matmul of all `n_kv * n_rep` query heads against
  all rows, in the pool's dtype with float32 accumulation: a row holds one KV
  head of one token, so a query head's scores against the other KV heads'
  rows are masked away with the dead positions. That spends `n_kv` times the
  needed multiply-adds on an MXU the kernel leaves idle anyway (it is bound
  by bytes), and spares every transposition of `[tokens, n_kv, hd]`. Running
  max, sum and accumulator are float32 loop carries (online softmax);
  probabilities stay float32 into the second matmul (Mosaic runs a float32
  product in one bfloat16 pass on the v5e: measured no slower than bfloat16
  probabilities, PERF.md section 6, PR 37).
- **One program.** Lengths are data: the same compiled kernel serves every
  batch; the block size comes from the operands' shapes alone (a page's
  bytes). No grid, no bucket, nothing tuned at import.

GQA: q arrives `[slots, n_kv, n_rep, hd]` (grouped by kv head). Keys and
values may differ in width, a window layer walks only the pages its window
can touch, and a layer with a learned sink adds one logit a head to the
softmax's denominator.

**Latent attention** has a kernel of its own over the same walk
(`paged_decode_attention_mla`, `_mla_decode_kernel`): the pool holds ONE row
a token, `[latent | rope key | zeros to the stored width]`, so a page is one
copy and not a key copy and a value copy; the values are the first `latent`
columns of the key block already in VMEM; every query head (64) meets every
row, nothing is masked away but dead positions. At 139,264 operations against
1,152 bytes a row it is not byte-bound as the kernel above is: 64 rows of
queries fill half an MXU pass, and the trace's roofline share (35-39% of the
byte floor on a v5e) is what that costs (PERF.md section 6, PR 37). Both
kernels are `_walk_live_blocks` with their own page copies and block reads.

The same kernel runs `interpret=True` on CPU CI, pinned against the dense
float32 reference (tests/test_serving.py, tests/test_serving_two_pools.py,
tests/test_ops.py), and compiled under Mosaic on a TPU, where it matches the
gather path (tests/test_ops.py TPU-gated test; `chip_smoke.py`).

Who calls them, and over what: only `paged_decode_step`, through
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

Each entry point is a `jax.jit` of its own, so a process traces and lowers
a kernel once a shape and every step program that calls it takes it from
there: the decode step and the step of each prefill chunk bucket have the
same decode rows (PERF.md, set-up with the chunk in the step).
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
    **walk,
):
    # o_ref [S, n_kv * n_rep, vd]; k_buf [2, block_pages * page, n_kv, hd] and v_buf alike: the two
    # blocks in flight; sems [2, 2]: keys / values by buffer
    sink_ref = rest[0] if len(rest) == 5 else None
    o_ref, k_buf, v_buf, sems = rest[-4:]
    rows = k_buf.shape[1] * n_kv  # (token, KV head) rows of a block

    def page_copies(page_id, i, buf):
        return (
            pltpu.make_async_copy(k_hbm.at[page_id], k_buf.at[buf, pl.ds(i * page, page)], sems.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[page_id], v_buf.at[buf, pl.ds(i * page, page)], sems.at[1, buf]),
        )

    def block(buf):
        # a block's keys as the 2-D matrix they already are in memory (a ref reshape, no relayout)
        return k_buf.at[buf].reshape(rows, k_buf.shape[-1])[...], v_buf.at[buf].reshape(rows, v_buf.shape[-1])[...].astype(jnp.float32)

    # a block's tail may hold pages never fetched: their probabilities are 0, and 0 x what the
    # buffer held must be 0 (keys need no such care: a masked score is replaced, not multiplied)
    v_buf[...] = jnp.zeros_like(v_buf)
    _walk_live_blocks(page_table_ref, seq_lens_ref, q_ref, sink_ref, o_ref, page_copies, block, page=page, n_kv=n_kv, **walk)


def _mla_decode_kernel(
    page_table_ref,  # [S, pages_per_slot] int32
    seq_lens_ref,  # [S] int32: the decode token's position; negative where the slot does not decode
    q_ref,  # [S, heads, width] in VMEM: the absorbed query, [q W_UK^T | q_rope | zeros to the stored width]
    rows_hbm,  # [P, page, width]: one row a token, [latent | rope key | zeros], where it lies
    o_ref,  # [S, heads, latent]
    buf_ref,  # [2, block_pages * page, width]: the two blocks in flight
    sems,  # [2]
    *,
    page: int,
    **walk,
):
    """Latent attention's decode step: ONE copy a page, and the values are the
    first `latent` columns of the key block already in VMEM. To the walk this
    is one KV head under every query head, so no score is masked away but a
    dead position's; the probabilities go to the rows' dtype for the second
    product, as the gather path's do (float32 ones measured no slower on a
    v5e, where Mosaic runs the product in one bfloat16 pass either way, and
    would cost a conversion of the block: PERF.md section 6, PR 37)."""
    latent = o_ref.shape[-1]

    def page_copies(page_id, i, buf):
        return (pltpu.make_async_copy(rows_hbm.at[page_id], buf_ref.at[buf, pl.ds(i * page, page)], sems.at[buf]),)

    def block(buf):
        k = buf_ref[buf]
        return k, k[:, :latent]

    buf_ref[...] = jnp.zeros_like(buf_ref)  # the values of a block's unfetched tail: 0 x them must be 0
    _walk_live_blocks(page_table_ref, seq_lens_ref, q_ref, None, o_ref, page_copies, block, page=page, n_kv=1, **walk)


def _walk_live_blocks(
    page_table_ref, seq_lens_ref, q_ref, sink_ref, o_ref,
    page_copies,  # (page id, its place in the block, buffer) -> the async copies that bring the page in
    block,  # buffer -> (keys [rows, hd], values [rows, vd]) of the block in it; the second product runs in the values' dtype
    *,
    page: int,
    n_kv: int,
    n_rep: int,
    block_pages: int,
    pages_walked: int,
    window: int,
    scale: float,
):
    """What both kernels do: every decoding slot's live pages in blocks of
    `block_pages`, two blocks in flight, an online softmax a slot."""
    slots, heads, _hd = q_ref.shape
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
            for copy in page_copies(page_table_ref[s, first + b * block_pages + i], i, buf):
                act(copy)
            return carry

        lax.fori_loop(0, jnp.minimum(block_pages, n - b * block_pages), one, 0)

    start_block = functools.partial(block_copies, act=lambda copy: copy.start())
    wait_block = functools.partial(block_copies, act=lambda copy: copy.wait())

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
            k, v = block(buf)
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
            acc = acc * corr + jnp.dot(p_exp.astype(v.dtype), v, preferred_element_type=jnp.float32)
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


@functools.partial(jax.jit, static_argnames=("window", "scale", "name", "interpret"))
def paged_decode_attention(
    q: jax.Array,  # [S, n_kv, n_rep, hd]
    k_pages: jax.Array,  # [P, page, n_kv, hd], or [P, page, hd] where n_kv is 1
    v_pages: jax.Array,  # [P, page, n_kv, vd], or [P, page, vd]
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
            pltpu.VMEM((2, block_pages * page) + k_pages.shape[2:], k_pages.dtype),
            pltpu.VMEM((2, block_pages * page) + v_pages.shape[2:], v_pages.dtype),
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


@functools.partial(jax.jit, static_argnames=("latent", "scale", "name", "interpret"))
def paged_decode_attention_mla(
    q: jax.Array,  # [S, heads, width]: the absorbed query at the rows' stored width
    rows: jax.Array,  # [P, page, width]: a token's [latent | rope key | zeros] row
    page_table: jax.Array,  # [S, pages_per_slot] int32
    seq_lens: jax.Array,  # [S] int32 — each slot's decode position; negative: the slot does not decode
    *,
    latent: int,  # the first `latent` columns of a row are also its value
    scale: float,
    name: str = "paged_decode_attention_mla",
    interpret: bool = False,
) -> jax.Array:
    """One decode step of latent attention over the paged rows. Returns
    [S, heads, latent]: each head's probabilities over the latents, which the
    caller takes up to the head's values. The contract is
    `paged_decode_attention`'s: a slot whose position is negative walks
    nothing and gets zeros."""
    s, heads, width = q.shape
    page, pages_per_slot = rows.shape[1], page_table.shape[1]
    block_pages = max(1, min(BLOCK_BYTES // (page * width * rows.dtype.itemsize), pages_per_slot))
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=in_vmem,
        scratch_shapes=[pltpu.VMEM((2, block_pages * page, width), rows.dtype), pltpu.SemaphoreType.DMA((2,))],
    )
    # every slot's query and output stay in VMEM for the call (64 slots x 64 heads: 5.2 + 4.2 MB in
    # bfloat16) beside the two blocks: past the compiler's default allowance, well inside the chip's
    resident = (q.size + s * heads * latent) * q.dtype.itemsize + 2 * block_pages * page * width * rows.dtype.itemsize
    return pl.pallas_call(
        functools.partial(
            _mla_decode_kernel, page=page, n_rep=heads, block_pages=block_pages, pages_walked=pages_per_slot, window=0, scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, heads, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(32 << 20, 2 * resident)),
        interpret=interpret,
        name=name,
    )(page_table, jnp.minimum(seq_lens, pages_per_slot * page - 1), q, rows)
