"""Paged (block) KV cache: fixed-size pages + a block allocator so
heterogeneous sequence lengths share ONE HBM pool.

The dense `KVCache` (llama.py) allocates `batch × max_len` per request —
serving N concurrent requests that way costs `N × max_len` HBM regardless of
how short each sequence actually is, and admitting a new request means
allocating (and compiling for) a new cache. Here the pool is allocated ONCE:

- **pages**: `[n_layers, num_pages, page_size, n_kv, hd]` k/v arrays — the
  whole serving tier's KV memory, fixed at engine start. HBM is bounded by
  `num_pages × page_size`, never by `num_requests × max_len`.
- **page table**: `[slots, pages_per_slot]` int32 — slot s's token position p
  lives in page `page_table[s, p // page_size]` at offset `p % page_size`.
- **block allocator** (`serving/pages.py`, host-side): a free list handing
  out pages one at a time as sequences grow. Fragmentation is structural-zero:
  any free page serves any slot (no contiguity requirement), so alloc/free
  churn from heterogeneous lengths can't strand capacity. This module is the
  device side alone: the pools, the tables, their jitted updates, the steps.

Page 0 is reserved as a **scratch page**: inactive slots' writes are routed
there, which keeps `paged_decode_step` a single fixed-shape executable (the
batch dimension is always `slots`; inactivity is data, not shape). Scratch
garbage is never read — attention masks positions beyond each slot's length.

TPU notes: everything here is static-shape jnp (gathers/scatters lower to
XLA dynamic-gather/scatter), so the same program runs on TPU, interpret-mode
Pallas hosts, and the CPU fallback unchanged (the Maple-style portability
constraint). A Pallas paged-attention kernel (per-page VMEM streaming like
ops/attention.py's flash kernel) is the TPU upgrade path — same signatures.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .experts import routed_experts
from .llama import LayerKind, LlamaConfig, apply_rope, repeat_kv, rms_norm, rope_frequencies

DEFAULT_PAGE_SIZE = 16
# positions of a slot's page row that a prefill chunk's attention visits in one
# turn of its loop (`_prefill_attention`); a constant of the code, not an option
PREFILL_KV_BLOCK = 512


def k_cache_dim(cfg: LlamaConfig) -> int:
    """The width a key is STORED at. A head wider than 128 that is no
    multiple of 128 (192) is padded with zeros to the next one (256): for a
    192-wide minor dimension XLA's TPU layout puts the pages dimension
    innermost, and the decode kernel, which needs a page's keys contiguous,
    would be handed a transposed copy of the whole pool at every call
    (PERF.md section 6, PR 29). The zeros add nothing to q.k; scores scale by
    the model's own width. A latent layer's row, [latent | rope key] (512 + 64),
    keeps the same rule (640): a 576-wide minor dimension is laid out the same
    way (PERF.md section 6, PR 37)."""
    width = cfg.kv_rank + cfg.qk_rope_dim if cfg.kv_rank else cfg.head_dim
    return width if width <= 128 or width % 128 == 0 else -(-width // 128) * 128


def window_pages_per_slot(window: int, page_size: int) -> int:
    """Pages of a window layer's pool one decoding slot can hold: the window
    (the query's own position included) and the page being written."""
    return math.ceil(window / page_size) + 1


def default_window_num_pages(cfg: LlamaConfig, slots: int, page_size: int, prefill_chunk: int) -> int:
    """A window pool no admission pattern can exhaust: every slot's window,
    one chunk's headroom for the slot in prefill (a chunk holds itself and the
    window before it, and gives back all but the window at its end), and the
    scratch page."""
    per_slot = window_pages_per_slot(cfg.window, page_size)
    in_chunk = math.ceil((prefill_chunk + cfg.window - 1) / page_size) + 1
    return 1 + slots * per_slot + max(0, in_chunk - per_slot)


class PagedKVCache(NamedTuple):
    """Device state of the shared pool (one per serving engine, NOT per
    request). All shapes static — one compiled decode executable serves
    every admission pattern.

    Every layer alike (`cfg.uniform`, the dense presets): ONE pool, k_pages
    and v_pages `[n_layers, num_pages, page_size, n_kv, hd]`. Layers of more
    than one kind: k_pages and v_pages are tuples, one array a group of
    `cfg.layer_groups`, `[layers in the group, pages of its kind's pool,
    page_size, its n_kv, stored key width | value width]`; full-attention
    groups share the page ids of `page_table` (a pool that grows with the
    context), window groups those of `window_table` (a pool bounded by the
    window: a page behind it goes back to its free list while the request
    lives, and its stale table entry is never addressed). A group of LATENT
    layers (`kind.latent`) keeps one array, `[layers, pages, page_size, 1,
    stored row width]`, a token's `[latent | rope key]` row, and None in
    v_pages: its values are the first columns of its rows.

    A page id names the same page in every layer of a pool: the tables, the
    allocators, `copy_page` and the page shipment index `[:, id]`. The jitted
    steps do not slice a layer out to use it: their layer loop carries a pool
    whole, viewed as `[layers * P, page, n_kv, width]`, and layer l reads and
    writes page `l * P + id` (`_run_layers`), so the donated buffers are
    updated in place and a step holds no second pool. Page 0 of EVERY layer
    is that layer's scratch page."""

    k_pages: Any
    v_pages: Any
    page_table: jax.Array  # [slots, pages_per_slot] int32 (0 = scratch)
    seq_lens: jax.Array  # [slots] int32 — tokens written per slot
    window_table: Optional[jax.Array] = None  # [slots, pages_per_slot]: the window layers' rows
    # (token, expert) pairs the expert layers computed on held experts, summed
    # over every step so far; uint32, wraps (the host reads differences)
    moe_pairs: Optional[jax.Array] = None
    # held experts that got at least one of a step's pairs, summed over expert
    # layers and steps the same way
    moe_touched: Optional[jax.Array] = None

    @staticmethod
    def create(
        cfg: LlamaConfig,
        slots: int,
        num_pages: int,
        page_size: int = DEFAULT_PAGE_SIZE,
        pages_per_slot: Optional[int] = None,
        window_num_pages: Optional[int] = None,
    ) -> "PagedKVCache":
        pages_per_slot = pages_per_slot or math.ceil(cfg.max_seq_len / page_size)
        tables = dict(
            page_table=jnp.zeros((slots, pages_per_slot), jnp.int32),
            seq_lens=jnp.zeros((slots,), jnp.int32),
        )
        if cfg.uniform:
            shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
            return PagedKVCache(k_pages=jnp.zeros(shape, cfg.dtype), v_pages=jnp.zeros(shape, cfg.dtype), **tables)
        if cfg.has_window and window_num_pages is None:
            raise ValueError("a model with window layers needs window_num_pages, its second pool's size")
        k_pages, v_pages = [], []
        for kind, _first, n in cfg.layer_groups:
            pool = (n, window_num_pages if kind.window else num_pages, page_size, kind.n_kv_heads)
            k_pages.append(jnp.zeros(pool + (k_cache_dim(cfg),), cfg.dtype))
            # what a pool stores is its layers' kind's: a latent layer has no values of its own
            v_pages.append(None if kind.latent else jnp.zeros(pool + (cfg.v_dim,), cfg.dtype))
        return PagedKVCache(
            k_pages=tuple(k_pages),
            v_pages=tuple(v_pages),
            window_table=jnp.zeros((slots, pages_per_slot), jnp.int32) if cfg.has_window else None,
            moe_pairs=jnp.zeros((), jnp.uint32) if cfg.has_experts else None,
            moe_touched=jnp.zeros((), jnp.uint32) if cfg.has_experts else None,
            **tables,
        )

    @property
    def page_size(self) -> int:
        return jax.tree_util.tree_leaves(self.k_pages)[0].shape[2]

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def kv_span(self) -> int:
        """Max attended positions per slot (pages_per_slot × page_size)."""
        return self.page_table.shape[1] * self.page_size

    def pool_bytes(self) -> int:
        """Bytes of every K and V pool (and of every latent one)."""
        return sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves((self.k_pages, self.v_pages)))


def pool_bytes_by_kind(cfg: LlamaConfig, cache: PagedKVCache) -> tuple[int, int]:
    """(bytes of the pool that grows with the context, bytes of the window
    layers' pool): what `page_table` and `window_table` address."""
    if cfg.uniform:
        return cache.pool_bytes(), 0
    sizes = [0, 0]
    for (kind, _first, _n), k, v in zip(cfg.layer_groups, cache.k_pages, cache.v_pages):
        sizes[bool(kind.window)] += sum(int(a.size) * a.dtype.itemsize for a in (k, v) if a is not None)
    return sizes[0], sizes[1]


# -- host-side table maintenance (small jitted updates between steps) --------


@partial(jax.jit, donate_argnums=(0,))
def assign_pages(cache: PagedKVCache, slot: int, start_index: int, pages: jax.Array) -> PagedKVCache:
    """Write an admission's page ids into slot's table row at
    [start_index : start_index+len(pages)] (len(pages) is static per call:
    the page manager pads a row to `pages_per_slot`, one executable)."""
    row = lax.dynamic_update_slice(cache.page_table[slot], pages.astype(jnp.int32), (start_index,))
    return cache._replace(page_table=cache.page_table.at[slot].set(row))


def pack_entries(num_slots: int, length: int, *tables: list) -> np.ndarray:
    """`assign_entries`' argument, made on the host: one list of (slot, index,
    page) a table, `page_table`'s first, as int32 `[tables, 3, length]`. An
    unused entry names slot `num_slots`, out of range, and is dropped."""
    packed = np.zeros((len(tables), 3, length), np.int32)
    packed[:, 0] = num_slots
    for row, entries in zip(packed, tables):
        if entries:
            row[:, : len(entries)] = np.asarray(entries, np.int32).T
    return packed


@partial(jax.jit, donate_argnums=(0,))
def assign_entries(cache: PagedKVCache, entries: jax.Array) -> PagedKVCache:
    """Write page ids into `page_table` at (slot, index), and where the model
    has window layers into `window_table` too: one call for everything a
    step's bookkeeping handed out, growth and the window pool's turn-over
    together. `entries` is `pack_entries`' array, `[tables, 3, length]` with
    one fixed length a caller (one executable a model: the tree says whether
    there is a `window_table`). Entries behind a window are left as they
    are: stale, never addressed."""
    tables = ("page_table",) if cache.window_table is None else ("page_table", "window_table")
    if entries.shape[0] != len(tables):
        raise ValueError(f"entries for {entries.shape[0]} tables, the cache has {len(tables)}")
    written = {
        name: getattr(cache, name).at[entries[t, 0], entries[t, 1]].set(entries[t, 2], mode="drop")
        for t, name in enumerate(tables)
    }
    return cache._replace(**written)


@partial(jax.jit, donate_argnums=(0,))
def release_slot(cache: PagedKVCache, slot: int) -> PagedKVCache:
    """Point the slot back at scratch and zero its length (the host frees
    the pages on the allocator side)."""
    cache = cache._replace(
        page_table=cache.page_table.at[slot].set(0),
        seq_lens=cache.seq_lens.at[slot].set(0),
    )
    if cache.window_table is not None:
        cache = cache._replace(window_table=cache.window_table.at[slot].set(0))
    return cache


@partial(jax.jit, donate_argnums=(0,))
def copy_page(cache: PagedKVCache, slot: int, table_index: int, dst_page: jax.Array) -> PagedKVCache:
    """Copy-on-write: duplicate the page the slot's table currently points
    at (all layers' K and V rows) into `dst_page` and repoint the table.
    The source page — still referenced by the prefix cache and/or other
    slots — is never mutated (ISSUE 12 CoW contract)."""
    src = cache.page_table[slot, table_index]
    dst = dst_page.astype(jnp.int32)
    # every array `page_table` addresses: one pool, or one a layer group (a latent group has no values)
    k_pages, v_pages = jax.tree_util.tree_map(lambda pool: pool.at[:, dst].set(pool[:, src]), (cache.k_pages, cache.v_pages))
    return cache._replace(k_pages=k_pages, v_pages=v_pages, page_table=cache.page_table.at[slot, table_index].set(dst))


@partial(jax.jit, donate_argnums=(0,))
def set_seq_lens(cache: PagedKVCache, new_lens: jax.Array, update: jax.Array) -> PagedKVCache:
    """Host-directed per-slot length update (speculative decoding: the
    verify step writes k+1 candidate positions, then the HOST decides how
    many were accepted — seq_lens is rolled to pos+accepted+1 here, and the
    rejected positions' KV becomes unattended garbage beyond the length)."""
    return cache._replace(
        seq_lens=jnp.where(update, new_lens.astype(jnp.int32), cache.seq_lens)
    )


@jax.jit
def set_token(tokens: jax.Array, slot: jax.Array, token: jax.Array) -> jax.Array:
    """A joining slot's token into the `[slots]` vector the next decode step
    feeds on, where that vector is the step before's `next_tokens` and never
    came to the host (serving/engine.py keeps one step in flight)."""
    return tokens.at[slot].set(token)


@jax.jit
def tokens_and_counts(tokens: jax.Array, moe_pairs: jax.Array, moe_touched: jax.Array) -> jax.Array:
    """A step's tokens with the cache's two expert counters behind them, as
    ONE int32 array for one fetch: the cache they live in is donated to the
    next launch, this copy is not. The counters are uint32 bit for bit."""
    counts = lax.bitcast_convert_type(jnp.stack([moe_pairs, moe_touched]), jnp.int32)
    return jnp.concatenate([tokens.reshape(-1), counts])


# -- KV-page shipment (prefill/decode disaggregation, ISSUE 18) ---------------


def _one_pool(cache: PagedKVCache, what: str) -> None:
    if isinstance(cache.k_pages, tuple):
        raise ValueError(
            f"{what} ships pages of ONE pool; this model keeps a pool a layer kind (full and window "
            "attention side by side), and a shipment format over two pools does not exist yet"
        )


def export_pages(cache: PagedKVCache, page_ids: list[int]) -> dict:
    """Pull the named pages off the device as host arrays, ready to ride a
    blob-plane frame to another replica. Shapes: k/v are
    [n_layers, len(page_ids), page_size, n_kv, hd] in the pool dtype —
    whole pages, so positions past the holder's seq_len travel as garbage
    and stay unattended on the importer too. Read-only: exporting pages
    that are refcount-shared with the prefix cache is safe."""
    _one_pool(cache, "export_pages")
    idx = jnp.asarray(page_ids, jnp.int32)
    return {
        "k": np.asarray(cache.k_pages[:, idx]),
        "v": np.asarray(cache.v_pages[:, idx]),
    }


@partial(jax.jit, donate_argnums=(0,))
def _import_pages(cache: PagedKVCache, idx: jax.Array, k: jax.Array, v: jax.Array) -> PagedKVCache:
    return cache._replace(
        k_pages=cache.k_pages.at[:, idx].set(k),
        v_pages=cache.v_pages.at[:, idx].set(v),
    )


def import_pages(cache: PagedKVCache, page_ids: list[int], data: dict) -> PagedKVCache:
    """Write a shipped page bundle (an `export_pages` dict) into freshly
    allocated local pages. One executable per page count — shipment sizes
    are prompt-page counts, so they bucket like prefill lengths in
    practice. The caller owns the page allocation/table wiring; dtype is
    cast to the pool's (a bf16 pool importing from a bf16 pool is a
    no-op cast)."""
    _one_pool(cache, "import_pages")
    idx = jnp.asarray(page_ids, jnp.int32)
    dtype = cache.k_pages.dtype
    return _import_pages(
        cache, idx, jnp.asarray(data["k"], dtype), jnp.asarray(data["v"], dtype)
    )


# -- paged forward internals --------------------------------------------------


def _scatter_kv(k_pages, v_pages, k, v, page_ids, offsets):
    """Write per-position K/V rows into their pages.
    k_pages/v_pages: [P, page, n_kv, hd]; k/v: [T, n_kv, hd];
    page_ids/offsets: [T] (scratch-routed entries carry page 0). A latent
    layer has rows and no values: v_pages and v are None."""
    return (
        k_pages.at[page_ids, offsets].set(k, mode="drop"),
        None if v_pages is None else v_pages.at[page_ids, offsets].set(v, mode="drop"),
    )


def _paged_attention(
    q, k_pages, v_pages, page_table, mask, positions=None, attn_impl="gather",
    window=0, sink=None, scale=None, kernel_name="paged_decode_attention", latent=0,
):
    """Attend each slot's page span: what `paged_decode_step` and
    `paged_verify_step` run (a prefill chunk has `_prefill_attention`).
    q: [S, Sq, H, hd]; k_pages/v_pages: [P, page, n_kv, hd]; page_table:
    [S, pages_per_slot]; mask: [S, 1, Sq, K] additive. Returns [S, Sq, H, hd].

    attn_impl (static at trace time): "gather" materializes the WHOLE span via
    `k_pages[page_table]`, live or not, and runs the einsum reference (the
    CPU's decode step, every verify step, and what the prefill loop is tested
    against); "kernel" / "kernel_interpret" stream pages HBM→VMEM with the
    Pallas decode kernel (ops/paged_attention.py) — decode only (Sq == 1,
    `positions` = each slot's token position, negative where the slot does
    not decode this step: the kernel walks nothing for it and its row is
    zeros no caller reads); the verify step's multi-token call always takes
    the gather path.

    What a layer kind adds, each off where the dense models leave it: values
    narrower than keys (v_pages' own last dimension); `scale` where q and the
    stored keys are padded past the model's width; a `window` (the mask the
    caller passes carries it, the kernel walks only the window's pages); a
    `sink` [H], one logit a query head that joins the softmax's denominator
    and takes no value; `latent` > 0 where the pool holds latent rows and no
    values (v_pages None): q is the absorbed query at the rows' stored width,
    a row's first `latent` columns are its value, and the kernel is the latent
    one (`paged_decode_attention_mla`)."""
    s, sq, h, hd = q.shape
    vd = latent or v_pages.shape[-1]
    scale = scale or 1.0 / math.sqrt(hd)
    if attn_impl in ("kernel", "kernel_interpret") and sq == 1 and positions is not None:
        from ..ops.paged_attention import paged_decode_attention, paged_decode_attention_mla

        if latent:
            out = paged_decode_attention_mla(
                q.reshape(s, h, hd), k_pages.reshape(k_pages.shape[:2] + (hd,)), page_table, positions,
                latent=latent, scale=scale, name=kernel_name, interpret=(attn_impl == "kernel_interpret"),
            )
            return out.reshape(s, sq, h, vd)
        n_kv = k_pages.shape[2]
        n_rep = h // n_kv
        out = paged_decode_attention(
            q.reshape(s, n_kv, n_rep, hd),  # repeat_kv order: head = kv*n_rep + rep
            k_pages,
            v_pages,
            page_table,
            positions,
            window=window,
            sink=None if sink is None else sink.reshape(n_kv, n_rep),
            scale=scale,
            name=kernel_name,
            interpret=(attn_impl == "kernel_interpret"),
        )
        return out.reshape(s, sq, h, vd)
    page = k_pages.shape[1]
    n_kv = k_pages.shape[2]
    k_span = page_table.shape[1] * page
    # [S, pages_per_slot, page, n_kv, hd] -> [S, K, n_kv, hd]
    k_att = k_pages[page_table].reshape(s, k_span, n_kv, hd)
    v_att = k_att[..., :latent] if latent else v_pages[page_table].reshape(s, k_span, n_kv, vd)
    n_rep = h // n_kv
    k_att = repeat_kv(k_att, n_rep)
    v_att = repeat_kv(v_att, n_rep)
    logits = jnp.einsum("sqhd,skhd->shqk", q, k_att, preferred_element_type=jnp.float32) * scale
    logits = (logits + mask).astype(jnp.float32)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    else:
        # one more column in the denominator, dropped before P.V
        column = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None], logits.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([logits, column], axis=-1), axis=-1)[..., :-1].astype(q.dtype)
    return jnp.einsum("shqk,skhd->sqhd", probs, v_att)


def prefill_kv_block_pages(pages_per_slot: int, page_size: int) -> int:
    """Pages in one KV block of the prefill loop: `PREFILL_KV_BLOCK`
    positions, never more than the slot's row holds."""
    return max(1, min(PREFILL_KV_BLOCK // page_size, pages_per_slot))


def prefill_kv_attended(live: int, pages_per_slot: int, page_size: int) -> int:
    """Positions the prefill loop visits for a chunk whose last token sits at
    `live - 1` (`start_pos + length` = live): whole blocks, so the host can
    count what the device walks (`/v1/stats` `prefill_kv_attended`)."""
    block = prefill_kv_block_pages(pages_per_slot, page_size) * page_size
    return math.ceil(live / block) * block


def _prefill_attention(q, k_pages, v_pages, row, q_pos, live, block_pages, window=0, sink=None, scale=None, expand=None):
    """One slot's prefill chunk against the slot's LIVE prefix: a flash
    forward over KV blocks of the page row. q: [Sq, H, hd] at positions q_pos
    [Sq]; k_pages/v_pages: [P, page, n_kv, hd], the chunk's own K/V already
    written; row: [pages_per_slot]; live: [] int32 = start_pos + length, the
    positions that hold something. Returns [Sq, H, hd].

    The trip count `ceil(live / block)` is data, so one executable serves
    every prefix length and nothing of the span's size (mask, gathered K/V,
    scores) exists. Each turn gathers `block_pages` pages, masks causally from
    q_pos and the block's positions, and folds the block into a running max,
    sum and float32 accumulator; query heads contract against their own KV
    head (head = kv * n_rep + rep, `repeat_kv`'s order). Scores, max, sum and
    accumulator are float32, the probabilities go to q's dtype for P·V: the
    gather path's arithmetic with the sum reassociated. Every row sees
    position 0 in block 0, so its running max is finite from the first turn
    and rows past `length` (garbage, never read) cannot make a NaN.

    A window layer (`window` > 0: the query at p sees keys p-window+1 .. p)
    starts at the block that holds the FIRST query's oldest key and masks
    from below too; a later row may then see nothing in a block, so its
    running max is kept finite by hand. A `sink` [H] joins each row's sum
    once, after the last block. Values may be narrower than keys, and
    `scale` is the model's own where q and the stored keys are padded.

    A pool of latent rows (v_pages None) brings `expand`: (a block's rows
    [block, 1, stored]) -> (keys [block, H, hd], values [block, H, vd]), the
    block's keys and values a head rebuilt from its latents, against q as the
    model has it [Sq, H, hd]. For a chunk of 256 rows that is cheaper on the
    chip than the decode step's absorbed form, whose accumulator is as wide
    as the latent (PERF.md section 6, PR 37)."""
    sq, h, hd = q.shape
    page, n_kv, stored = k_pages.shape[1:]
    block = block_pages * page
    if expand is not None:
        k_s, v_s = jax.eval_shape(expand, jax.ShapeDtypeStruct((block, n_kv, stored), k_pages.dtype))
        n_kv_q, vd = k_s.shape[1], v_s.shape[-1]
    else:
        n_kv_q, vd = n_kv, v_pages.shape[-1]
    n_rep = h // n_kv_q
    # a row the block does not divide is padded with page 0 (a scratch page,
    # of the pool's first layer where the ids are a later layer's): those
    # positions lie past the span, so past every row that is read
    row = jnp.pad(row, (0, -row.shape[0] % block_pages))
    qg = q.reshape(sq, n_kv_q, n_rep, hd)
    scale = scale or 1.0 / math.sqrt(hd)
    offsets = jnp.arange(block, dtype=jnp.int32)

    def fold(b, carry):
        m, l, acc = carry
        ids = lax.dynamic_slice_in_dim(row, b * block_pages, block_pages)
        k_blk = k_pages[ids].reshape(block, n_kv, stored)
        if expand is not None:
            k_blk, v_blk = expand(k_blk)
        else:
            v_blk = v_pages[ids].reshape(block, n_kv, vd)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k_blk, preferred_element_type=jnp.float32) * scale
        kv_pos = (b * block + offsets)[None, :]
        seen = kv_pos <= q_pos[:, None]  # [Sq, block]
        if window:
            seen = seen & (kv_pos > q_pos[:, None] - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        if window:
            # a row that has seen no key yet: exp(-inf - 0) = 0, not exp(nan)
            m_ref = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_ref[..., None])
            shrink = jnp.exp(m - m_ref)
        else:
            p = jnp.exp(s - m_new[..., None])
            shrink = jnp.exp(m - m_new)
        l = shrink * l + p.sum(axis=-1)
        acc = shrink[..., None] * acc + jnp.einsum(
            "grqk,kgd->grqd", p.astype(q.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    init = (
        jnp.full((n_kv_q, n_rep, sq), -jnp.inf, jnp.float32),
        jnp.zeros((n_kv_q, n_rep, sq), jnp.float32),
        jnp.zeros((n_kv_q, n_rep, sq, vd), jnp.float32),
    )
    first_block = jnp.maximum(q_pos[0] - (window - 1), 0) // block if window else 0
    m, l, acc = lax.fori_loop(first_block, (live + block - 1) // block, fold, init)
    if sink is not None:
        l = l + jnp.exp(sink.astype(jnp.float32).reshape(n_kv_q, n_rep)[..., None] - m)
    out = (acc / l[..., None]).astype(q.dtype)  # [n_kv, n_rep, Sq, vd]
    return out.transpose(2, 0, 1, 3).reshape(sq, h, vd)


def _rope(kind, x, positions, inv_freq):
    """Rotary positions on the first `kind.rope_dim` dims of each head; the
    rest pass through (all of them turn in the dense models). Under YaRN the
    turned dims are scaled by its attention factor."""
    rd, scale = kind.rope_dim, kind.yarn[4] if kind.yarn else 1.0
    if rd == x.shape[-1]:
        return apply_rope(x, positions, inv_freq, scale)
    return jnp.concatenate([apply_rope(x[..., :rd], positions, inv_freq, scale), x[..., rd:]], axis=-1)


def _pad_last(x, width):
    return x if x.shape[-1] == width else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _heads_qkv(cfg, kind, h, layer, positions, inv_freq, stored):
    """Keys and values a KV head: (q [S, Sq, H, stored], k [S, Sq, n_kv,
    stored], v [S, Sq, n_kv, vd]) of the normed input h."""
    from .quant import qmm

    s, sq, _d = h.shape
    hd, vd = cfg.head_dim, cfg.v_dim
    q = qmm(h, layer["wq"]).reshape(s, sq, kind.n_heads, hd)
    k = qmm(h, layer["wk"]).reshape(s, sq, kind.n_kv_heads, hd)
    v = qmm(h, layer["wv"]).reshape(s, sq, kind.n_kv_heads, vd)
    q = _rope(kind, q, positions, inv_freq)
    k = _rope(kind, k, positions, inv_freq)
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    # k_cache_dim: zeros past the model's width
    return _pad_last(q, stored), _pad_last(k, stored), v


def _latent_qkv(cfg, kind, h, layer, positions, inv_freq, stored):
    """Latent attention's two down-projections: (q [S, Sq, H, nope + rope],
    the head as the model has it, its rope part turned; the row that is
    cached [S, Sq, 1, stored] = [rmsnorm(latent) | the ONE rope key of the
    token, turned | zeros]; None: the row's first columns are its value)."""
    from .quant import qmm

    s, sq, _d = h.shape
    _q_rank, kv_rank, nope, rope, _vd = kind.latent
    with jax.named_scope("mla_q_proj"):
        q = qmm(rms_norm(qmm(h, layer["wq_down"]), layer["q_norm"], cfg.norm_eps), layer["wq_up"])
        q = q.reshape(s, sq, kind.n_heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(kind, q[..., nope:], positions, inv_freq)], axis=-1)
    with jax.named_scope("mla_kv_compress"):
        down = qmm(h, layer["wkv_down"])[:, :, None, :]  # [S, Sq, 1, kv_rank + rope]
        row = jnp.concatenate([
            rms_norm(down[..., :kv_rank], layer["kv_norm"], cfg.norm_eps), _rope(kind, down[..., kv_rank:], positions, inv_freq),
        ], axis=-1)
    return q, _pad_last(row, stored), None


def _latent_up(kind, layer):
    """The latent's up-projection a head (the published kv_b_proj
    [kv_rank, H x (nope + vd)]): (W_UK [kv_rank, H, nope], W_UV [kv_rank, H, vd])."""
    _q_rank, kv_rank, nope, _rope_dim, vd = kind.latent
    w_up = layer["wkv_up"].reshape(kv_rank, kind.n_heads, nope + vd)
    return w_up[..., :nope], w_up[..., nope:]


def _absorbed_attention(kind, q, layer, stored, inner):
    """Latent attention without a key or a value a head: q [S, Sq, H, nope +
    rope] is taken through W_UK onto the latent (`q' = q_nope W_UK^T`), `inner`
    (absorbed query [S, Sq, H, stored] -> [S, Sq, H, kv_rank]) attends the
    cached rows with it, their first kv_rank columns as values, and W_UV takes
    each head's mix of latents up to its values. Returns [S, Sq, H, vd]."""
    nope = kind.latent[2]
    w_uk, w_uv = _latent_up(kind, layer)
    with jax.named_scope("mla_absorb"):
        q_latent = jnp.einsum("sqhn,lhn->sqhl", q[..., :nope], w_uk)
        q_abs = _pad_last(jnp.concatenate([q_latent, q[..., nope:]], axis=-1), stored)
    out = inner(q_abs)
    with jax.named_scope("mla_v_up"):
        return jnp.einsum("sqhl,lhv->sqhv", out, w_uv)


def _paged_layer(cfg, kind, x, layer, positions, write_page_ids, write_offsets, table, inv_freq, kp, vp, attend, valid=None):
    """One transformer layer of `kind` over paged KV. x: [S, Sq, D];
    positions: [S, Sq]; kp/vp: [pages, page, n_kv, width], the pool this
    layer's pages lie in (a latent layer: kp its rows, vp None);
    write_page_ids/offsets: flat [S*Sq] scatter targets
    in it; table: the page ids this layer reads, as the caller's `attend`
    takes them; attend: (kind, q [S, Sq, H, hd], k_pages, v_pages, table,
    layer) -> [S, Sq, H, vd] over the pool as this layer has just written it —
    the caller's own (`_paged_attention` for decode and verify,
    `_prefill_attention` for a prefill chunk). Returns (x, kp, vp, counts):
    uint32 [2], the (token, expert) pairs an expert layer computed for the
    `valid` [S*Sq] tokens and the held experts they touched; 0 for a dense FFN."""
    from .quant import qmm

    s, sq, d = x.shape
    # the scopes are names only (HLO metadata, profiler traces): nothing
    # computed changes
    with jax.named_scope(kind.attn_name + "_attention" if kind.attn_name else "paged_attention"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = (_latent_qkv if kind.latent else _heads_qkv)(cfg, kind, h, layer, positions, inv_freq, kp.shape[-1])
        with jax.named_scope("kv_write"):
            flat = lambda a: None if a is None else a.reshape((s * sq,) + a.shape[2:])  # noqa: E731
            kp, vp = _scatter_kv(kp, vp, flat(k), flat(v), write_page_ids, write_offsets)
        attn_out = attend(kind, q, kp, vp, table, layer)
        if kind.gated:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(qmm(h, layer["wg"]).astype(jnp.float32))  # [S, Sq, H]: a scalar a head
                attn_out = (attn_out.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
        x = x + qmm(attn_out.reshape(s, sq, -1), layer["wo"])
    pairs = jnp.zeros((2,), jnp.uint32)
    if kind.experts:
        h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        y, pairs = routed_experts(cfg, h.reshape(s * sq, d), layer, valid)
        x = x + y.reshape(s, sq, d)
    else:
        with jax.named_scope("ffn"):
            h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            gated = jax.nn.silu(qmm(h, layer["w_gate"]).astype(jnp.float32)).astype(x.dtype) * qmm(h, layer["w_up"])
            x = x + qmm(gated, layer["w_down"])
    return x, kp, vp, pairs


def _run_layers(params, cfg, x, positions, pools, cache, attend, valid=None):
    """Every layer in turn: a group of like layers at a time (`cfg.layer_groups`;
    the dense presets are one group), one scan over the group's stacked weights.
    `pools`: (write page ids, write offsets, page table) for the pool that
    grows with the context, and a second triple for the window layers' pool
    where the model has them; ids and table count pages within ONE layer.
    Returns (x, k_pages, v_pages, pairs).

    The group's pool is the scan's CARRY, whole, viewed as
    `[layers * P, page, n_kv, width]` (a bitcast of the stored
    `[layers, P, ...]`), and layer l addresses its pages in it at `l * P + id`:
    the write ids and the table are shifted by `l * P`, so page 0 of each layer
    stays that layer's scratch page and every reader (`attend`, the kernel)
    runs as over a pool of one layer. Nothing of a layer's or a pool's size is
    sliced out or written back: the scatter of the new rows lands in place in
    the donated buffer. (A pool that is a scanned input and a stacked output
    is sliced a layer at a time, written back into a second pool and copied
    whole after the loop, in every step: PERF.md section 6, PR 31.)"""
    groups = (params["layers"], cache.k_pages, cache.v_pages)
    if cfg.uniform:  # one group, kept bare and not as tuples of one
        groups = tuple((g,) for g in groups)
    pairs = jnp.zeros((2,), jnp.uint32)
    k_pages, v_pages = [], []
    for (kind, _first, n), layers, kp, vp in zip(cfg.layer_groups, *groups):
        inv_freq = rope_frequencies(cfg, kind)
        write_page_ids, write_offsets, table = pools[bool(kind.window)]
        pool_pages = kp.shape[1]

        def body(carry, layer_and_index):
            x_carry, pairs_carry, kp_all, vp_all = carry
            layer, index = layer_and_index
            first_page = index * pool_pages
            x_out, kp_all, vp_all, used = _paged_layer(
                cfg, kind, x_carry, layer, positions, write_page_ids + first_page, write_offsets,
                table + first_page, inv_freq, kp_all, vp_all, attend, valid,
            )
            return (x_out, pairs_carry + used, kp_all, vp_all), None

        as_one = lambda pool: None if pool is None else pool.reshape((-1,) + pool.shape[2:])  # noqa: E731
        (x, pairs, kp_all, vp_all), _ = lax.scan(
            body, (x, pairs, as_one(kp), as_one(vp)), (layers, jnp.arange(n, dtype=jnp.int32)),
        )
        k_pages.append(kp_all.reshape(kp.shape))
        v_pages.append(None if vp is None else vp_all.reshape(vp.shape))  # a latent group: rows, no values
    if cfg.uniform:
        return x, k_pages[0], v_pages[0], pairs
    return x, tuple(k_pages), tuple(v_pages), pairs


def _kernel_name(kind: LayerKind) -> str:
    return "paged_decode_attention" + ("_" + kind.attn_name if kind.attn_name else "")


def _softmax_scale(cfg: LlamaConfig, kind: LayerKind) -> float:
    """The model's own, whatever width q and the keys are stored or absorbed at."""
    return kind.softmax_scale or 1.0 / math.sqrt(cfg.head_dim)


def _rebuild_kv(kind, layer, rows):
    """A block of cached latent rows [block, 1, stored] -> its keys [block,
    H, nope + rope] and values [block, H, vd] a head, as the model's equations
    have them: k_j = [c W_UK_j | the token's one rope key], v_j = c W_UV_j."""
    _q_rank, kv_rank, _nope, rope, _vd = kind.latent
    w_uk, w_uv = _latent_up(kind, layer)
    c, k_rope = rows[:, 0, :kv_rank], rows[:, :, kv_rank : kv_rank + rope]
    k = jnp.concatenate([jnp.einsum("kl,lhn->khn", c, w_uk), jnp.broadcast_to(k_rope, (rows.shape[0], kind.n_heads, rope))], axis=-1)
    return k, jnp.einsum("kl,lhv->khv", c, w_uv)


def _advance(cache, k_pages, v_pages, pairs, seq_lens):
    cache = cache._replace(k_pages=k_pages, v_pages=v_pages, seq_lens=seq_lens)
    if cache.moe_pairs is not None:
        cache = cache._replace(moe_pairs=cache.moe_pairs + pairs[0], moe_touched=cache.moe_touched + pairs[1])
    return cache


def _logits(params, cfg, x_last):
    from .quant import qmm

    with jax.named_scope("logits"):
        x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
        return qmm(x_last, params["lm_head"]).astype(jnp.float32)


# -- public jitted entry points ----------------------------------------------
# The trainer's switch-style MoE (`cfg.n_experts`, parallel/moe.py) is not
# in this path; the serving engine refuses it at construction. Routed experts
# for serving are a layer kind (`cfg.ffn_pattern`, models/experts.py).


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def paged_prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [S_pad] int32 — one slot's prompt chunk, padded
    length: jax.Array,  # [] int32 — real token count (<= S_pad)
    cache: PagedKVCache,
    slot: jax.Array,  # [] int32
    start_pos: jax.Array,  # [] int32 — tokens already in the slot (chunked prefill)
):
    """Prefill one slot's prompt chunk into its pages while existing slots'
    pages stay untouched. Padded positions (>= length) scatter to the scratch
    page and are never attended. Returns (last_logits [V], next_token [],
    cache); chunked callers ignore logits until the final chunk.

    Each layer writes the chunk's K/V into the pool and then attends the
    slot's live prefix, `start_pos + length` positions, in KV blocks of its
    page row (`_prefill_attention`): the chunk's cost follows what the slot
    holds, not `kv_span`. The live length is data, so it is still one
    executable per (cfg, S_pad): callers bucket prompt lengths
    (PREFILL_BUCKETS) so arbitrary prompts hit a handful of compiles."""
    from .quant import qembed

    (s_pad,) = tokens.shape
    page = cache.page_size
    idx = jnp.arange(s_pad, dtype=jnp.int32)
    valid = idx < length
    positions = start_pos + idx  # [S_pad]
    row = cache.page_table[slot]  # [pages_per_slot]
    write_page_ids = jnp.where(valid, row[jnp.clip(positions // page, 0, row.shape[0] - 1)], 0)
    write_offsets = jnp.where(valid, positions % page, 0)
    block_pages = prefill_kv_block_pages(row.shape[0], page)
    pools = [(write_page_ids, write_offsets, row)]
    if cache.window_table is not None:
        window_row = cache.window_table[slot]
        window_ids = jnp.where(valid, window_row[jnp.clip(positions // page, 0, row.shape[0] - 1)], 0)
        pools.append((window_ids, write_offsets, window_row))

    def attend(kind, q, k_pages, v_pages, row, layer):
        # causal within the live prefix: q at position p sees kv_pos <= p (a
        # window layer: and > p - window); rows past `length` are garbage but
        # their outputs are never read
        return _prefill_attention(
            q[0], k_pages, v_pages, row, positions, start_pos + length, block_pages,
            window=kind.window, sink=layer.get("sink"), scale=_softmax_scale(cfg, kind),
            expand=partial(_rebuild_kv, kind, layer) if kind.latent else None,
        )[None]

    x = qembed(params["embed"], tokens[None, :])  # [1, S_pad, D]
    x, k_pages, v_pages, pairs = _run_layers(params, cfg, x, positions[None, :], pools, cache, attend, valid)
    last = lax.dynamic_index_in_dim(x[0], length - 1, axis=0, keepdims=False)  # [D]
    logits = _logits(params, cfg, last)
    cache = _advance(cache, k_pages, v_pages, pairs, cache.seq_lens.at[slot].set(start_pos + length))
    return logits, jnp.argmax(logits).astype(jnp.int32), cache


@partial(jax.jit, static_argnames=("cfg", "attn_impl"), donate_argnames=("cache",))
def paged_decode_step(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [slots] int32 — current token per slot
    cache: PagedKVCache,
    active: jax.Array,  # [slots] bool
    attn_impl: str = "gather",
):
    """One continuous-batching decode step over EVERY slot (fixed shape:
    inactive slots compute on garbage routed to the scratch page). Returns
    (logits [slots, V], next_tokens [slots], cache). Joining or leaving a
    slot between steps never changes the executable — admission is data.

    attn_impl selects the attention inner: "gather" (dense span gather, runs
    anywhere) or "kernel"/"kernel_interpret" (Pallas HBM→VMEM page streaming,
    ops/paged_attention.py) — static, so each choice is its own executable."""
    from .quant import qembed

    slots = cache.num_slots
    page = cache.page_size
    positions = cache.seq_lens  # [slots] — the new token's position
    rows = cache.page_table  # [slots, pages_per_slot]
    page_idx = jnp.clip(positions // page, 0, rows.shape[1] - 1)
    write_page_ids = jnp.where(active, jnp.take_along_axis(rows, page_idx[:, None], axis=1)[:, 0], 0)
    write_offsets = jnp.where(active, positions % page, 0)

    x = qembed(params["embed"], tokens[:, None])  # [slots, 1, D]
    kv_pos = jnp.arange(cache.kv_span, dtype=jnp.int32)[None, None, None, :]
    mask = jnp.where(kv_pos <= positions[:, None, None, None], 0.0, -jnp.inf).astype(jnp.float32)
    pools, masks = [(write_page_ids, write_offsets, rows)], [mask]
    if cache.window_table is not None:
        window_ids = jnp.take_along_axis(cache.window_table, page_idx[:, None], axis=1)[:, 0]
        pools.append((jnp.where(active, window_ids, 0), write_offsets, cache.window_table))
        behind = kv_pos <= positions[:, None, None, None] - cfg.window
        masks.append(jnp.where(behind, -jnp.inf, mask))
    # the kernel walks the live pages of the slots that decode and nothing for the rest: a slot
    # whose prompt is half prefilled has a length and no query this step
    decoding = jnp.where(active, positions, -1)

    def attend(kind, q, k_pages, v_pages, table, layer):
        def over_span(q, latent=0):
            return _paged_attention(
                q, k_pages, v_pages, table, masks[bool(kind.window)], decoding, attn_impl, window=kind.window,
                sink=layer.get("sink"), scale=_softmax_scale(cfg, kind), kernel_name=_kernel_name(kind), latent=latent,
            )

        if not kind.latent:
            return over_span(q)
        # a decode step never rebuilds a key or a value a head from the cache
        return _absorbed_attention(kind, q, layer, k_pages.shape[-1], partial(over_span, latent=kind.latent[1]))

    x, k_pages, v_pages, pairs = _run_layers(params, cfg, x, positions[:, None], pools, cache, attend, active)
    logits = _logits(params, cfg, x[:, 0, :])  # [slots, V]
    cache = _advance(cache, k_pages, v_pages, pairs, jnp.where(active, cache.seq_lens + 1, cache.seq_lens))
    return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def paged_verify_step(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [slots, K1] int32 — [cur, draft_1..draft_k] per slot
    cache: PagedKVCache,
    active: jax.Array,  # [slots] bool
):
    """Speculative-decoding verify: run K1 = k+1 tokens per slot through the
    target model in ONE step, writing their KV at positions
    `seq_lens[s] + [0..k]` and returning every position's logits
    ([slots, K1, V]) — logits[s, j] is the target's distribution for the
    token AFTER tokens[s, j].

    seq_lens is deliberately NOT advanced here: acceptance is a host
    decision (compare draft proposals against the target's own sampled
    chain), and the host rolls seq_lens forward by accepted+1 via
    `set_seq_lens`. Rejected positions' KV stays behind as garbage beyond
    the rolled length — never attended, overwritten by the next writes at
    those positions. One fixed-shape executable per (cfg, K1): speculation
    depth is a config, not a shape that churns compiles."""
    from .quant import qembed

    if not cfg.uniform:
        raise ValueError(
            "paged_verify_step runs one pool of one layer kind: a window layer's bounded pool cannot keep "
            "k+1 speculative positions it may have to roll back, and routed experts are not in the verify path"
        )
    slots, k1 = tokens.shape
    page = cache.page_size
    rows = cache.page_table  # [slots, pages_per_slot]
    positions = cache.seq_lens[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]  # [S, K1]
    page_idx = jnp.clip(positions // page, 0, rows.shape[1] - 1)
    write_page_ids = jnp.where(active[:, None], jnp.take_along_axis(rows, page_idx, axis=1), 0)
    write_offsets = jnp.where(active[:, None], positions % page, 0)

    x = qembed(params["embed"], tokens)  # [slots, K1, D]
    kv_pos = jnp.arange(cache.kv_span, dtype=jnp.int32)[None, None, None, :]
    mask = jnp.where(
        kv_pos <= positions[:, None, :, None], 0.0, -jnp.inf
    ).astype(jnp.float32)  # [S, 1, K1, K]

    def attend(_kind, q, k_pages, v_pages, table, _layer):
        return _paged_attention(q, k_pages, v_pages, table, mask)

    x, k_pages, v_pages, _pairs = _run_layers(
        params, cfg, x, positions, [(write_page_ids.reshape(-1), write_offsets.reshape(-1), rows)], cache, attend
    )
    logits = _logits(params, cfg, x)  # [slots, K1, V]
    cache = cache._replace(k_pages=k_pages, v_pages=v_pages)
    return logits, cache


# -- which decode attention a process runs (ops/paged_attention.py) ------------


def resolve_attn_impl() -> str:
    """The static `attn_impl` of `paged_decode_step` that an engine runs: the
    Pallas page-streaming kernel on a TPU, the gather path everywhere else.
    `kernel_interpret` (the kernel's body run by the interpreter, for parity
    runs off the chip) is a value only a caller of the step passes."""
    return "kernel" if jax.default_backend() == "tpu" else "gather"


# prompt-length buckets: one prefill executable per bucket serves every
# prompt that pads into it (mirrors sampling.DECODE_CHUNK's
# one-executable-per-length discipline)
PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


def prefill_bucket(n: int, max_len: int) -> int:
    for b in PREFILL_BUCKETS:
        if b >= n and b <= max_len:
            return b
    return max_len
