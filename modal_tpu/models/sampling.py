"""Decode: prefill + fused greedy generation with a static KV cache.

TPU-first: generation runs as ONE compiled program (`lax.scan` over decode
steps, cache donated so XLA updates HBM in place) — a single dispatch for
the whole sequence instead of a host↔device round trip per token.
`greedy_generate` decodes in fixed-size chunks so ONE executable
serves any generation length (no per-length recompiles); `decode_step`
remains for callers that need token-at-a-time streaming.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .llama import KVCache, LlamaConfig, forward


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def prefill(params: dict, cfg: LlamaConfig, tokens: jax.Array, cache: KVCache):
    """Run the prompt through the model, filling the cache.
    Returns (last_token_logits [B, V], cache). The incoming (empty) cache is
    donated — ISSUE 20 donation audit: without it prefill held TWO full KV
    caches live (the dead input + the filled output), doubling peak HBM for
    the largest transient buffer in serving."""
    logits, cache = forward(params, cfg, tokens, cache=cache)
    return logits[:, -1, :], cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: dict, cfg: LlamaConfig, token: jax.Array, cache: KVCache):
    """One token in, one distribution out. token: [B, 1]."""
    logits, cache = forward(params, cfg, token, cache=cache)
    return logits[:, -1, :], cache


@partial(jax.jit, static_argnames=("cfg", "num_tokens"), donate_argnames=("cache",))
def decode_tokens(
    params: dict,
    cfg: LlamaConfig,
    first_token: jax.Array,  # [B, 1]
    cache: KVCache,
    num_tokens: int,
):
    """Generate `num_tokens` greedily inside ONE compiled program
    (`lax.scan` over decode steps). One dispatch for the whole generation:
    per-step python dispatch costs a host↔device round trip per token, the
    scan costs one.

    Returns (tokens [B, num_tokens], final_token [B, 1], cache)."""

    def step(carry, _):
        tok, c = carry
        logits, c = forward(params, cfg, tok, cache=c)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1, keepdims=True).astype(jnp.int32)
        return (nxt, c), tok

    (final_tok, cache), toks = lax.scan(step, (first_token, cache), length=num_tokens)
    # toks: [T, B, 1] — emitted tokens INCLUDE first_token, exclude final
    return toks[:, :, 0].T, final_tok, cache


DECODE_CHUNK = 64  # one compiled program serves any length (pad + truncate)


def greedy_generate(
    params: dict,
    cfg: LlamaConfig,
    prompt: jax.Array,  # [B, S] int32
    max_new_tokens: int,
    cache_len: Optional[int] = None,
) -> jax.Array:
    """Greedy decode. Returns [B, S + max_new_tokens].

    Decodes in DECODE_CHUNK-token fused scans: every chunk reuses the same
    compiled executable, so varying generation lengths never recompile
    (waste is at most CHUNK-1 surplus steps on the final chunk, truncated
    from the output). Falls back to one exact-length scan when the cache
    has no room for the padding."""
    b, s = prompt.shape
    n_chunks = -(-max_new_tokens // DECODE_CHUNK)
    padded = n_chunks * DECODE_CHUNK
    cache_len = cache_len or cfg.max_seq_len
    if s + max_new_tokens > cache_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds cache_len ({cache_len})"
        )
    cache = KVCache.create(cfg, b, cache_len)
    logits, cache = prefill(params, cfg, prompt, cache)
    next_tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    if s + padded > cache_len:
        # not enough cache for chunk padding: single exact-length program
        toks, _final, _cache = decode_tokens(params, cfg, next_tok, cache, max_new_tokens)
        return jnp.concatenate([prompt, toks], axis=1)
    pieces = []
    for _ in range(n_chunks):
        toks, next_tok, cache = decode_tokens(params, cfg, next_tok, cache, DECODE_CHUNK)
        pieces.append(toks)
    out = jnp.concatenate(pieces, axis=1)[:, :max_new_tokens]
    return jnp.concatenate([prompt, out], axis=1)


@jax.jit
def _sync_probe(leaves):
    # one fused program touching every input buffer — a single dispatch +
    # one scalar transfer, instead of a host round trip per leaf
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + leaf.ravel()[0].astype(jnp.float32)
    return total


def host_sync(tree) -> None:
    """Force completion of every buffer in `tree` by pulling a dependent
    scalar to host: the bytes are in host memory when device_get returns."""
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(tree) if hasattr(leaf, "ravel")]
    if not leaves:
        return
    jax.device_get(_sync_probe(leaves))


def benchmark_decode(
    params: dict,
    cfg: LlamaConfig,
    batch: int = 1,
    prompt_len: int = 128,
    gen_len: int = 128,
    cache_len: int = 1024,
) -> dict:
    """Measure prefill + decode throughput. Returns timing dict (seconds,
    tokens/sec)."""
    prompt = jnp.ones((batch, prompt_len), jnp.int32)
    cache = KVCache.create(cfg, batch, cache_len)

    # All timings sync by pulling a result to host (device_get of a small
    # dependent array): the bytes are in host memory when it returns, and
    # the transfer itself (KBs) is noise.
    t0 = time.perf_counter()
    logits, cache = prefill(params, cfg, prompt, cache)
    jax.device_get(logits[:, :8])
    prefill_compile_s = time.perf_counter() - t0

    next_tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    # AOT-compile the FUSED decode program (whole generation = one lax.scan
    # = one dispatch — per-token python dispatch costs a host↔device round
    # trip per step). lower().compile() builds the executable WITHOUT
    # executing, so no second cache allocation.
    t0 = time.perf_counter()
    compiled_decode = decode_tokens.lower(params, cfg, next_tok, cache, gen_len).compile()
    decode_compile_s = time.perf_counter() - t0

    # timed steady-state fused generation (uses the real prefilled cache;
    # the AOT executable takes only the non-static args)
    t0 = time.perf_counter()
    toks, next_tok, cache = compiled_decode(params, next_tok, cache)
    jax.device_get(toks)
    decode_s = time.perf_counter() - t0

    # timed prefill (warm)
    cache2 = KVCache.create(cfg, batch, cache_len)
    t0 = time.perf_counter()
    logits2, cache2 = prefill(params, cfg, prompt, cache2)
    jax.device_get(logits2[:, :8])
    prefill_s = time.perf_counter() - t0

    # device telemetry (observability/device_telemetry.py): steady-state
    # step-time histograms + a post-run HBM sample ride the metrics plane
    from ..observability.device_telemetry import observe_step_time, sample_device_memory

    observe_step_time(decode_s / max(1, gen_len), "decode")
    observe_step_time(prefill_s, "prefill")
    sample_device_memory()

    return {
        "prefill_compile_s": prefill_compile_s,
        "decode_compile_s": decode_compile_s,
        "prefill_s": prefill_s,
        "prefill_tokens_per_s": batch * prompt_len / prefill_s,
        "decode_s": decode_s,
        "decode_tokens_per_s": batch * gen_len / decode_s,
        "ms_per_token": decode_s / gen_len * 1000,
    }


def _filter_logits(logits: jax.Array, temperature: float, top_k: int) -> jax.Array:
    """Temperature scale + top-k mask (shared by the fused scan and the
    first-token path so both sample the same distribution)."""
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        # lax.top_k: O(V) threshold, not a full-vocab sort in the hot loop
        kth = lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


# -- batched per-slot sampling (serving tier, ISSUE 12) ------------------------
# The continuous-batching engine samples every decode step's [slots, V]
# logits in ONE fixed-shape executable. Unlike `_filter_logits` above,
# temperature/top_k/top_p here are per-row DATA, not static args — admission
# mixing greedy and sampled requests never changes the executable.


def filter_logits_batched(
    logits: jax.Array,  # [N, V] float32
    temperature: jax.Array,  # [N] float — 0 = greedy (handled by caller)
    top_k: jax.Array,  # [N] int32 — 0 = no top-k cut
    top_p: jax.Array,  # [N] float — 1.0 = no nucleus cut
) -> jax.Array:
    """Per-row temperature / top-k / top-p filtering with all knobs as data.
    One descending sort serves both cuts; rows with top_k=0 / top_p=1 pass
    through untouched (the thresholds degenerate to the row minimum)."""
    n, v = logits.shape
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [N, V]
    rows = jnp.arange(n)
    # top-k: mask logits strictly below the k-th largest (k=0 ⇒ keep all)
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    kth = sorted_desc[rows, k_eff - 1]  # [N]
    # top-p: smallest prefix of the sorted distribution with mass >= top_p;
    # the cutoff logit is where the cumulative softmax first crosses top_p
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut_idx = jnp.argmax(cum >= top_p[:, None], axis=-1)  # first crossing
    # top_p >= 1 keeps everything — and guards argmax's all-False → 0 when
    # float error leaves cum[-1] just under 1.0
    pth = jnp.where(top_p < 1.0, sorted_desc[rows, cut_idx], -jnp.inf)
    thresh = jnp.maximum(kth, pth)
    return jnp.where(scaled < thresh[:, None], -jnp.inf, scaled)


@jax.jit
def sample_step(
    logits: jax.Array,  # [N, V] float32 — one position's logits per row
    seeds: jax.Array,  # [N] int32 — per-request PRNG seed
    indices: jax.Array,  # [N] int32 — the sampled token's index in its stream
    temperature: jax.Array,  # [N] float32
    top_k: jax.Array,  # [N] int32
    top_p: jax.Array,  # [N] float32
) -> jax.Array:
    """Sample one token per row. The key for row i is
    `fold_in(PRNGKey(seeds[i]), indices[i])` — a pure function of THAT
    request's seed and position, never of batch composition. This is what
    makes sampled streams bit-reproducible under mid-decode joins and
    preemption/re-prefill: companions change neither the row's logits (the
    decode step is row-wise math in one fixed-shape executable) nor its key.
    temperature <= 0 rows take the raw argmax (exact greedy, key unused)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filtered = filter_logits_batched(logits, temperature, top_k, top_p)
    keys = jax.vmap(lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i))(seeds, indices)
    sampled = jax.vmap(lambda k, lg: jax.random.categorical(k, lg))(keys, filtered).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


@partial(jax.jit, static_argnames=("cfg", "num_tokens", "top_k"), donate_argnames=("cache",))
def sample_tokens(
    params: dict,
    cfg: LlamaConfig,
    first_token: jax.Array,  # [B, 1]
    cache: KVCache,
    num_tokens: int,
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
):
    """Temperature / top-k sampling, fused like `decode_tokens` (one
    compiled scan = one dispatch for the whole generation)."""

    def step(carry, step_key):
        tok, c = carry
        logits, c = forward(params, cfg, tok, cache=c)
        logits = _filter_logits(logits[:, -1, :], temperature, top_k)
        nxt = jax.random.categorical(step_key, logits, axis=-1)[:, None].astype(jnp.int32)
        return (nxt, c), tok

    keys = jax.random.split(key, num_tokens)
    (final_tok, cache), toks = lax.scan(step, (first_token, cache), keys)
    return toks[:, :, 0].T, final_tok, cache


def sample_generate(
    params: dict,
    cfg: LlamaConfig,
    prompt: jax.Array,  # [B, S] int32
    max_new_tokens: int,
    *,
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
    cache_len: Optional[int] = None,
) -> jax.Array:
    """Stochastic decode (temperature + optional top-k). Returns
    [B, S + max_new_tokens]. Chunked like `greedy_generate` so one compiled
    executable serves any generation length."""
    b, s = prompt.shape
    cache_len = cache_len or cfg.max_seq_len
    if s + max_new_tokens > cache_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds cache_len "
            f"({cache_len}) — the KV cache would overflow"
        )
    n_chunks = -(-max_new_tokens // DECODE_CHUNK)
    padded = n_chunks * DECODE_CHUNK
    cache = KVCache.create(cfg, b, cache_len)
    logits, cache = prefill(params, cfg, prompt, cache)
    first_key, gen_key = jax.random.split(key)
    first_logits = _filter_logits(logits, temperature, top_k)
    next_tok = jax.random.categorical(first_key, first_logits, axis=-1)[:, None].astype(jnp.int32)
    if s + padded > cache_len:
        # no room for chunk padding: one exact-length program
        toks, _final, _cache = sample_tokens(
            params, cfg, next_tok, cache, max_new_tokens, gen_key, temperature, top_k
        )
        return jnp.concatenate([prompt, toks], axis=1)
    pieces = []
    for chunk_key in jax.random.split(gen_key, n_chunks):
        toks, next_tok, cache = sample_tokens(
            params, cfg, next_tok, cache, DECODE_CHUNK, chunk_key, temperature, top_k
        )
        pieces.append(toks)
    out = jnp.concatenate(pieces, axis=1)[:, :max_new_tokens]
    return jnp.concatenate([prompt, out], axis=1)
