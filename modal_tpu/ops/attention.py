"""Pallas flash attention for TPU.

The hot op of both judged workloads (decode + pretrain). XLA's fused
attention is good; this kernel keeps the softmax statistics in VMEM and never
materializes the [S, S] score matrix in HBM — the standard flash-attention
trade that matters once S is large (long-context prefill), and the building
block the ring-attention path shards over chips.

Grid: (batch, heads, q_blocks); the kernel loops over K/V blocks with online
softmax (running max/sum), accumulating in fp32. Causal masking by global
position. Block sizes default to the MXU/VPU-friendly 128 lane width
(see /opt/skills/guides/pallas_guide.md).

`flash_attention` chooses by platform and shape (`flash_kernel_refusal`
says which and why): the kernels on a TPU for block-aligned causal calls
that fit the VMEM staging budget, the plain einsum path everywhere else
(pallas interpret mode is used in tests).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from ..config import logger

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30

# The kernels stage the full K/V (forward, dQ) or Q/dO (dK/dV) for one
# (batch, head) into VMEM per grid step, and no `vmem_limit_bytes` is passed
# to the pallas_calls, so Mosaic's default scoped limit applies: 16 MiB on a
# v5e (my chip run, PR 21, head dim 128 bf16: S=8192 compiled forward and
# backward; S=32768 compiled forward but the dK/dV kernel was refused,
# "scoped allocation 16.25M exceeded limit 16.00M" — its two staged operands
# alone are 16 MiB; S=48384 was refused forward). The budget stays under
# that limit with room for the blocks and accumulators, so a longer sequence
# takes the einsum path instead of failing to compile. Raising it means
# raising the scoped limit on the calls, or tiling K/V through the grid.
VMEM_STAGED_BUDGET_BYTES = 12 * 1024 * 1024


def _fits_vmem_budget(q: jax.Array, k: jax.Array) -> bool:
    skv, d = k.shape[1], k.shape[3]
    s = q.shape[1]
    itemsize = jnp.dtype(q.dtype).itemsize
    # fwd/dQ: K+V staged [skv, d]; dK/dV: Q+dO staged [s, d] (+ fp32 lse/delta)
    staged = 2 * max(s, skv) * d * itemsize + 2 * max(s, skv) * 4
    return staged <= VMEM_STAGED_BUDGET_BYTES


def _flash_kernel(
    q_ref,  # [block_q, head_dim]
    k_ref,  # [S, head_dim]
    v_ref,  # [S, head_dim]
    o_ref,  # [block_q, head_dim]
    lse_ref,  # [block_q, 1] — logsumexp per query row (backward needs it)
    *,
    sm_scale: float,
    block_k: int,
    causal: bool,
    block_q: int,
):
    # All row statistics are kept (block_q, 1)-shaped: Mosaic's block rule
    # wants the last two dims of every ref (8, 128)-aligned or full, and the
    # VPU handles 2D vectors natively; interpret mode accepts rank-1 but the
    # real lowering does not.
    q_blk = pl.program_id(2)
    seq_len = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * sm_scale
    q_pos = q_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[1]), jnp.float32)

    num_k_blocks = seq_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T  # [block_q, block_k]
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + p @ v
        return m_new, l_new, acc_new

    if causal:
        # k blocks up to (and including) this q block's diagonal — CEILING
        # division so a partial diagonal block (block_k > block_q) is still
        # visited; the in-loop mask trims it exactly
        last_block = jnp.minimum(num_k_blocks, -(-((q_blk + 1) * block_q) // block_k))
    else:
        last_block = num_k_blocks
    m, l, acc = lax.fori_loop(0, last_block, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l_safe)


def _flash_dq_kernel(
    q_ref,  # [block_q, d]
    k_ref,  # [S, d]
    v_ref,  # [S, d]
    do_ref,  # [block_q, d]
    lse_ref,  # [block_q, 1]
    delta_ref,  # [block_q, 1] — rowsum(dO * O)
    dq_ref,  # [block_q, d]
    *,
    sm_scale: float,
    block_k: int,
    causal: bool,
    block_q: int,
):
    """dQ = (P ∘ (dP - delta)) @ K, recomputing P from the saved logsumexp —
    the standard flash-attention backward (no [S, S] materialization)."""
    q_blk = pl.program_id(2)
    seq_len = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]  # [block_q, 1]
    delta = delta_ref[...]  # [block_q, 1]
    q_pos = q_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    num_k_blocks = seq_len // block_k

    def body(kb, acc):
        k = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * sm_scale
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # exact probs via saved lse
        dp = do @ v.T
        ds = p * (dp - delta) * sm_scale
        return acc + ds @ k

    if causal:
        # ceiling division: include the partial diagonal K block
        last_block = jnp.minimum(num_k_blocks, -(-((q_blk + 1) * block_q) // block_k))
    else:
        last_block = num_k_blocks
    acc0 = jnp.zeros((block_q, q_ref.shape[1]), jnp.float32)
    dq_ref[...] = lax.fori_loop(0, last_block, body, acc0).astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref,  # [S, d]
    k_ref,  # [block_k, d]
    v_ref,  # [block_k, d]
    do_ref,  # [S, d]
    lse_ref,  # [S, 1]
    delta_ref,  # [S, 1]
    dk_ref,  # [block_k, d]
    dv_ref,  # [block_k, d]
    *,
    sm_scale: float,
    block_k: int,
    causal: bool,
    block_q: int,
):
    """dV = Pᵀ @ dO and dK = dSᵀ @ Q, iterating over the query blocks this
    K/V block is visible to (for causal: q blocks at/after the diagonal)."""
    k_blk = pl.program_id(2)
    seq_len = q_ref.shape[0]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    k_pos = k_blk * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    num_q_blocks = seq_len // block_q

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q = q_ref[pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(qb * block_q, block_q), :]  # [block_q, 1]
        delta = delta_ref[pl.ds(qb * block_q, block_q), :]
        s = (q @ k.T) * sm_scale  # [block_q, block_k]
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc = dv_acc + p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta) * sm_scale
        dk_acc = dk_acc + ds.T @ q
        return dk_acc, dv_acc

    if causal:
        first_block = (k_blk * block_k) // block_q  # earlier q rows can't see this k
    else:
        first_block = 0
    zeros = jnp.zeros((k_ref.shape[0], k_ref.shape[1]), jnp.float32)
    dk, dv = lax.fori_loop(first_block, num_q_blocks, body, (zeros, zeros))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _blocks(s: int, skv: int, block_q: int, block_k: int) -> tuple[int, int]:
    block_q = min(block_q, s)
    block_k = min(block_k, skv)
    if s % block_q or skv % block_k:
        raise ValueError(f"seq lengths ({s},{skv}) must divide block sizes ({block_q},{block_k})")
    return block_q, block_k


def _flash_forward(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [B,S,H,D], lse [B,H,S,1])."""
    b, s, h, d = q.shape
    skv = k.shape[1]
    if causal and s != skv:
        raise ValueError(
            f"causal flash attention requires Sq == Sk (got {s} != {skv}): the kernel "
            "aligns q and k at position 0; cached/chunked calls need an explicit mask"
        )
    block_q, block_k = _blocks(s, skv, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)

    # layout: [B, H, S, D] so the grid tiles (batch, head, q block)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal, block_q=block_q
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            # lse rides a trailing unit dim: Mosaic requires the last two
            # block dims be (8,128)-aligned or full, which a squeezed rank-1
            # block can't satisfy
            pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def flash_attention_pallas(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, Skv, H, D] (kv heads already repeated to H)
    v: jax.Array,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_backward(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # [B, H, S, 1]
    do: jax.Array,  # [B, S, H, D]
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, s, h, d = q.shape
    skv = k.shape[1]
    block_q, block_k = _blocks(s, skv, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)

    # delta = rowsum(dO ∘ O) — cheap elementwise, XLA fuses it
    delta = jnp.einsum(
        "bshd,bshd->bhs",
        do.astype(jnp.float32),
        out.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )[..., None]  # [B, H, S, 1] to match the lse block layout

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)

    dq_kernel = functools.partial(
        _flash_dq_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal, block_q=block_q
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal, block_q=block_q
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, skv // block_k),
        in_specs=[
            pl.BlockSpec((None, None, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, skv, d), v.dtype),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)
    return dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_causal(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable causal flash attention (pallas forward AND backward —
    training never materializes the [S, S] score matrix)."""
    return _flash_forward(q, k, v, True, block_q, block_k, interpret)[0]


def _flash_vjp_fwd(q, k, v, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, True, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, do, True, block_q, block_k, interpret)


flash_attention_causal.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_kernel_refusal(q: jax.Array, k: jax.Array, mask: Optional[jax.Array]) -> str:
    """Why this call cannot take the Pallas kernels ('' = it can). The one
    place the kernel-vs-einsum choice is made, from what the code can observe
    (platform, mask, shape), so callers and logs can say which path ran.
    Only the sequence length and head dim matter for the shape test: batch
    and heads are grid dimensions, so the answer is the same per shard."""
    try:
        platform = next(iter(q.devices())).platform
    except Exception:  # tracers raise ConcretizationTypeError under jit
        platform = jax.default_backend()
    if platform != "tpu":
        return f"platform is {platform}, not tpu"
    if mask is not None:
        return "explicit mask (cached/chunked call): the kernel assumes 0-aligned causal positions"
    s = q.shape[1]
    if s < DEFAULT_BLOCK_Q or s % DEFAULT_BLOCK_Q:
        return f"sequence length {s} is not a multiple of the {DEFAULT_BLOCK_Q}-row block"
    if not _fits_vmem_budget(q, k):
        return f"full-sequence K/V staging for S={s} exceeds VMEM_STAGED_BUDGET_BYTES"
    return ""


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Drop-in for models.llama.attention (same attn_impl contract:
    `mask=None` = pure causal, q/k aligned at position 0, requires Sq == Sk).
    Pallas kernel on TPU for block-aligned causal calls; einsum elsewhere.
    KV-cache/chunked-prefill calls must pass an explicit mask and take the
    einsum path — the kernel assumes 0-aligned positions. Runs at trace time,
    so the path taken is logged once per compiled shape, not per step."""
    if mask is None and q.shape[1] != k.shape[1]:
        raise ValueError(
            f"mask=None implies aligned causal attention but Sq={q.shape[1]} != Sk={k.shape[1]}; "
            "pass the cache visibility mask for cached/chunked calls"
        )
    refusal = flash_kernel_refusal(q, k, mask)
    if not refusal:
        logger.debug(f"flash_attention{q.shape}: pallas kernels")
        # custom_vjp: differentiable, so the training path can use it too
        return flash_attention_causal(q, k, v)
    logger.debug(f"flash_attention{q.shape}: einsum path ({refusal})")
    from ..models.llama import attention as einsum_attention

    return einsum_attention(q, k, v, mask)


def make_sharded_flash_attention(
    mesh: Mesh, batch_axes: tuple = ("data", "fsdp"), head_axis: Optional[str] = "model"
):
    """attn_impl for a jit whose activations are sharded over `mesh`. The
    SPMD partitioner cannot split a Mosaic custom call: left alone, lowering
    fails ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map" — what the train step did on a v5e 2x2, PR 21).
    Attention needs no communication across batch rows or heads, so the
    kernel runs
    under `shard_map` with the batch over `batch_axes` and the heads over
    `head_axis`, each device on its own block. Calls the kernels refuse
    (off-TPU, masked, unaligned) go to `flash_attention` unwrapped — the
    einsum path is ordinary XLA the partitioner shards by itself."""
    if head_axis is not None and mesh.shape.get(head_axis, 1) <= 1:
        head_axis = None
    spec = P(batch_axes, None, head_axis, None)

    def _impl(q, k, v, mask):
        if flash_kernel_refusal(q, k, mask):
            return flash_attention(q, k, v, mask)
        return jax.shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, None),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return _impl
