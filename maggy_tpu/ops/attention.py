"""Blockwise attention with online softmax.

The memory-efficient attention substrate (absent from the reference — SURVEY.md
§5.7 'green-field, required by the north star'): instead of materializing the
[S, S] score matrix, KV is processed in blocks with running (max, denominator,
accumulator) statistics — the FlashAttention/blockwise-attention recurrence.
The same block-update rule drives three consumers:

* :func:`blockwise_attention` — single-device, ``lax.scan`` over KV blocks
  (XLA fuses it; ``jax.checkpoint`` on the body keeps the backward at block
  granularity too);
* :func:`maggy_tpu.parallel.ringattention.ring_attention` — the scan runs over
  *devices*, rotating KV shards along the ``seq`` ICI ring with ``ppermute``;
* :mod:`maggy_tpu.ops.flash` — the Pallas TPU kernel, same math in VMEM tiles.

All statistics are fp32 regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """GQA: broadcast KV heads up to the query head count."""
    kh = k.shape[2]
    if kh == n_heads:
        return k
    return jnp.repeat(k, n_heads // kh, axis=2)


def online_block_update(
    carry: Tuple[jax.Array, jax.Array, jax.Array],
    q: jax.Array,
    k_blk: jax.Array,
    v_blk: jax.Array,
    mask: Optional[jax.Array],
    scale: float,
):
    """One online-softmax step over a KV block.

    carry = (acc [B,H,Q,D] fp32, m [B,H,Q] fp32 running max,
             l [B,H,Q] fp32 running denominator); q [B,Q,H,D];
    k_blk/v_blk [B,Kb,H,D]; mask broadcastable to [B,H,Q,Kb] (True = attend).
    """
    acc, m, l = carry
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # fully-masked-so-far rows keep m = NEG_INF; exp(NEG_INF - NEG_INF) would be
    # exp(0)=1, so clamp the shift to stay a true no-op for those rows
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk, preferred_element_type=jnp.float32
    )
    acc_new = acc * corr[..., None] + pv
    return acc_new, m_new, l_new


def _finalize(acc: jax.Array, l: jax.Array, dtype) -> jax.Array:
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,H,Q,D]
    return out.transpose(0, 2, 1, 3).astype(dtype)  # [B,Q,H,D]


# public surface for cross-module consumers (flash kernel, ring, KV-cache
# decode) — same objects, stable contracts
repeat_kv = _repeat_kv
finalize = _finalize


def init_carry(b: int, h: int, q: int, d: int):
    return (
        jnp.zeros((b, h, q, d), jnp.float32),
        jnp.full((b, h, q), NEG_INF, jnp.float32),
        jnp.zeros((b, h, q), jnp.float32),
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_k", "remat_blocks")
)
def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    block_k: int = 512,
    remat_blocks: bool = True,
    bound: Optional[jax.Array] = None,
) -> jax.Array:
    """Memory-efficient attention, drop-in for
    :func:`maggy_tpu.models.transformer.default_attention`.

    q [B,S,H,D]; k/v [B,S,Kh,D] (GQA broadcast internally); never materializes
    more than [B,H,S,block_k] scores. ``bound`` ([B, Sq] int32, with
    ``causal``): the query at row index ``t`` sees the keys at ``s <= bound[t]``
    in place of ``s <= t`` (``ops.flash.flash_attention``'s causal bound a query).
    """
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    sk = k.shape[1]
    block_k = min(block_k, sk)
    n_blocks = (sk + block_k - 1) // block_k
    pad = n_blocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-1)

    scale = 1.0 / (d**0.5)
    q_hi = jnp.arange(sq)[None] if bound is None else bound  # the last key index a query sees
    kv_pos = jnp.arange(n_blocks * block_k)

    k_blocks = k.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    kpos_blocks = kv_pos.reshape(n_blocks, block_k)
    if segment_ids is not None:
        seg_blocks = segment_ids.reshape(b, n_blocks, block_k).transpose(1, 0, 2)
    else:
        seg_blocks = jnp.zeros((n_blocks, 1, 1), jnp.int32)  # unused placeholder

    def body(carry, blk):
        k_blk, v_blk, kpos, seg = blk
        mask = jnp.ones((1, 1, sq, block_k), bool)
        if causal:
            mask = mask & (q_hi[:, None, :, None] >= kpos[None, None, None, :])
        mask = mask & (kpos < sk)[None, None, None, :]  # padding
        if segment_ids is not None:
            qseg = segment_ids[:, :sq]
            mask = mask & (qseg[:, None, :, None] == seg[:, None, None, :])
        return online_block_update(carry, q, k_blk, v_blk, mask, scale), None

    if remat_blocks:
        body = jax.checkpoint(body, prevent_cse=False)

    carry = init_carry(b, h, sq, d)
    xs = (k_blocks, v_blocks, kpos_blocks, seg_blocks)
    (acc, _, l), _ = jax.lax.scan(body, carry, xs)
    return _finalize(acc, l, q.dtype)
