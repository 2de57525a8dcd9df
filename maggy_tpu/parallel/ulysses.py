"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The second context-parallel scheme SURVEY.md §5.7 calls for: activations arrive
sharded on sequence; two ``all_to_all`` collectives re-shard them to
head-parallel (full sequence, H/n heads per device), attention runs locally
with any kernel, and the inverse all-to-all restores sequence sharding. Ideal
when n divides the head count and sequence lengths are moderate — one pair of
all-to-alls costs less than a full KV ring rotation for short S.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from maggy_tpu.ops.attention import _repeat_kv, blockwise_attention
from maggy_tpu.parallel.spec import AXIS_SEQ


def _local_ulysses(
    q, k, v, seg, *, axis_name: str, num_shards: int, causal: bool,
    attn_fn: Callable, use_segments: bool,
):
    # local: [B, C, H, D] with C = S/n; re-shard to [B, S, H/n, D]
    def seq_to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    if use_segments:
        # head-parallel attention sees the FULL sequence, so every device
        # needs the full [B, S] segment ids — an all_gather of the int
        # shard (a few KB, nothing next to the qkv all-to-alls)
        seg_full = jax.lax.all_gather(seg, axis_name, axis=1, tiled=True)
        out = attn_fn(qh, kh, vh, causal=causal, segment_ids=seg_full)
    else:
        out = attn_fn(qh, kh, vh, causal=causal)
    return heads_to_seq(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    axis_name: str = AXIS_SEQ,
    attn_fn: Optional[Callable] = None,
    segment_ids=None,
):
    """Global-view Ulysses attention: q [B,S,H,D] sharded on S over
    ``axis_name``; requires n | H and n | Kh (the all-to-all splits heads).

    ``segment_ids`` [B, S] (sharded on S) enables packed sequences: the local
    head-parallel attention receives the all-gathered full-length ids and
    masks across segment boundaries."""
    num_shards = mesh.shape[axis_name]
    h, kh = q.shape[2], k.shape[2]
    if num_shards > 1 and kh % num_shards != 0:
        # broadcast GQA heads so the all-to-all can split them
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
        kh = h
    inner = attn_fn or (
        lambda q, k, v, causal=True, segment_ids=None: blockwise_attention(
            q, k, v, causal=causal, segment_ids=segment_ids
        )
    )
    if num_shards == 1:
        return inner(q, k, v, causal=causal, segment_ids=segment_ids)
    if h % num_shards != 0:
        raise ValueError(
            f"Ulysses needs the seq-axis size ({num_shards}) to divide the head "
            f"count ({h}); use ring attention instead."
        )
    use_segments = segment_ids is not None
    if not use_segments:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)  # uniform dummy
    spec = P(None, axis_name, None, None)
    fn = functools.partial(
        _local_ulysses,
        axis_name=axis_name,
        num_shards=num_shards,
        causal=causal,
        attn_fn=inner,
        use_segments=use_segments,
    )
    return shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, P(None, axis_name)),
        out_specs=spec, check_vma=False,
    )(q, k, v, segment_ids)


def make_ulysses_attention(mesh, axis_name: str = AXIS_SEQ, attn_fn=None):
    def attn(q, k, v, *, causal: bool = True, segment_ids=None):
        return ulysses_attention(
            q, k, v, mesh=mesh, causal=causal, axis_name=axis_name,
            attn_fn=attn_fn, segment_ids=segment_ids,
        )

    return attn
