"""Ring attention: sequence/context parallelism over the ICI ring.

Absent from the reference (SURVEY.md §5.7) and first-class here: the global
sequence is sharded over the ``seq`` mesh axis; each device computes attention
for its query shard while KV shards rotate around the ring via
``jax.lax.ppermute`` (XLA lowers neighbor permutes onto ICI links and overlaps
them with the per-step compute). Per-device memory stays O(S/n · S/n) per
block and the full [S, S] score matrix never exists anywhere.

The per-step math is the shared online-softmax block update from
:mod:`maggy_tpu.ops.attention`, so ring attention is numerically the blockwise
schedule with blocks distributed over devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from maggy_tpu.ops import attention as ops_attn
from maggy_tpu.parallel.spec import AXIS_SEQ


def _local_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg: jax.Array,
    *,
    axis_name: str,
    num_shards: int,
    causal: bool,
    use_segments: bool,
):
    """Runs on each device under shard_map: q [B,C,H,D], k/v [B,C,Kh,D] local
    seq shards. KV rotates at its native (grouped) head count — broadcasting to
    the query head count happens per-step on the compute side, so GQA pays
    h/kh times less ICI traffic. With ``use_segments``, the [B,C] segment-id
    shard rotates alongside KV and scores are masked where query and key
    segments differ (packed sequences, SURVEY §5.7)."""
    b, c, h, d = q.shape
    scale = 1.0 / (d**0.5)
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = my_idx * c + jnp.arange(c)

    def body(step, carry):
        acc, m, l, k_cur, v_cur, seg_cur = carry
        src = (my_idx - step) % num_shards  # which KV chunk we hold this step
        k_pos = src * c + jnp.arange(c)
        if causal:
            mask = (q_pos[None, None, :, None] >= k_pos[None, None, None, :])
        else:
            mask = jnp.ones((1, 1, c, c), bool)
        if use_segments:
            mask = mask & (
                seg[:, None, :, None] == seg_cur[:, None, None, :]
            )
        acc, m, l = ops_attn.online_block_update(
            (acc, m, l),
            q,
            ops_attn._repeat_kv(k_cur, h),
            ops_attn._repeat_kv(v_cur, h),
            mask,
            scale,
        )
        # rotate KV (and its segment ids) to the next device; device i
        # receives the chunk from i-1
        perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        seg_nxt = (
            jax.lax.ppermute(seg_cur, axis_name, perm) if use_segments else seg_cur
        )
        return acc, m, l, k_nxt, v_nxt, seg_nxt

    carry = (*ops_attn.init_carry(b, h, c, d), k, v, seg)
    acc, m, l, _, _, _ = jax.lax.fori_loop(0, num_shards, body, carry)
    return ops_attn._finalize(acc, l, q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    axis_name: str = AXIS_SEQ,
    segment_ids=None,
):
    """Global-view ring attention: q [B,S,H,D], k/v [B,S,Kh,D] sharded on S.

    Call under ``jit`` with the mesh active; works as the Decoder's
    ``attention_fn`` when the sharding spec has ``sp > 1``. XLA schedules the
    ``ppermute`` rotation against the block compute; fully differentiable.

    :param segment_ids: optional [B, S] int ids for packed sequences (sharded
        on S like q/k/v); tokens only attend within their own segment. The
        segment-id shard rotates around the ring with its KV shard.
    """
    num_shards = mesh.shape[axis_name]
    if num_shards == 1:
        return ops_attn.blockwise_attention(
            q, k, v, causal=causal, segment_ids=segment_ids
        )
    spec = P(None, axis_name, None, None)
    seg_spec = P(None, axis_name)
    use_segments = segment_ids is not None
    if not use_segments:
        # uniform dummy (never read): keeps one shard_map signature
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)
    fn = functools.partial(
        _local_ring_attention,
        axis_name=axis_name,
        num_shards=num_shards,
        causal=causal,
        use_segments=use_segments,
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, segment_ids)


def make_ring_attention(mesh, axis_name: str = AXIS_SEQ):
    """Build an ``attention_fn`` for DecoderConfig: same signature as
    ``default_attention``."""

    def attn(q, k, v, *, causal: bool = True, segment_ids=None):
        return ring_attention(
            q, k, v, mesh=mesh, causal=causal, axis_name=axis_name,
            segment_ids=segment_ids,
        )

    return attn
