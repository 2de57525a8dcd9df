"""Block-diffusion attention: a clean and a noised stream of one row through
the same layer, a block-wise mask between them.

A row holds ``L`` tokens in packed documents; a token at position ``p`` of its
document lies in block ``c = p // block``. Both streams carry the same
positions and documents. Per layer

* a **clean** query sees the clean keys of its document in its own block and
  the blocks before it (block-causal);
* a **noised** query sees the noised keys of its own block, in both
  directions, and the clean keys of the blocks before it, under one softmax;
* no clean query sees a noised key.

Both views of the clean keys are *a causal bound a query*: key ``s`` counts
iff ``s <= hi[t]`` inside the document, with ``hi[t]`` the row index of the
last token of ``t``'s block (clean on clean) or of the last token before it
(noised on clean). ``ops/flash.py``'s kernels take that bound as they stand
(``flash_attention(bound=)``): it stands in the diagonal's place in a tile's
mask and in the visit table, so a layer is **two calls** of ``flash_fwd`` /
``flash_bwd``, one a stream, about a causal call's pairs each.

The noised queries' own block is ``block`` keys a query (4): plain XLA on the
``2 * block - 1`` neighbours a block-mate can be (documents are not multiples
of the block long, so blocks do not lie on the row's grid), joined with the
kernel's part by the rows' log-sum-exp as ``ops/eva.py`` joins its two parts
(``eva.join_by_lse``). The backward is FlashAttention's recurrence on the
joint output and log-sum-exp: ``flash_bwd`` returns the clean keys' share and
the queries', the own block's share is the same four lines in XLA. Kept for
the backward and named as the flash kernels' results are, so that every
recompute policy keeps them: the joint output and log-sum-exp.

Off the chip the same mathematics runs on the explicit mask (``noised_mask``)
through the XLA attention (``models.transformer.auto_blockdiff_attention``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from maggy_tpu.ops import flash
from maggy_tpu.ops.attention import NEG_INF
from maggy_tpu.ops.eva import _rows_of_lanes, join_by_lse


class Layout(NamedTuple):
    """What the mask is made of, from one stream's positions and segment ids
    ``[B, L]`` (numpy on the host, or traced): the two bounds, int32 ``[B, L]``,
    and ``own`` bool ``[B, L, 2 * block - 1]``: whether the token ``j - (block
    - 1)`` places after the query is a block-mate of it (itself among them)."""

    hi_clean: jax.Array
    hi_noised: jax.Array
    own: jax.Array


def layout(positions, segment_ids, block: int) -> Layout:
    xp = np if isinstance(positions, np.ndarray) else jnp
    b, l = positions.shape
    at = xp.arange(l, dtype=xp.int32)[None, :]
    r = (positions % block).astype(xp.int32)
    start = at - r  # the row index of the block's first token
    own = []
    for off in range(1 - block, block):
        mate = (r + off >= 0) & (r + off < block) & (at + off >= 0) & (at + off < l)
        own.append(mate & (xp.roll(segment_ids, -off, axis=1) == segment_ids))
    return Layout(start + block - 1, start - 1, xp.stack(own, axis=-1))


def pairs(positions, segment_ids, lay: Layout, block: int):
    """``[pairs the mask keeps, causal pairs inside documents]`` of the real
    queries, float32: a token at position ``p`` with ``n`` block-mates (itself
    among them) whose block starts at position ``p0`` keeps ``p0 + n`` clean
    keys as a clean query, and ``p0`` clean and ``n`` noised ones as a noised
    query, where one causal stream keeps ``p + 1``."""
    real = (segment_ids > 0).astype(jnp.float32)
    n = lay.own.sum(-1).astype(jnp.float32)
    p = positions.astype(jnp.float32)
    p0 = p - (positions % block).astype(jnp.float32)
    return jnp.stack([((2.0 * p0 + 2.0 * n) * real).sum(), ((p + 1.0) * real).sum()])


def noised_mask(positions, segment_ids, lay: Layout, block: int):
    """bool [B, L, 2L]: what a noised query sees of the clean keys (the first
    ``L`` columns) and of the noised ones (the last ``L``)."""
    l = positions.shape[1]
    same = segment_ids[:, :, None] == segment_ids[:, None, :]
    clean = same & (jnp.arange(l)[None, None, :] <= lay.hi_noised[:, :, None])
    c = positions // block
    return jnp.concatenate([clean, same & (c[:, :, None] == c[:, None, :])], axis=-1)


# --------------------------------------------------------------- on the kernels


def _neighbours(a, block: int):
    """``a`` [B, L, Kh, D] beside itself ``2 * block - 1`` times: entry ``j`` of
    axis 2 holds the row ``j - (block - 1)`` places after (the row's ends wrap;
    ``Layout.own`` masks them)."""
    return jnp.stack([jnp.roll(a, -off, axis=1) for off in range(1 - block, block)], axis=2)


def _back_to_rows(a, block: int):
    """The transpose of ``_neighbours``: entry ``j``'s rows put back
    ``j - (block - 1)`` places and summed, float32."""
    return sum(
        jnp.roll(a[:, :, j].astype(jnp.float32), off, axis=1) for j, off in enumerate(range(1 - block, block))
    )


# The own block's products are batched ``dot_general``s in bfloat16 with float32 results. Written as
# elementwise products summed over the head's width the call alone ran 8% faster on one v5e (43.6
# against 47.3 ms forward and backward at the cell's shape), but its float32 intermediates of q's
# size made the whole step plan 14.63 GiB with 2 ``.remat`` where this form plans 12.81 with none
# (PERF.md section 6, PR 43).
def _own_scores(qg, ks, own, scale):
    s = jnp.einsum("blkgd,blwkd->blkgw", qg, ks, preferred_element_type=jnp.float32) * scale
    return jnp.where(own[:, :, None, None, :], s, NEG_INF)


@functools.lru_cache(maxsize=None)
def _core(block: int, tiles: tuple, interpret: bool):
    """Differentiable attention of the noised stream on q [B, L, H, D], the
    clean and the noised keys and values [B, L, Kh, D], the segment ids and
    the queries' bounds over the clean keys int32 [B, 1, L] and ``Layout.own``
    (no cotangent to the last three)."""
    block_q, block_k, bwd_block_q, bwd_block_k = tiles

    def rows(a):
        b, l, h, d = a.shape
        return a.transpose(0, 2, 1, 3).reshape(b * h, l, d)

    def unrows(a, b):
        return a.reshape(b, -1, *a.shape[1:]).transpose(0, 2, 1, 3)

    def bounds(l, segs, hi, bq, bk, outer):
        return jnp.asarray(flash.visit_bounds(
            segs, outer, causal=True, sq=l, sk=l, block_q=bq, block_k=bk, bound=hi,
        ))

    def forward(q, k_c, v_c, k_n, v_n, segs, hi, own):
        b, l, h, d = q.shape
        kh = k_c.shape[2]
        kw = dict(causal=True, group=h // kh, heads=h, interpret=interpret)
        o_c, lse_c = flash._fwd_call(
            rows(q), rows(k_c), rows(v_c), segs, bounds(l, segs, hi, block_q, block_k, "q"), hi=hi,
            block_q=block_q, block_k=block_k, **kw,
        )
        with jax.named_scope("diffusion.merge"):
            s = _own_scores(q.reshape(b, l, kh, h // kh, d), _neighbours(k_n, block), own, 1.0 / d**0.5)
            m = s.max(-1, keepdims=True)  # a query is its own block-mate: never empty
            p = jnp.where(own[:, :, None, None, :], jnp.exp(s - m), 0.0)
            denom = p.sum(-1, keepdims=True)
            o_n = jnp.einsum(
                "blkgw,blwkd->blkgd", (p / denom).astype(v_n.dtype), _neighbours(v_n, block),
                preferred_element_type=jnp.float32,
            ).reshape(b, l, h, d)
            lse_n = (m + jnp.log(denom)).reshape(b, l, h).transpose(0, 2, 1)
            o, lse = join_by_lse(
                rows(o_n), _rows_of_lanes(lse_n, b * h, l), o_c, _rows_of_lanes(lse_c, b * h, l),
            )
        return o, lse

    @jax.custom_vjp
    def core(q, k_c, v_c, k_n, v_n, segs, hi, own):
        return unrows(forward(q, k_c, v_c, k_n, v_n, segs, hi, own)[0], q.shape[0])

    def core_fwd(q, k_c, v_c, k_n, v_n, segs, hi, own):
        o, lse = forward(q, k_c, v_c, k_n, v_n, segs, hi, own)
        o = checkpoint_name(o, flash.FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse, flash.FLASH_RESIDUALS[1])
        return unrows(o, q.shape[0]), (q, k_c, v_c, k_n, v_n, segs, hi, own, o, lse)

    def core_bwd(res, g):
        q, k_c, v_c, k_n, v_n, segs, hi, own, o, lse = res
        b, l, h, d = q.shape
        kh = k_c.shape[2]
        group = h // kh
        g = g.astype(o.dtype)
        dq_c, dk_h, dv_h = flash._bwd_call(
            rows(q), rows(k_c), rows(v_c), o, rows(g), lse.reshape(b * h, l // bwd_block_q, bwd_block_q, 1), segs,
            functools.partial(bounds, l, segs, hi, bwd_block_q, bwd_block_k), hi=hi,
            block_q=bwd_block_q, block_k=bwd_block_k, causal=True, group=group, heads=h, interpret=interpret,
        )
        dk_c, dv_c = flash.sum_groups(dk_h, dv_h, group, k_c.dtype, v_c.dtype)
        with jax.named_scope("diffusion.merge"):
            qg, gg = q.reshape(b, l, kh, group, d), g.reshape(b, l, kh, group, d)
            og = unrows(o, b).reshape(b, l, kh, group, d)
            ks, vs = _neighbours(k_n, block), _neighbours(v_n, block)
            joint = lse.reshape(b, h, l).transpose(0, 2, 1).reshape(b, l, kh, group, 1)
            p = jnp.where(own[:, :, None, None, :], jnp.exp(_own_scores(qg, ks, own, 1.0 / d**0.5) - joint), 0.0)
            dp = jnp.einsum("blkgd,blwkd->blkgw", gg, vs, preferred_element_type=jnp.float32)
            delta = (gg.astype(jnp.float32) * og.astype(jnp.float32)).sum(-1, keepdims=True)
            ds = (p * (dp - delta) * (1.0 / d**0.5)).astype(q.dtype)
            dq_n = jnp.einsum("blkgw,blwkd->blkgd", ds, ks, preferred_element_type=jnp.float32).reshape(b, l, h, d)
            dk_n = _back_to_rows(jnp.einsum("blkgw,blkgd->blwkd", ds, qg, preferred_element_type=jnp.float32), block)
            dv_n = _back_to_rows(
                jnp.einsum("blkgw,blkgd->blwkd", p.astype(g.dtype), gg, preferred_element_type=jnp.float32), block
            )
            dq = (unrows(dq_c, b).astype(jnp.float32) + dq_n).astype(q.dtype)
        return (
            dq, unrows(dk_c, b), unrows(dv_c, b), dk_n.astype(k_n.dtype), dv_n.astype(v_n.dtype), None, None, None,
        )

    core.defvjp(core_fwd, core_bwd)
    return core


def untileable(l: int, head_dim: int, compiled: bool) -> Optional[str]:
    """Why the kernels cannot take a stream of ``l`` positions (None: they
    can): a segmented, bounded call of ``l`` queries on ``l`` keys."""
    return flash._untileable(l, l, head_dim, *flash._auto_blocks(l, l, True, head_dim), True, compiled)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def noised_attention(q, k_c, v_c, k_n, v_n, segment_ids, lay: Layout, *, block: int, interpret: Optional[bool] = None):
    """The noised stream's attention on the flash kernels (the module
    docstring): q [B, L, H, D], the clean and the noised keys and values
    [B, L, Kh, D] -> [B, L, H, D], differentiable in all five. ``interpret``
    defaults to the Pallas interpreter off a TPU. A shape the kernels cannot
    tile raises, naming the dimension."""
    b, l, h, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = untileable(l, d, compiled=not interpret)
    if why is not None:
        raise ValueError(f"noised_attention cannot run on the flash kernels for q{q.shape}: {why}")
    tiles = flash._auto_blocks(l, l, True, d)
    return _core(block, tiles, bool(interpret))(
        q, k_c, v_c, k_n, v_n, segment_ids.astype(jnp.int32).reshape(b, 1, l),
        lay.hi_noised.astype(jnp.int32).reshape(b, 1, l), lay.own,
    )


def tiles_visited_share(segment_ids, *, block: int, head_dim: int = 128):
    """Of the tiles in the two forward grids of a layer for a packed host
    batch (``segment_ids`` [B, L], numpy), the share the kernels visit: the
    clean stream's under its block-causal bound, the noised one's under the
    bound before its block. None where the tiles do not divide the row."""
    seg = np.asarray(segment_ids)
    b, l = seg.shape
    block_q, block_k = flash._auto_blocks(l, l, True, head_dim)[:2]
    if l % block_q or l % block_k:
        return None
    starts = np.concatenate([np.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    at = np.broadcast_to(np.arange(l), (b, l))
    positions = at - np.maximum.accumulate(np.where(starts, at, 0), axis=1)
    lay = layout(positions.astype(np.int32), seg, block)
    visited = 0
    for hi in (lay.hi_clean, lay.hi_noised):
        first, last = flash.visit_bounds(
            seg.reshape(b, 1, l), "q", causal=True, sq=l, sk=l, block_q=block_q, block_k=block_k,
            bound=hi.reshape(b, 1, l),
        ).reshape(-1, 2).T
        visited += int((last - first + 1).sum())
    return visited / (2 * b * (l // block_q) * (l // block_k))
