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

The noised queries' own block is ``block`` keys a query (4), and documents are
not multiples of the block long, so blocks do not lie on the row's grid: the
own block is a band around the diagonal, and two kernels of its own compute it
(``own_block_fwd``, ``own_block_bwd``, under the scope ``diffusion.merge``).
Their grid is (batch x key heads, tiles of the row, the group's heads) with no
reduction axis: a step holds a tile of one head's queries and the noised keys
and values at the same rows with a halo of ``block - 1`` rows either side
(padded to the sublanes), from an array XLA builds once a layer
(``_with_halo``), and masks a product to block-mates by comparing a block id a
query and a key (``block_ids``). The softmax is one tile's and continues the
flash call's: the forward takes that call's output and log-sum-exp and writes
the joint ones; the backward is FlashAttention's recurrence on them:
``flash_bwd`` returns the clean keys' share and the queries', ``own_block_bwd``
adds the own block's dq to it and returns dk and dv of each tile and halo,
summed over the group's heads, which the transpose of ``_with_halo`` adds back
onto the rows. Kept for the backward and named as the flash kernels' results
are, so that every recompute policy keeps them: the joint output and
log-sum-exp.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from maggy_tpu.ops import flash
from maggy_tpu.ops.attention import NEG_INF
from maggy_tpu.ops.eva import _rows_of_lanes


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

# queries a product of the band kernels: one tile of lanes, so a query's
# numbers (log-sum-exp, block id, ``delta``) are rows of full registers and a
# product's keys, ``_SUB + 2 * halo`` of them, whole sublanes with none idle
_SUB = 128


def band_tiles(l: int, block: int) -> tuple:
    """``(tq, sub, halo)`` of the band kernels for a stream of ``l`` positions,
    from the shape alone: a grid step holds ``tq`` queries and the keys at the
    same rows with ``halo`` rows more on either side (``block - 1`` padded to
    the sublanes), and computes them ``sub`` queries a product against the
    ``sub + 2 * halo`` keys around them. At the sdar-30b-a3b-chat cell's shape
    (2 x 8,192, 32 heads over 4 of 128, the packed8k rows) the call, forward
    and backward, took 28.35 ms on one v5e at tiles of 256 rows, 27.01 at 512
    and 26.88 at 1,024 (PERF.md section 6, PR 44): a grid step costs more
    than the halo a larger tile saves."""
    tq = flash._pick_divisor(l, 1024)
    return tq, (_SUB if tq % _SUB == 0 else tq), -(-(block - 1) // 8) * 8


def block_ids(lay: Layout, segment_ids):
    """int32 [B, L], from 1: one number a run of neighbours in one document
    and block, so that a kernel compares a query's with a key's as the flash
    kernels compare segment ids: where positions count up inside a document
    the pairs of equal numbers are ``Layout.own``'s (padding, all at one
    position, is a block a row there and up to ``block`` rows here: no real
    query sees it either way). 0 is no block's."""
    start = lay.hi_noised + 1
    new = (start[:, 1:] != start[:, :-1]) | (segment_ids[:, 1:] != segment_ids[:, :-1])
    return 1 + jnp.pad(jnp.cumsum(new.astype(jnp.int32), axis=1), ((0, 0), (1, 0)))


def _with_halo(a, tq: int, halo: int):
    """``a`` [R, L, ...] in tiles with their neighbours' edges
    [R, L / tq, tq + 2 * halo, ...]: tile ``t`` holds the rows ``t * tq - halo``
    to ``(t + 1) * tq + halo``, zeros outside the row. Linear: its transpose
    adds the edges back onto the neighbours' rows."""
    r, l = a.shape[:2]
    tiles = a.reshape(r, l // tq, tq, *a.shape[2:])
    none = jnp.zeros_like(tiles[:, :1, :halo])
    before = jnp.concatenate([none, tiles[:, :-1, tq - halo:]], axis=1)
    after = jnp.concatenate([tiles[:, 1:, :halo], none], axis=1)
    return jnp.concatenate([before, tiles, after], axis=2)


def _nt(a, b):
    """``a b^T`` in float32: both operands contract over the head's width."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b`` in float32: both operands contract over their rows."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _mates(kid_ref, qid_ref, win, rows):
    """bool [keys, queries]: the pairs of one block."""
    return kid_ref[0, 0, win] == qid_ref[0, :, rows]


def _own_fwd_kernel(q_ref, k_ref, v_ref, kid_ref, qid_ref, oc_ref, lsec_ref, o_ref, lse_ref, *, scale, sub, width):
    """One head's tile of noised queries on their own blocks, continuing the
    flash call's softmax over the clean keys (``oc``, ``lsec``): the scores lie
    keys by queries, so a query's maximum, sum and log-sum-exp are rows."""
    for j in range(q_ref.shape[1] // sub):
        rows, win = pl.ds(j * sub, sub), pl.ds(j * sub, width)
        v = v_ref[0, 0, win]
        s = jnp.where(_mates(kid_ref, qid_ref, win, rows), _nt(k_ref[0, 0, win], q_ref[0, rows]) * scale, NEG_INF)
        m = s.max(axis=0, keepdims=True)  # a query is its own block-mate: never empty
        e = jnp.exp(s - m)
        lse_n = m + jnp.log(e.sum(axis=0, keepdims=True))
        lse_c = lsec_ref[0, :, rows]
        lse_c = jnp.where(lse_c == jnp.inf, -jnp.inf, lse_c)  # the kernel's mark of a query that saw no clean key
        top = jnp.maximum(lse_n, lse_c)
        lse = top + jnp.log(jnp.exp(lse_n - top) + jnp.exp(lse_c - top))
        own = _tn((e * jnp.exp(m - lse)).astype(v.dtype), v)
        w_c = jnp.exp(lse_c - lse).reshape(sub, 1)
        o_ref[0, rows] = (own + w_c * oc_ref[0, rows].astype(jnp.float32)).astype(o_ref.dtype)
        lse_ref[0, :, rows] = lse


def _own_bwd_kernel(
    q_ref, g_ref, k_ref, v_ref, kid_ref, qid_ref, lse_ref, delta_ref, dqc_ref, dq_ref, dk_ref, dv_ref,
    *, scale, sub, width,
):
    """FlashAttention's recurrence on the same tile from the joint log-sum-exp:
    dq on top of the flash call's, dk and dv of the tile's keys and halo summed
    over the group's heads (the innermost grid axis; float32, resident)."""
    @pl.when(pl.program_id(2) == 0)
    def _first_head():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    for j in range(q_ref.shape[1] // sub):
        rows, win = pl.ds(j * sub, sub), pl.ds(j * sub, width)
        q, g, k = q_ref[0, rows], g_ref[0, rows], k_ref[0, 0, win]
        s = jnp.where(_mates(kid_ref, qid_ref, win, rows), _nt(k, q) * scale, NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rows])
        ds = (p * (_nt(v_ref[0, 0, win], g) - delta_ref[0, :, rows]) * scale).astype(q.dtype)
        dq_ref[0, rows] = (dqc_ref[0, rows].astype(jnp.float32) + _tn(ds, k)).astype(dq_ref.dtype)
        dk_ref[0, 0, win] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dv_ref[0, 0, win] += jnp.dot(p.astype(g.dtype), g, preferred_element_type=jnp.float32)


def _own_call(kernel, name, operands, outs, *, band, group, kv_heads, interpret, aliases=None):
    """One band kernel on the grid (batch * key heads, tiles, the group's
    heads): ``operands`` and ``outs`` pair each array (or result's shape) with
    the name of its BlockSpec. The keys' tile stays in VMEM while the group's
    heads pass; ids are a batch row's, a query's numbers ``[.., 1, L]`` as the
    flash kernels take segment ids."""
    tq, sub, halo = band
    bh, l, d = operands[0][1].shape

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    sp = dict(
        q=spec((1, tq, d), lambda i, t, g: (i * group + g, t, 0)),
        kv=spec((1, 1, tq + 2 * halo, d), lambda i, t, g: (i, t, 0, 0)),
        kid=spec((1, 1, tq + 2 * halo, 1), lambda i, t, g: (i // kv_heads, t, 0, 0)),
        qid=spec((1, 1, tq), lambda i, t, g: (i // kv_heads, 0, t)),
        row=spec((1, 1, tq), lambda i, t, g: (i * group + g, 0, t)),
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / d**0.5, sub=sub, width=sub + 2 * halo),
        grid=(bh // group, l // tq, group),
        in_specs=[sp[which] for which, _ in operands],
        out_specs=[sp[which] for which, _ in outs],
        out_shape=[shape for _, shape in outs],
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(*(a for _, a in operands))


def _own_keys(k_n, v_n, ids, band):
    """What both band kernels take of the noised keys: keys and values as rows
    in tiles with halos, and the block ids a key (a column) and a query."""
    tq, _, halo = band
    b, l = ids.shape
    return [
        ("kv", _with_halo(_rows(k_n), tq, halo)), ("kv", _with_halo(_rows(v_n), tq, halo)),
        ("kid", _with_halo(ids[:, :, None], tq, halo)), ("qid", ids.reshape(b, 1, l)),
    ]


def _rows(a):
    b, l, h, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _unrows(a, b):
    return a.reshape(b, -1, *a.shape[1:]).transpose(0, 2, 1, 3)


def _bounds(l, segs, hi, bq, bk, outer):
    return jnp.asarray(flash.visit_bounds(
        segs, outer, causal=True, sq=l, sk=l, block_q=bq, block_k=bk, bound=hi,
    ))


def _forward(tiles, band, interpret, q, k_c, v_c, k_n, v_n, segs, hi, ids):
    """``_core``'s forward: the joint output ``[B * H, L, D]`` and the joint
    log-sum-exp as rows of lanes, as the backward keeps them."""
    block_q, block_k = tiles[:2]
    b, l, h, d = q.shape
    kh = k_c.shape[2]
    qr = _rows(q)
    o_c, lse_c = flash._fwd_call(
        qr, _rows(k_c), _rows(v_c), segs, _bounds(l, segs, hi, block_q, block_k, "q"), hi=hi,
        block_q=block_q, block_k=block_k, causal=True, group=h // kh, heads=h, interpret=interpret,
    )
    with jax.named_scope("diffusion.merge"):
        o, lse = _own_call(
            _own_fwd_kernel, "own_block_fwd",
            [("q", qr), *_own_keys(k_n, v_n, ids, band), ("q", o_c), ("row", lse_c.reshape(b * h, 1, l))],
            [("q", jax.ShapeDtypeStruct(qr.shape, q.dtype)), ("row", jax.ShapeDtypeStruct((b * h, 1, l), jnp.float32))],
            band=band, group=h // kh, kv_heads=kh, interpret=interpret,
        )
        return o, _rows_of_lanes(lse, b * h, l)


@functools.lru_cache(maxsize=None)
def _core(tiles: tuple, band: tuple, interpret: bool):
    """Differentiable attention of the noised stream on q [B, L, H, D], the
    clean and the noised keys and values [B, L, Kh, D], the segment ids and
    the queries' bounds over the clean keys int32 [B, 1, L] and the block ids
    [B, L] (no cotangent to the last three); ``tiles`` the flash calls' four,
    ``band`` the band kernels' (``band_tiles``)."""
    bwd_block_q, bwd_block_k = tiles[2:]
    tq, _, halo = band
    forward = functools.partial(_forward, tiles, band, interpret)

    @jax.custom_vjp
    def core(q, k_c, v_c, k_n, v_n, segs, hi, ids):
        return _unrows(forward(q, k_c, v_c, k_n, v_n, segs, hi, ids)[0], q.shape[0])

    def core_fwd(q, k_c, v_c, k_n, v_n, segs, hi, ids):
        o, lse = forward(q, k_c, v_c, k_n, v_n, segs, hi, ids)
        o = checkpoint_name(o, flash.FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse, flash.FLASH_RESIDUALS[1])
        return _unrows(o, q.shape[0]), (q, k_c, v_c, k_n, v_n, segs, hi, ids, o, lse)

    def core_bwd(res, g):
        q, k_c, v_c, k_n, v_n, segs, hi, ids, o, lse = res
        b, l, h, d = q.shape
        kh = k_c.shape[2]
        group = h // kh
        qr, gr = _rows(q), _rows(g.astype(o.dtype))
        dq_c, dk_h, dv_h = flash._bwd_call(
            qr, _rows(k_c), _rows(v_c), o, gr, lse.reshape(b * h, l // bwd_block_q, bwd_block_q, 1), segs,
            functools.partial(_bounds, l, segs, hi, bwd_block_q, bwd_block_k), hi=hi,
            block_q=bwd_block_q, block_k=bwd_block_k, causal=True, group=group, heads=h, interpret=interpret,
        )
        dk_c, dv_c = flash.sum_groups(dk_h, dv_h, group, k_c.dtype, v_c.dtype)
        with jax.named_scope("diffusion.merge"):
            delta = (gr.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
            edges = jax.ShapeDtypeStruct((b * kh, l // tq, tq + 2 * halo, d), jnp.float32)
            dq, dk_t, dv_t = _own_call(
                _own_bwd_kernel, "own_block_bwd",
                [
                    ("q", qr), ("q", gr), *_own_keys(k_n, v_n, ids, band),
                    ("row", lse.reshape(b * h, 1, l)), ("row", delta.reshape(b * h, 1, l)), ("q", dq_c),
                ],
                [("q", jax.ShapeDtypeStruct(qr.shape, q.dtype)), ("kv", edges), ("kv", edges)],
                band=band, group=group, kv_heads=kh, interpret=interpret, aliases={8: 0},
            )
            # the halos back onto their neighbours' rows: the transpose of the tiling
            untiled = jax.linear_transpose(
                functools.partial(_with_halo, tq=tq, halo=halo), jax.ShapeDtypeStruct((b * kh, l, d), jnp.float32)
            )
            dk_n, dv_n = untiled(dk_t)[0].astype(k_n.dtype), untiled(dv_t)[0].astype(v_n.dtype)
        return _unrows(dq, b), _unrows(dk_c, b), _unrows(dv_c, b), _unrows(dk_n, b), _unrows(dv_n, b), None, None, None

    core.defvjp(core_fwd, core_bwd)
    return core


def untileable(l: int, head_dim: int, block: int, compiled: bool) -> Optional[str]:
    """Why the kernels cannot take a stream of ``l`` positions (None: they
    can): a segmented, bounded call of ``l`` queries on ``l`` keys, and the
    band kernels' tiles of it."""
    why = flash._untileable(l, l, head_dim, *flash._auto_blocks(l, l, True, head_dim), True, compiled)
    tq, _, halo = band_tiles(l, block)
    if why is None and (l % tq or halo > tq or (compiled and tq % _SUB)):
        why = f"the own block's tile of {tq} rows (halo {halo}) does not tile sequence length {l}"
    return why


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def noised_attention(q, k_c, v_c, k_n, v_n, segment_ids, lay: Layout, *, block: int, interpret: Optional[bool] = None):
    """The noised stream's attention on the kernels (the module docstring):
    q [B, L, H, D], the clean and the noised keys and values [B, L, Kh, D]
    -> [B, L, H, D], differentiable in all five. ``interpret`` defaults to
    the Pallas interpreter off a TPU. A shape the kernels cannot tile raises,
    naming the dimension."""
    b, l, h, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = untileable(l, d, block, compiled=not interpret)
    if why is not None:
        raise ValueError(f"noised_attention cannot run on the kernels for q{q.shape}: {why}")
    segment_ids = segment_ids.astype(jnp.int32)
    return _core(flash._auto_blocks(l, l, True, d), band_tiles(l, block), bool(interpret))(
        q, k_c, v_c, k_n, v_n, segment_ids.reshape(b, 1, l),
        lay.hi_noised.astype(jnp.int32).reshape(b, 1, l), block_ids(lay, segment_ids),
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
