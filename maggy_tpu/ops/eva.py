"""EVA attention: exact keys inside a window, one learned summary a chunk
before it, one softmax over both.

A row of ``S`` positions is cut on its own grid into windows of ``window``
positions and chunks of ``chunk`` (``chunk`` divides ``window``, ``window``
divides ``S``). A query at position ``t`` of document ``d(t)`` sees

* the exact keys of its own window up to itself that lie in its document
  (``L(t)``: the *local* part), and
* one summary key and value for every chunk of an earlier window whose last
  position lies in its document (``R(t)``: the *remote* part),

under one softmax over both sets (``eva_attention``). A chunk's summary
(``summaries``) is a softmax-weighted mean of the chunk's keys and of its
values over the positions that lie in the document of the chunk's last
position, the weights from the keys' product with one learned vector a head
(``phi``), and a second learned vector (``mu``) added to the key.

**No kernel of its own.** The two parts are calls of ``ops/flash.py``'s
kernels as they stand, and what joins them is the rows' log-sum-exp:

* local: the windows are folded into the grid's rows, ``[B*H, S, D]`` read as
  ``[B*H*S/window, window, D]`` (a view, no copy), with the segment ids of
  each window beside them: plain causal packed attention on rows of one
  window, so the visit table bounds the tiles by the diagonal and the
  documents as it does everywhere, and no tile outside a window exists;
* remote: the ``S`` queries against the ``S / chunk`` summaries under a
  selection (int8 ``[B, S, S / chunk]``: the summary's document is the
  query's and its window an earlier one), which the kernels take as a tile
  beside the others and whose empty tiles the visit table drops in both
  orders (``needed_tiles(selected=)``);
* merge: ``lse = logaddexp(lse_l, lse_r)`` and the two outputs weighted by
  ``exp(lse_x - lse)``, float32.

The backward needs nothing new either: FlashAttention's recurrence makes a
tile's probabilities from the row's log-sum-exp and ``delta = rowsum(dO * O)``,
and given the **joint** ``lse`` and ``O`` each part's kernel returns its own
share of the joint softmax's gradient (``flash_bwd`` on the folded rows: dq,
dk, dv; ``flash_bwd`` under the selection: dq, and the summaries' dk and dv,
which flow on to ``phi`` and ``mu`` through ``summaries``). The two dq add.
Kept for the backward, and named as the flash kernels' results are
(``FLASH_RESIDUALS``) so that every recompute policy keeps them: the joint
output and log-sum-exp. A recomputed layer's replay rebuilds q, k, v and the
summaries around them and runs no kernel.

Off the chip, and for shapes the kernels cannot tile, ``eva_attention_xla`` is
the same mathematics in plain ``jax.numpy``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from maggy_tpu.ops import flash
from maggy_tpu.ops.attention import NEG_INF

_LANES = 128


def check_grid(s: int, window: int, chunk: int) -> None:
    """A row is a whole number of windows, a window a whole number of chunks."""
    if chunk < 1 or window % chunk or s % window:
        raise ValueError(
            f"EVA attention cuts a row of {s} into windows of {window} and chunks of {chunk}: "
            "the chunk divides the window and the window the row"
        )


def summaries(k, v, phi, mu, segment_ids, chunk: int):
    """``(ks, vs)`` [B, S / chunk, H, D]: a chunk's summary key and value.
    Over the chunk's positions ``M`` that lie in the document of its last one,
    ``a = softmax over M of (k_j . phi_h / sqrt(D))``, ``ks = sum a_j k_j +
    mu_h``, ``vs = sum a_j v_j``; the softmax and the sums in float32. k, v
    [B, S, H, D] (k after the rotary embedding); phi, mu [H, D]."""
    b, s, h, d = k.shape
    n = s // chunk
    kc, vc = k.reshape(b, n, chunk, h, d), v.reshape(b, n, chunk, h, d)
    scores = jnp.einsum(
        "bnchd,hd->bnch", kc, phi.astype(k.dtype), preferred_element_type=jnp.float32
    ) * (1.0 / d**0.5)
    if segment_ids is not None:
        seg = segment_ids.reshape(b, n, chunk)
        scores = jnp.where((seg == seg[:, :, -1:])[..., None], scores, NEG_INF)
    a = jax.nn.softmax(scores, axis=2)[..., None]
    ks = (a * kc.astype(jnp.float32)).sum(axis=2) + mu.astype(jnp.float32)
    vs = (a * vc.astype(jnp.float32)).sum(axis=2)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def remote_mask(segment_ids, window: int, chunk: int):
    """bool [B, S, S / chunk]: query ``t`` sees chunk ``c``'s summary where the
    chunk's last position lies in ``t``'s document and the chunk in a window
    before ``t``'s."""
    s = segment_ids.shape[1]
    chunk_doc = segment_ids[:, chunk - 1::chunk]
    xp = np if isinstance(segment_ids, np.ndarray) else jnp
    earlier = (xp.arange(s // chunk) * chunk // window)[None, :] < (xp.arange(s) // window)[:, None]
    return (segment_ids[:, :, None] == chunk_doc[:, None, :]) & earlier[None]


def eva_attention_xla(q, k, v, ks, vs, segment_ids, *, window: int, chunk: int):
    """The two sets of keys under one softmax, in plain ``jax.numpy``:
    q, k, v [B, S, H, D], ks, vs [B, S / chunk, H, D] -> [B, S, H, D]. Scores,
    softmax and sums in float32; a window's ``[window, window]`` block of
    local scores beside the ``[S, S / chunk]`` remote ones."""
    b, s, h, d = q.shape
    check_grid(s, window, chunk)
    if k.shape[2] != h:
        raise ValueError("EVA attention takes as many key heads as query heads")
    n = s // window
    scale = 1.0 / d**0.5
    seg = jnp.ones((b, s), jnp.int32) if segment_ids is None else segment_ids
    fold = lambda a: a.reshape(b, n, window, *a.shape[2:])
    local = jnp.einsum("bwqhd,bwkhd->bhwqk", fold(q), fold(k), preferred_element_type=jnp.float32) * scale
    inside = (fold(seg)[:, :, :, None] == fold(seg)[:, :, None, :]) & jnp.tril(jnp.ones((window, window), bool))
    local = jnp.where(inside[:, None], local, NEG_INF).reshape(b, h, s, window)
    remote = jnp.einsum("bqhd,bkhd->bhqk", q, ks, preferred_element_type=jnp.float32) * scale
    seen = remote_mask(seg, window, chunk)[:, None]
    remote = jnp.where(seen, remote, NEG_INF)
    m = jnp.maximum(local.max(-1), remote.max(-1))[..., None]
    p_local = jnp.where(inside[:, None].reshape(b, 1, s, window), jnp.exp(local - m), 0.0)
    p_remote = jnp.where(seen, jnp.exp(remote - m), 0.0)
    denom = p_local.sum(-1) + p_remote.sum(-1)
    out = jnp.einsum(
        "bhwqk,bwkhd->bwqhd", p_local.reshape(b, h, n, window, window).astype(v.dtype), fold(v),
        preferred_element_type=jnp.float32,
    ).reshape(b, s, h, d) + jnp.einsum("bhqk,bkhd->bqhd", p_remote.astype(vs.dtype), vs, preferred_element_type=jnp.float32)
    return (out / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# --------------------------------------------------------------- on the kernels


def tiles(s: int, window: int, chunk: int, head_dim: int) -> dict:
    """The tile sizes of the two parts' kernels, ``(block_q, block_k,
    bwd_block_q, bwd_block_k)`` each: the local part's are what a packed row
    of one window takes anywhere (``flash._auto_blocks``); the remote part's
    q tile is the widest the local part runs (512 or 1,024 rows) and its key
    tile up to 512 summaries (the summaries of four windows of 2,048 at chunk
    16, so a query block of the second to fifth window visits one)."""
    local = flash._auto_blocks(window, window, True, head_dim)
    n = s // chunk
    bq, bk = flash._pick_divisor(s, 1024), flash._pick_divisor(n, 512)
    return {"local": local, "remote": (bq, bk, flash._pick_divisor(s, 512), bk)}


def untileable(s: int, window: int, chunk: int, head_dim: int, compiled: bool) -> Optional[str]:
    """Why the kernels cannot take this call (None: they can): the local part
    is a segmented call on rows of one window, the remote one a masked call
    of ``S`` queries on ``S / chunk`` keys, whose int8 tile wants 32 rows and
    128 lanes compiled."""
    t = tiles(s, window, chunk, head_dim)
    why = flash._untileable(window, window, head_dim, *t["local"], True, compiled)
    if why is None:
        why = flash._untileable(s, s // chunk, head_dim, *t["remote"], False, compiled)
    if why is None and compiled and any(b % _LANES for b in t["remote"]):
        why = f"the selection's tile {t['remote']} is not a multiple of {_LANES} both ways"
    return why


def _rows_of_lanes(lse, rows: int, s: int):
    """A kernel's log-sum-exp column ``[rows', blocks, block, 1]`` as rows of
    128 lanes ``[rows, S / 128, 128]`` (``flash.py`` keeps it so between its
    passes: the column is padded to 128 lanes by a TPU layout)."""
    lanes = _LANES if s % _LANES == 0 else 1
    return lse.reshape(rows, s // lanes, lanes)


def join_by_lse(o_a, lse_a, o_b, lse_b):
    """Two parts of one softmax joined by their rows' log-sum-exp: outputs
    ``[rows, S, D]`` each normalised over its own set of keys, log-sum-exp as
    rows of lanes ``[rows, S / 128, 128]`` float32 -> the output over both sets
    (in ``o_b``'s type) and the joint log-sum-exp, in float32. Part ``b`` may
    see nothing (the kernels write +inf there, read as -inf here); part ``a``
    always sees a key."""
    rows, s, _ = o_b.shape
    lse_b = jnp.where(lse_b == jnp.inf, -jnp.inf, lse_b)
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse).reshape(rows, s, 1)
    w_b = jnp.exp(lse_b - lse).reshape(rows, s, 1)
    return (o_a.astype(jnp.float32) * w_a + o_b.astype(jnp.float32) * w_b).astype(o_b.dtype), lse


@functools.lru_cache(maxsize=None)
def _core(window: int, chunk: int, heads: int, local: tuple, remote: tuple, interpret: bool):
    """Differentiable EVA attention on q, k, v [B*H, S, D], ks, vs
    [B*H, S / chunk, D], the windows' segment ids [B*H*S/window, 1, window]
    and the remote selection int8 [B, S, S / chunk] (no cotangent to either)."""
    kw_l = dict(causal=True, group=1, heads=1, interpret=interpret)
    kw_r = dict(causal=False, group=1, heads=heads, interpret=interpret)

    def bounds_local(segs, block_q, block_k, outer):
        return jnp.asarray(flash.visit_bounds(
            segs, outer, causal=True, sq=window, sk=window, block_q=block_q, block_k=block_k,
        ))

    def bounds_remote(sel, block_q, block_k, outer):
        return jnp.asarray(flash.visit_bounds(
            None, outer, causal=False, sq=sel.shape[1], sk=sel.shape[2], block_q=block_q, block_k=block_k,
            selected=sel,
        ))

    def forward(q, k, v, ks, vs, segs, sel):
        bh, s, d = q.shape
        fold = lambda a: a.reshape(-1, window, d)
        with jax.named_scope("eva.local"):
            o_l, lse_l = flash._fwd_call(
                fold(q), fold(k), fold(v), segs, bounds_local(segs, local[0], local[1], "q"),
                block_q=local[0], block_k=local[1], **kw_l,
            )
        with jax.named_scope("eva.remote"):
            o_r, lse_r = flash._fwd_call(
                q, ks, vs, None, bounds_remote(sel, remote[0], remote[1], "q"), sel,
                block_q=remote[0], block_k=remote[1], **kw_r,
            )
        with jax.named_scope("eva.merge"):
            # a query with no summary in sight reads +inf from the remote kernel
            o, lse = join_by_lse(o_l.reshape(bh, s, d), _rows_of_lanes(lse_l, bh, s), o_r, _rows_of_lanes(lse_r, bh, s))
        return o, lse

    @jax.custom_vjp
    def core(q, k, v, ks, vs, segs, sel):
        return forward(q, k, v, ks, vs, segs, sel)[0]

    def core_fwd(q, k, v, ks, vs, segs, sel):
        o, lse = forward(q, k, v, ks, vs, segs, sel)
        o = checkpoint_name(o, flash.FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse, flash.FLASH_RESIDUALS[1])
        return o, (q, k, v, ks, vs, segs, sel, o, lse)

    def core_bwd(res, g):
        q, k, v, ks, vs, segs, sel, o, lse = res
        bh, s, d = q.shape
        fold = lambda a: a.reshape(-1, window, d)
        g = g.astype(o.dtype)
        with jax.named_scope("eva.local"):
            dq_l, dk, dv = flash._bwd_call(
                fold(q), fold(k), fold(v), fold(o), fold(g),
                lse.reshape(-1, window // local[2], local[2], 1), segs,
                functools.partial(bounds_local, segs, local[2], local[3]),
                block_q=local[2], block_k=local[3], **kw_l,
            )
        with jax.named_scope("eva.remote"):
            dq_r, dks, dvs = flash._bwd_call(
                q, ks, vs, o, g, lse.reshape(bh, s // remote[2], remote[2], 1), None,
                functools.partial(bounds_remote, sel, remote[2], remote[3]), sel,
                block_q=remote[2], block_k=remote[3], **kw_r,
            )
        with jax.named_scope("eva.merge"):
            dq = (dq_l.reshape(bh, s, d).astype(jnp.float32) + dq_r.astype(jnp.float32)).astype(q.dtype)
        return dq, dk.reshape(bh, s, d), dv.reshape(bh, s, d), dks, dvs, None, None

    core.defvjp(core_fwd, core_bwd)
    return core


@functools.partial(jax.jit, static_argnames=("window", "chunk", "interpret"))
def eva_attention(q, k, v, ks, vs, segment_ids=None, *, window: int, chunk: int, interpret: Optional[bool] = None):
    """q, k, v [B, S, H, D], ks, vs [B, S / chunk, H, D] (``summaries``) ->
    [B, S, H, D], on the flash kernels (the module docstring), differentiable
    in all five. ``interpret`` defaults to the Pallas interpreter off a TPU. A
    shape the kernels cannot tile raises, naming the dimension; callers that
    want a silent choice use ``models.transformer.auto_eva_attention``."""
    b, s, h, d = q.shape
    check_grid(s, window, chunk)
    if k.shape[2] != h:
        raise ValueError("EVA attention takes as many key heads as query heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = untileable(s, window, chunk, d, compiled=not interpret)
    if why is not None:
        raise ValueError(f"eva_attention cannot run on the flash kernels for q{q.shape}, window {window}, chunk {chunk}: {why}")
    t = tiles(s, window, chunk, d)
    seg = jnp.ones((b, s), jnp.int32) if segment_ids is None else segment_ids.astype(jnp.int32)
    rows = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, a.shape[1], d)
    # a window's ids, once a head: the folded rows are (batch, head, window) and the visit table has a row each
    segs = jnp.broadcast_to(seg.reshape(b, 1, s // window, 1, window), (b, h, s // window, 1, window))
    sel = remote_mask(seg, window, chunk).astype(jnp.int8)
    out = _core(window, chunk, h, t["local"], t["remote"], bool(interpret))(
        rows(q), rows(k), rows(v), rows(ks), rows(vs), segs.reshape(-1, 1, window), sel,
    )
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def remote_tiles_needed(seg, window: int, chunk: int, block_q: int, block_k: int):
    """bool [S / block_q, S / chunk / block_k] for one row's segment ids
    (numpy): the tiles of the remote grid that hold a summary some query of
    the block sees, found from the documents' runs alone (no ``[S, S / chunk]``
    mask on the host): a document's queries in a block see the chunks that end
    inside the document and lie in a window before the last of those queries'."""
    s = len(seg)
    need = np.zeros((s // block_q, s // chunk // block_k), bool)
    starts = np.flatnonzero(np.diff(seg, prepend=seg[0] - 1))
    for a, b in zip(starts, [*starts[1:], s]):  # one document: positions a..b-1
        first_chunk, last_chunk = -(-(a - chunk + 1) // chunk), (b - chunk) // chunk
        for qi in range(a // block_q, (b - 1) // block_q + 1):
            last_query = min(b, (qi + 1) * block_q) - 1
            seen_to = min(last_chunk, last_query // window * (window // chunk) - 1)  # the last chunk before its window
            if seen_to >= max(first_chunk, 0):
                need[qi, max(first_chunk, 0) // block_k: seen_to // block_k + 1] = True
    return need


def tiles_visited_share(segment_ids, *, window: int, chunk: int, head_dim: int = 128):
    """Of the tiles in the two forward kernels' grids for a packed host batch
    (``segment_ids`` [B, S], numpy), the share they visit: the folded local
    grid's first-to-last needed blocks and the remote grid's, which the
    selection bounds (``remote_tiles_needed``: the same tiles as
    ``needed_tiles(selected=)`` finds in the mask). None where the tiles do
    not divide the row."""
    seg = np.asarray(segment_ids)
    b, s = seg.shape
    if chunk < 1 or window % chunk or s % window:
        return None
    t = tiles(s, window, chunk, head_dim)
    (lq, lk), (rq, rk) = t["local"][:2], t["remote"][:2]
    if window % lq or window % lk or s % rq or (s // chunk) % rk:
        return None
    first, last = flash.visit_bounds(
        seg.reshape(-1, 1, window), "q", causal=True, sq=window, sk=window, block_q=lq, block_k=lk,
    ).reshape(-1, 2).T
    local = float((last - first + 1).sum())
    remote = 0
    for row in seg:  # a query block's first to last needed block, as the kernel walks them
        need = remote_tiles_needed(row, window, chunk, rq, rk)
        first, last = need.argmax(1), need.shape[1] - 1 - need[:, ::-1].argmax(1)
        remote += int(np.where(need.any(1), last - first + 1, 0).sum())
    grid = b * (s // window) * (window // lq) * (window // lk) + b * (s // rq) * (s // chunk // rk)
    return (local + remote) / grid
