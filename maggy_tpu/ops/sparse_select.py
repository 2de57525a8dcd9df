"""Attention over the keys a learned indexer selects (DeepSeek-Sparse-Attention's
lightning indexer, training form): the index scores, the exact selection of
each query's top ``k`` keys, and the indexer's own loss.

For query ``t`` and key ``s`` of one document, ``s <= t``::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32; w carries the two scale factors)
    S_t     = the k keys of largest I[t, .], ties towards the earlier key
              (all of them while t sees at most k)

``[S, S]`` float32 does not fit beside a model at the lengths this is for, so
nothing here holds it: a block of ``Q`` queries at a time, ``I`` is made by a
Pallas kernel (``index_scores``: ``heads`` products of depth ``index_head_dim``
a tile, the relu and the weighted sum in VMEM, never ``[S, S, heads]``), a
second kernel finds each query's threshold (``topk_thresholds``: the ``k``-th
largest score exactly, by bisection over the bits of the scores' order-keeping
integer form, 32 counting passes over a block held in VMEM, the position of
the last tie it admits, and the log-sum-exp of the scores so selected), and
the selection leaves as what the flash kernels take (``ops/flash.py``
``selected``): an int8 ``[B, S, S]`` mask, one for all the heads of a layer,
compared out of the very block of scores its thresholds were found in
(``select``: one pass of ``index_scores`` in the forward). The three numbers
a query are the residual a layer keeps (``SPARSE_RESIDUALS``), recomputed or
not, and the mask is none: the flash kernels' backward makes it again from
them with a second pass of ``index_scores`` (``selection_mask``, handed over
as ``reselect``), the same kernel on the same operands and so the same bits,
and a recomputed layer's replay neither scores nor selects. Two passes a
layer and step; one would need the mask itself to live until the backward, a
byte a pair.

``index_loss`` is the indexer's objective, ``mean over real t of KL(mean over
heads of the attention's probabilities on S_t || softmax over S_t of I[t, .])``,
with its gradient to ``qI``, ``kI`` and ``w`` from the same pass. Where the
attention's kernels gave the heads' log-sum-exp (every call on a TPU) the pass
is a third kernel, ``index_loss``: a block of 512 queries at a time, over the
key tiles at or under its diagonal only, a tile's heads' scores (one
exponential a head and pair, no softmax), the index heads' products and the
two products back from them all in VMEM, ``d kI`` held there for the whole
row; ``I``'s own log-sum-exp comes from where the selection is made
(``topk_thresholds``). A call that got none (the XLA attention off the chip)
takes the blockwise ``jax.numpy`` form with its own softmax (``_loss_pass``).
The gradients are named residuals too, so the replay of a recomputed layer
drops the pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from maggy_tpu.ops.attention import NEG_INF
from maggy_tpu.ops.flash import _LANES, _optional_refs, _selected, _tile_mask
from maggy_tpu.ops.flash import _pick_divisor as _divisor

# what a recompute policy keeps of a selected-key attention layer beside the
# flash kernel's two results: each query's threshold, last admitted tie and the
# log-sum-exp of its selected scores ([B, 3, S] int32), and the indexer's three
# gradients from ``index_loss``
SPARSE_RESIDUALS = ("sparse_threshold", "sparse_index_grads")

_INT_MIN = np.iinfo(np.int32).min
_NEG_INF = float("-inf")


def order_key(x):
    """float32 -> int32 whose signed order is the floats' (no NaN; the zeros
    are one value here because ``index_scores`` writes +0.0 only)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def from_order_key(key):
    """``order_key`` back: the same exchange of the low 31 bits under a set sign."""
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


# ------------------------------------------------------------------ index scores


def _index_kernel(
    off_ref, q_ref, k_ref, w_ref, qseg_ref, kseg_ref, o_ref, *, block_q, block_k, heads, segmented
):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start = off_ref[0] + qi * block_q
    k_start = ki * block_k
    below = k_start <= q_start + block_q - 1  # the tile holds a pair at or under the diagonal

    @pl.when(below)
    def _compute():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            z = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            acc = acc + w[:, j:j + 1] * jnp.maximum(z, 0.0)
        acc = jnp.where(acc == 0.0, 0.0, acc)  # one zero: -0.0 would order below +0.0
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = rows >= cols
        if segmented:
            mask = mask & (qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :])
        o_ref[0] = jnp.where(mask, acc, _NEG_INF)

    @pl.when(jnp.logical_not(below))
    def _above():
        o_ref[0] = jnp.full((block_q, block_k), _NEG_INF, jnp.float32)


def index_scores(qi, ki, w, segs, q_start, rows: int, *, block_q=None, block_k=None, interpret=None):
    """``I`` [B, rows, S] float32 for the ``rows`` queries from ``q_start`` (a
    traced multiple of the q tile) against every key: ``qi`` [B, J, S, Dj],
    ``ki`` [B, S, Dj], ``w`` [B, S, J] float32 (scale factors in), ``segs``
    [B, 1, S] int32 or None. ``-inf`` where the key is after the query or in
    another document; tiles wholly above the diagonal are filled, not
    computed."""
    b, heads, s, dj = qi.shape
    block_q = block_q or _divisor(rows, 512)
    block_k = block_k or _divisor(s, 1024)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    segmented = segs is not None
    if not segmented:
        segs = jnp.zeros((b, 1, s), jnp.int32)  # placeholder, never read

    def q_block(i, off_ref):
        return off_ref[0] // block_q + i

    def k_block(i, r, off_ref):  # above the diagonal: the block already in VMEM
        return jnp.minimum(r, (off_ref[0] + (i + 1) * block_q - 1) // block_k)

    vmem = pltpu.VMEM
    return pl.pallas_call(
        functools.partial(
            _index_kernel, block_q=block_q, block_k=block_k, heads=heads, segmented=segmented
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, rows // block_q, s // block_k),
            in_specs=[
                pl.BlockSpec((1, heads, block_q, dj), lambda n, i, r, off: (n, 0, q_block(i, off), 0), memory_space=vmem),
                pl.BlockSpec((1, block_k, dj), lambda n, i, r, off: (n, k_block(i, r, off), 0), memory_space=vmem),
                pl.BlockSpec((1, block_q, heads), lambda n, i, r, off: (n, q_block(i, off), 0), memory_space=vmem),
                pl.BlockSpec((1, 1, block_q), lambda n, i, r, off: (n, 0, q_block(i, off)), memory_space=vmem),
                pl.BlockSpec((1, 1, block_k), lambda n, i, r, off: (n, 0, k_block(i, r, off)), memory_space=vmem),
            ],
            out_specs=pl.BlockSpec((1, block_q, block_k), lambda n, i, r, off: (n, i, r), memory_space=vmem),
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2**20,
        ),
        name="index_scores",
        interpret=interpret,
    )(jnp.asarray(q_start, jnp.int32).reshape(1), qi, ki, w, segs, segs)


# ------------------------------------------------------------------- thresholds


def _threshold_kernel(off_ref, i_ref, o_ref, keys_ref, *, k, chunk):
    rows, s = keys_ref.shape
    # key chunks that hold a pair at or under the diagonal for this block of queries
    last_row = off_ref[0] + (pl.program_id(1) + 1) * rows - 1
    n_vis = jnp.minimum(last_row // chunk + 1, s // chunk)

    def fill(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        keys_ref[:, at] = order_key(i_ref[0, :, at])
        return carry

    jax.lax.fori_loop(0, n_vis, fill, 0)

    def count(test):
        """[rows, 1]: how many keys of a row ``test(keys, columns)`` holds for."""
        def body(c, total):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
            return total + jnp.sum(test(keys_ref[:, at], cols).astype(jnp.int32), axis=1, keepdims=True)
        return jax.lax.fori_loop(0, n_vis, body, jnp.zeros((rows, 1), jnp.int32))

    # the largest T with at least k keys >= T, a bit at a time from the sign down
    # (no such T above the smallest: a row that sees fewer than k keys keeps them all)
    enough = count(lambda keys, _: keys >= 0) >= k
    t = jnp.where(enough, 0, _INT_MIN).astype(jnp.int32)

    def bit(i, t):
        cand = t | jax.lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda keys, _: keys >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, bit, t)
    above = count(lambda keys, _: keys > t)
    need = k - above  # ties at T to admit, the earliest first
    ties = count(lambda keys, _: keys == t)

    def last_tie(_):
        # the largest p with fewer than ``need`` ties before column p: the need-th tie's column
        def bit(i, p):
            cand = p | jax.lax.shift_left(jnp.int32(1), (s - 1).bit_length() - 1 - i)
            return jnp.where(count(lambda keys, cols: (keys == t) & (cols < cand)) < need, cand, p)
        return jax.lax.fori_loop(0, (s - 1).bit_length(), bit, jnp.zeros((rows, 1), jnp.int32))

    cut = (need < ties) & (need > 0)  # a tie left out: rare, and the only rows that need the column
    p = jax.lax.cond(jnp.max(cut.astype(jnp.int32)) > 0, last_tie, lambda _: jnp.zeros((rows, 1), jnp.int32), 0)
    p = jnp.where(cut, p, s - 1)

    # the log-sum-exp of the row's selected scores, which the indexer's loss wants (``index_loss``): two more
    # passes over the block here, where it lies in VMEM (as two reductions of XLA over the scores in HBM they
    # took 12.9 ms a layer at S 32,768, as one beside a maximum from here 7.3: PERF.md section 6, PR 33)
    def largest(c, top):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return jnp.maximum(top, jnp.max(keys_ref[:, at], axis=1, keepdims=True))

    top = from_order_key(jax.lax.fori_loop(0, n_vis, largest, jnp.full((rows, 1), _INT_MIN, jnp.int32)))

    def selected_exp(c, total):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        keys = keys_ref[:, at]
        cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        scores = from_order_key(keys)
        keep = (scores > _NEG_INF) & ((keys > t) | ((keys == t) & (cols <= p)))
        return total + jnp.sum(jnp.where(keep, jnp.exp(scores - top), 0.0), axis=1, keepdims=True)

    lse = top + jnp.log(jax.lax.fori_loop(0, n_vis, selected_exp, jnp.zeros((rows, 1), jnp.float32)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, o_ref.shape[2]), 1)
    o_ref[0] = jnp.where(lane == 0, t, jnp.where(lane == 1, p, jax.lax.bitcast_convert_type(lse, jnp.int32)))


def topk_thresholds(scores, q_start, k: int, *, interpret=None):
    """``[B, 3, rows]`` int32 from ``scores`` [B, rows, S] (``index_scores``'
    for the queries from ``q_start``): each row's ``k``-th largest score as
    its ``order_key``, the column of the last tie at it that the top ``k``
    still hold (ties go to the earlier key, as ``jax.lax.top_k`` breaks them),
    and the float32 bits of the log-sum-exp of the scores so selected
    (``-inf`` for a row that sees no key). A row that sees fewer than ``k``
    keys gets a threshold under every score."""
    b, rows, s = scores.shape
    block = _divisor(rows, max(8, min(128, 2**22 // s)))
    chunk = _divisor(s, 2048)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, rows // block),
            in_specs=[pl.BlockSpec((1, block, s), lambda n, i, off: (n, i, 0), memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, block, 128), lambda n, i, off: (n, i, 0), memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((block, s), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(100 * 2**20, 16 * 2**20 + 4 * block * s * 4),
        ),
        name="sparse_select",
        interpret=interpret,
    )(jnp.asarray(q_start, jnp.int32).reshape(1), scores)
    return out[:, :, :3].swapaxes(1, 2)


def selection_from(scores, thresholds):
    """bool [B, rows, S]: the pairs the thresholds keep of ``scores``."""
    keys = order_key(scores)
    t, p = thresholds[:, 0, :, None], thresholds[:, 1, :, None]
    cols = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return (scores > _NEG_INF) & ((keys > t) | ((keys == t) & (cols <= p)))


# -------------------------------------------------------------------- selection


def _query_block(s: int) -> int:
    """Queries a pass takes at a time: ``[B, Q, S]`` float32 of scores, 128 MiB
    a batch row at S 32,768."""
    return _divisor(s, 1024)


def _each_block(one, s: int):
    """``one(q_start)`` for every block of ``_query_block(s)`` queries in turn,
    the results stacked on a new leading axis."""
    q = _query_block(s)
    return jax.lax.map(one, jnp.arange(s // q, dtype=jnp.int32) * q)


def select(qi, ki, w, segs, k: int, *, interpret=None):
    """The top ``k`` keys a query, from one block of scores a block of
    queries: ``(mask int8 [B, S, S], counts int32 [3], lse [B, S] float32,
    thresholds [B, 3, S] int32)``. ``counts``: the pairs selected, the pairs
    visible (at or before the query, in its document) and the queries whose
    set is not ``min(k, visible)`` keys; ``lse``: the log-sum-exp of each
    query's selected scores (``index_loss`` wants it); ``thresholds``:
    ``topk_thresholds``', named so that a recompute policy keeps them
    (``SPARSE_RESIDUALS``) and ``selection_mask`` can give the mask again.
    Nothing here has a gradient: the indexer learns from ``index_loss``
    alone."""
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    b, _, s, _ = qi.shape
    q = _query_block(s)

    def one(q_start):
        with jax.named_scope("sparse.index"):
            scores = index_scores(qi, ki, w, segs, q_start, q, interpret=interpret)
        with jax.named_scope("sparse.select"):
            thresholds = topk_thresholds(scores, q_start, k, interpret=interpret)
        with jax.named_scope("sparse.index"):
            keep = selection_from(scores, thresholds)
            n_keep = keep.sum(-1, dtype=jnp.int32)
            n_visible = (scores > _NEG_INF).sum(-1, dtype=jnp.int32)
            counts = jnp.stack([
                n_keep.sum(), n_visible.sum(), (n_keep != jnp.minimum(n_visible, k)).sum(dtype=jnp.int32),
            ])
        return keep.astype(jnp.int8), counts, thresholds

    mask, counts, thresholds = _each_block(one, s)  # [n, B, Q, S], [n, 3], [n, B, 3, Q]
    thresholds = checkpoint_name(jnp.moveaxis(thresholds, 0, 2).reshape(b, 3, s), SPARSE_RESIDUALS[0])
    lse = jax.lax.bitcast_convert_type(thresholds[:, 2], jnp.float32)
    return jnp.moveaxis(mask, 0, 1).reshape(b, s, s), counts.sum(0), lse, thresholds


def selection_mask(qi, ki, w, segs, thresholds, *, interpret=None):
    """``select``'s mask again from its thresholds, with the index scores made
    again and nothing selected: the same kernel on the same operands, so the
    same bits. The backward of an attention under the selection calls it
    (``ops/flash.py`` ``reselect``), so that no forward's mask has to live
    until then, nor be made by a recomputed layer's replay."""
    b, _, s, _ = qi.shape
    q = _query_block(s)

    def one(q_start):
        with jax.named_scope("sparse.index"):
            scores = index_scores(qi, ki, w, segs, q_start, q, interpret=interpret)
            keep = selection_from(scores, jax.lax.dynamic_slice_in_dim(thresholds, q_start, q, axis=2))
            return keep.astype(jnp.int8)

    return jnp.moveaxis(_each_block(one, s), 0, 1).reshape(b, s, s)


# ------------------------------------------------------------- the indexer's loss

LOSS_TILE = (512, 1024)  # the kernel's tile, queries x keys
LOSS_BLOCK = 256  # blockwise: queries at a time, [B, heads of a group, 256, S] float32 of scores
LOSS_BANDS = 4  # blockwise: runs of query blocks, each against the keys up to its own end: 5/8 of the square, not all of it


def _loss_pass(q, k, qi, ki, w, mask, segs, weight, with_grads: bool):
    """``(loss, d qi, d ki, d w)`` (the gradients None unless asked for) where
    the attention gave no log-sum-exp (the XLA paths off the chip): blockwise
    ``jax.numpy``, a block of queries at a time against the keys up to its
    band's end (``LOSS_BANDS``; a selection is causal), a softmax a group of
    heads. ``q`` [B, S, H, D], ``k`` [B, S, Kh, D] (no gradient goes there),
    ``qi`` [B, J, S, Dj], ``ki`` [B, S, Dj], ``w`` [B, S, J] float32, ``mask``
    int8 [B, S, S] or None (every visible key), ``weight`` [B, S] float32 (a
    real query's share of the mean, zero on padding)."""
    b, s, h, d = q.shape
    kh, heads = k.shape[2], qi.shape[1]
    rows = _divisor(s, LOSS_BLOCK)
    n = s // rows
    bands = LOSS_BANDS if n % LOSS_BANDS == 0 else 1
    scale = 1.0 / d**0.5
    f32 = jnp.float32

    def split(a, axis=1):  # [B, S, ...] -> [n, B, rows, ...]
        return jnp.moveaxis(a.reshape(*a.shape[:axis], n, rows, *a.shape[axis + 1:]), axis, 0)

    xs = dict(
        i=jnp.arange(n, dtype=jnp.int32), q=split(q.reshape(b, s, kh, h // kh, d)), qi=split(qi, 2),
        w=split(w), weight=split(weight),
    )
    if mask is not None:
        xs["mask"] = split(mask)
    if segs is not None:
        xs["seg"] = split(segs[:, 0])

    def block(carry, x, s, k, ki):
        """One block of queries against the first ``s`` keys (a later key is
        after every query of the block's band)."""
        if mask is not None:
            vis = x["mask"][:, :, :s] != 0
        else:
            vis = (x["i"] * rows + jnp.arange(rows, dtype=jnp.int32))[None, :, None] >= jnp.arange(s, dtype=jnp.int32)
            if segs is not None:
                vis = vis & (x["seg"][:, :, None] == segs[:, 0, None, :s])

        def group(total, g):  # the heads over one key-value head
            sc = jnp.einsum("bqgd,bsd->bgqs", x["q"][:, :, g], k[:, :, g], preferred_element_type=f32) * scale
            p = jax.nn.softmax(jnp.where(vis[:, None], sc, NEG_INF), axis=-1)
            return total + jnp.where(vis, p.sum(1), 0.0), None

        target, _ = jax.lax.scan(group, jnp.zeros((b, rows, s), f32), jnp.arange(kh))
        target = target / h

        def index_head(j):
            return jnp.einsum("bqd,bsd->bqs", x["qi"][:, j], ki, preferred_element_type=f32)

        def add_head(total, j):
            return total + x["w"][:, :, j, None] * jnp.maximum(index_head(j), 0.0), None

        scores, _ = jax.lax.scan(add_head, jnp.zeros((b, rows, s), f32), jnp.arange(heads))
        logq = jax.nn.log_softmax(jnp.where(vis, scores, NEG_INF), axis=-1)
        kl = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-37)) - logq), 0.0).sum(-1)
        loss = carry[0] + (kl * x["weight"]).sum()
        if not with_grads:
            return (loss,), None
        d_scores = jnp.where(vis, jnp.exp(logq) - target, 0.0) * x["weight"][..., None]

        def back_head(d_ki, j):
            z = index_head(j)
            d_w = (d_scores * jnp.maximum(z, 0.0)).sum(-1)
            d_z = (jnp.where(z > 0, d_scores, 0.0) * x["w"][:, :, j, None]).astype(ki.dtype)
            d_qi = jnp.einsum("bqs,bsd->bqd", d_z, ki, preferred_element_type=f32)
            d_ki = d_ki + jnp.einsum("bqs,bqd->bsd", d_z, x["qi"][:, j], preferred_element_type=f32)
            return d_ki, (d_qi.astype(qi.dtype), d_w)

        d_ki, (d_qi, d_w) = jax.lax.scan(back_head, carry[1], jnp.arange(heads))
        return (loss, d_ki), (jnp.moveaxis(d_qi, 0, 1), jnp.moveaxis(d_w, 0, -1))  # [B, J, rows, Dj], [B, rows, J]

    loss, d_ki, outs = jnp.zeros((), f32), jnp.zeros(ki.shape, f32), []
    for band in range(bands):
        blocks = slice(band * n // bands, (band + 1) * n // bands)
        end = (band + 1) * s // bands  # the band's last query sees no key from here on
        body = functools.partial(block, s=end, k=k[:, :end], ki=ki[:, :end])
        band_xs = {name: a[blocks] for name, a in xs.items()}
        if not with_grads:
            (loss,), _ = jax.lax.scan(body, (loss,), band_xs)
            continue
        (loss, d_band), out = jax.lax.scan(body, (loss, d_ki[:, :end]), band_xs)
        d_ki = d_ki.at[:, :end].set(d_band)
        outs.append(out)
    if not with_grads:
        return loss, None, None, None
    d_qi, d_w = (jnp.concatenate([out[i] for out in outs]) for i in range(2))
    d_qi = jnp.moveaxis(d_qi, 0, 2).reshape(qi.shape)
    d_w = jnp.moveaxis(d_w, 0, 1).reshape(w.shape)
    return loss, d_qi, d_ki.astype(ki.dtype), d_w.astype(w.dtype)


def index_lse(qi, ki, w, segs, mask=None, *, interpret=None):
    """``[B, S]`` float32: each query's log-sum-exp of ``I`` over the keys it
    keeps (``mask`` int8 [B, S, S], or every visible key), a block of queries
    at a time; ``-inf`` for a query that keeps none. Where a selection was
    made, the ``sparse_select`` kernel gives the same from the block it holds."""
    b, _, s, _ = qi.shape
    q = _query_block(s)

    def one(q_start):
        scores = index_scores(qi, ki, w, segs, q_start, q, interpret=interpret)
        if mask is not None:
            scores = jnp.where(jax.lax.dynamic_slice_in_dim(mask, q_start, q, axis=1) != 0, scores, _NEG_INF)
        return jax.nn.logsumexp(scores, axis=-1)

    return jnp.moveaxis(_each_block(one, s), 0, 1).reshape(b, s)  # from [n, B, Q]


def _column(tile, lane, j):
    """Column ``j`` (traced) of ``tile`` [rows, n] as [rows, 1]: a lane cannot
    be sliced at a traced offset, so it is selected and summed."""
    return jnp.sum(jnp.where(lane == j, tile, 0.0), axis=1, keepdims=True)


def _each(n, body):
    """``body(j)`` for j in range(n), four of them a step of the loop where
    four divide n (two, one): a step of one head leaves the MXU waiting on
    the step's ends (at 512 x 1,024 the kernel took 73.0 ms a call with one,
    68.2 with two, 66.2 with four, 66.0 with eight: PERF.md section 6, PR 33)."""
    unroll = next(u for u in (4, 2, 1) if n % u == 0)

    def step(p, carry):
        for u in range(unroll):
            body(p * unroll + u)
        return carry

    jax.lax.fori_loop(0, n // unroll, step, 0)


def _loss_kernel(*refs, scale, block_q, block_k, heads, group, index_heads, segmented, masked):
    """One tile of ``index_loss``: a block of queries (outer, in order)
    against a tile of keys at or under its diagonal. The heads' scores, the
    index heads' products and everything between them and the three
    gradients stay in VMEM; ``d ki`` for the whole row does too, in its
    output's block, float32 and transposed (``[key tiles, Dj, block_k]``: the
    products that make it then fill the lanes and transpose ``qi``'s tile,
    not ``d z``), and leaves once a batch row."""
    (q_ref, k_ref, qi_ref, ki_ref, rows_ref), qseg_ref, kseg_ref, sel_ref, _hi_ref, rest = _optional_refs(
        refs, 5, segmented, masked
    )
    dqi_ref, dki_ref, dw_ref, kl_ref, dqi_acc, kl_acc = rest
    i, r = pl.program_id(1), pl.program_id(2)
    q_start, k_start = i * block_q, r * block_k
    f32 = jnp.float32

    @pl.when((i == 0) & (r == 0))
    def _init_row():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(r == 0)
    def _init_block():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        kl_acc[...] = jnp.zeros_like(kl_acc)

    def compute(t_ref, i_ref, z_ref):  # [block_q, block_k] float32: the target then d scores, I, and z of every index head
        if masked:
            keep = _selected(sel_ref)
        else:
            keep = _tile_mask(q_start, k_start, block_q, block_k)
            if segmented:
                keep = keep & (qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :])
        rows = rows_ref[0]  # a query a row: the heads' lse, w, the lse of I, the query's weight
        lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        lse_i, weight = (rows[:, heads + index_heads + n:heads + index_heads + n + 1] for n in range(2))
        t_ref[...] = jnp.zeros_like(t_ref)

        def head(h):  # exp(score - the row's log-sum-exp): a head's probabilities with no softmax
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h // group], (((1,), (1,)), ((), ())), preferred_element_type=f32
            ) * scale
            t_ref[...] += jnp.exp(s - _column(rows, lane, h))

        _each(heads, head)
        ki = ki_ref[0]
        i_ref[...] = jnp.zeros_like(i_ref)

        def index_head(j):
            z = jax.lax.dot_general(qi_ref[0, j], ki, (((1,), (1,)), ((), ())), preferred_element_type=f32)
            z_ref[j] = z
            i_ref[...] += _column(rows, lane, heads + j) * jnp.maximum(z, 0.0)

        _each(index_heads, index_head)
        target = jnp.where(keep, t_ref[...], 0.0) / heads
        logq = i_ref[...] - lse_i
        kl = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-37)) - logq), 0.0)
        kl_acc[...] += kl.sum(axis=1, keepdims=True) * weight
        t_ref[...] = jnp.where(keep, jnp.exp(logq) - target, 0.0) * weight  # d scores
        w_lane = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 1)  # its own: Mosaic does not slice ``lane``

        def back_head(j):
            z, d = z_ref[j], t_ref[...]
            dw_ref[0] += jnp.where(w_lane == j, (d * jnp.maximum(z, 0.0)).sum(axis=1, keepdims=True), 0.0)
            d_z = (jnp.where(z > 0, d, 0.0) * _column(rows, lane, heads + j)).astype(ki.dtype)
            dqi_acc[j] += jax.lax.dot_general(d_z, ki, (((1,), (0,)), ((), ())), preferred_element_type=f32)
            dki_ref[0, r] += jax.lax.dot_general(qi_ref[0, j], d_z, (((0,), (0,)), ((), ())), preferred_element_type=f32)

        _each(index_heads, back_head)

    @pl.when(k_start <= q_start + block_q - 1)  # the tile holds a pair at or under the diagonal
    def _compute():
        # a tile's own values are the kernel's to place (under ``vmem_limit_bytes``), not scratch operands: XLA
        # gives those 16 MiB when it fuses the call with what takes its results (a scan's stacked residuals)
        tile = pltpu.VMEM((block_q, block_k), f32)
        pl.run_scoped(compute, tile, tile, pltpu.VMEM((index_heads, block_q, block_k), f32))

    @pl.when(r == pl.num_programs(2) - 1)
    def _finalize_block():
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        kl_ref[0, 0] = kl_acc[...].sum(axis=0, keepdims=True)


def _loss_vmem_bytes(s, h, kh, d, heads, dj, block_q, block_k, itemsize, masked):
    """What ``index_loss`` may use of VMEM (Mosaic's default is 16 MiB), from
    the sizes the call sees: 91 MiB at the Keye cell's (S 32,768, 32 heads
    over 4 of 128, 16 x 64 index heads, tiles of 512 x 1,024; 55 at 256 x
    1,024), of a v5e's 128."""
    pad = lambda n: max(n, _LANES)
    return (
        (heads + 2 + 6) * block_q * block_k * 4  # z of every index head, the target and I, the values between them
        + 2 * (h * block_q + kh * block_k) * pad(d) * itemsize  # q and k: two buffers each
        + heads * block_q * pad(dj) * (4 * itemsize + 4)  # qi and d qi in two buffers each, and d qi's accumulator
        + 2 * block_k * pad(dj) * itemsize  # ki
        + 2 * s * dj * 4  # d ki for the row, float32, as the pipeline holds an output's block
        + 5 * block_q * _LANES * 4  # a query's numbers and d w in two buffers each, the KL's column, padded to the lanes
        + (block_q * block_k * (2 + 4) if masked else 0)  # a selection's tile: int8 in two buffers, and its int32 form
    )


def _loss_kernel_pass(q, k, lse, qi, ki, w, lse_i, mask, segs, weight, *, block_q=None, block_k=None, interpret=None):
    """``(loss, d qi, d ki, d w)`` by the ``index_loss`` kernel: ``_loss_pass``'s
    arguments, the heads' log-sum-exp over the kept pairs ``lse`` [B, H, S]
    (what the flash kernels keep) and ``I``'s ``lse_i`` [B, S]
    (``index_lse``). Grid (batch, q blocks, key tiles); a tile above the
    diagonal names the block already in VMEM and computes nothing."""
    b, s, h, d = q.shape
    kh, (heads, dj) = k.shape[2], qi.shape[1::2]
    block_q, block_k = block_q or _divisor(s, LOSS_TILE[0]), block_k or _divisor(s, LOSS_TILE[1])
    n_k = s // block_k
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    segmented, masked = segs is not None, mask is not None
    vmem = pltpu.VMEM

    def k_tile(i, r):  # above the diagonal: the tile already in VMEM
        return jnp.minimum(r, ((i + 1) * block_q - 1) // block_k)

    def by_row(width):
        return pl.BlockSpec((1, block_q, width), lambda n, i, r: (n, i, 0), memory_space=vmem)

    def by_head(n_heads, width):
        return pl.BlockSpec((1, n_heads, block_q, width), lambda n, i, r: (n, 0, i, 0), memory_space=vmem)

    in_specs = [
        by_head(h, d),
        pl.BlockSpec((1, kh, block_k, d), lambda n, i, r: (n, 0, k_tile(i, r), 0), memory_space=vmem),
        by_head(heads, dj),
        pl.BlockSpec((1, block_k, dj), lambda n, i, r: (n, k_tile(i, r), 0), memory_space=vmem),
        by_row(h + heads + 2),
    ]
    # a query's own numbers side by side, a lane each: as [B, S, 1] columns a TPU layout pads them 128 times
    per_query = jnp.concatenate([lse.transpose(0, 2, 1), w.astype(jnp.float32), lse_i[..., None], weight[..., None]], -1)
    operands = [q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), qi, ki, per_query]
    # every operand and result in HBM, a block at a time in VMEM: left to choose, XLA lays the small ones out in
    # VMEM whole when it fuses the call with what takes its results (a scan's stacked residuals), past its own 16 MiB
    if not interpret:
        operands = [pltpu.with_memory_space_constraint(a, pltpu.HBM) for a in operands]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda n, i, r: (n, 0, i), memory_space=vmem),
            pl.BlockSpec((1, 1, block_k), lambda n, i, r: (n, 0, k_tile(i, r)), memory_space=vmem),
        ]
        operands += [segs, segs]
    if masked:
        in_specs.append(pl.BlockSpec((1, block_q, block_k), lambda n, i, r: (n, i, k_tile(i, r)), memory_space=vmem))
        operands.append(mask)
    d_qi, d_ki, d_w, kl = pl.pallas_call(
        functools.partial(
            _loss_kernel, scale=1.0 / d**0.5, block_q=block_q, block_k=block_k, heads=h, group=h // kh,
            index_heads=heads, segmented=segmented, masked=masked,
        ),
        grid=(b, s // block_q, n_k),
        in_specs=in_specs,
        out_specs=[
            by_head(heads, dj),
            pl.BlockSpec((1, n_k, dj, block_k), lambda n, i, r: (n, 0, 0, 0), memory_space=vmem),
            by_row(heads),
            pl.BlockSpec((1, 1, 1, 1), lambda n, i, r: (n, i, 0, 0), memory_space=vmem),
        ],
        out_shape=[
            pltpu.HBM(qi.shape, qi.dtype),
            pltpu.HBM((b, n_k, dj, block_k), jnp.float32),  # summed in place over the row's q blocks
            pltpu.HBM(w.shape, jnp.float32),  # and over a q block's key tiles
            pltpu.HBM((b, s // block_q, 1, 1), jnp.float32),  # a block of queries' share of the loss
        ],
        scratch_shapes=[vmem((heads, block_q, dj), jnp.float32), vmem((block_q, 1), jnp.float32)],
        # d ki lives across both inner axes, so neither may be split or reordered
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(100 * 2**20, 16 * 2**20 + _loss_vmem_bytes(
                s, h, kh, d, heads, dj, block_q, block_k, q.dtype.itemsize, masked
            )),
        ),
        name="index_loss",
        interpret=interpret,
    )(*operands)
    return kl.sum(), d_qi, d_ki.swapaxes(2, 3).reshape(ki.shape).astype(ki.dtype), d_w.astype(w.dtype)


def _loss(qi, ki, w, q, k, lse, lse_i, mask, segs, weight, with_grads):
    """``(loss, d qi, d ki, d w)`` by the form the call's ``lse`` chooses; the
    kernel makes the gradients in its one pass whether asked or not."""
    if lse is None:
        return _loss_pass(q, k, qi, ki, w, mask, segs, weight, with_grads)
    return _loss_kernel_pass(q, k, lse, qi, ki, w, lse_i, mask, segs, weight)


@jax.custom_vjp
def _index_loss(qi, ki, w, q, k, lse, lse_i, mask, segs, weight):
    return _loss(qi, ki, w, q, k, lse, lse_i, mask, segs, weight, False)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse, lse_i, mask, segs, weight):
    loss, *grads = _loss(qi, ki, w, q, k, lse, lse_i, mask, segs, weight, True)
    return loss, tuple(checkpoint_name(g, SPARSE_RESIDUALS[1]) for g in grads)


def _index_loss_bwd(grads, g):
    return (*(g.astype(a.dtype) * a for a in grads), *(None,) * 7)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, ki, w, q, k, lse, mask, segs, real, lse_i=None):
    """The indexer's loss (module docstring) over the queries ``real`` marks
    ([B, S] bool; a mean over them), differentiable in ``qi``, ``ki`` and
    ``w`` only: the target and the selection are constants to it. ``mask``
    None: every visible key is selected. ``lse`` [B, H, S], the heads'
    log-sum-exp over the kept pairs where the attention's kernels gave it:
    the ``index_loss`` kernel, which also wants ``I``'s (``lse_i`` [B, S]:
    ``select``'s where a selection was made, else made here); None: the
    blockwise form with its own softmax (``_loss_pass``)."""
    weight = real.astype(jnp.float32) / jnp.maximum(real.sum(), 1).astype(jnp.float32)
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    if lse is not None:
        lse = jax.lax.stop_gradient(lse)
        lse_i = index_lse(*(jax.lax.stop_gradient(a) for a in (qi, ki, w)), segs, mask) if lse_i is None else lse_i
    return _index_loss(qi, ki, w, q, k, lse, lse_i, mask, segs, weight)
