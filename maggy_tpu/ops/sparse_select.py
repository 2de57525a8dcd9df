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
integer form, 32 counting passes over a block held in VMEM, and the position
of the last tie it admits), and the selection leaves as what the flash kernels
take (``ops/flash.py`` ``selected``): an int8 ``[B, S, S]`` mask, one for all
the heads of a layer. The two numbers a query are the residual a recomputed
layer keeps (``SPARSE_RESIDUALS``): the replay rebuilds the mask from them
with one more pass of ``index_scores`` and does not select again.

``index_loss`` is the indexer's objective, ``mean over real t of KL(mean over
heads of the attention's probabilities on S_t || softmax over S_t of I[t, .])``,
with its gradient to ``qI``, ``kI`` and ``w`` from the same pass: blockwise
``jax.numpy`` (a block of queries against the keys up to the end of its band,
a quarter of the row: the heads' scores once more, the index scores and their
transposed products, over 5/8 of the square), not a kernel yet. The
gradients are named residuals too, so the replay of a recomputed layer drops
the pass.
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
from maggy_tpu.ops.flash import _pick_divisor as _divisor

# what a recompute policy keeps of a selected-key attention layer beside the
# flash kernel's two results: each query's threshold and last admitted tie
# ([B, 2, S] int32), and the indexer's three gradients from ``index_loss``
SPARSE_RESIDUALS = ("sparse_threshold", "sparse_index_grads")

_INT_MIN = np.iinfo(np.int32).min
_NEG_INF = float("-inf")


def order_key(x):
    """float32 -> int32 whose signed order is the floats' (no NaN; the zeros
    are one value here because ``index_scores`` writes +0.0 only)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


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
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, o_ref.shape[2]), 1)
    o_ref[0] = jnp.where(lane == 0, t, p)


def topk_thresholds(scores, q_start, k: int, *, interpret=None):
    """``[B, 2, rows]`` int32 from ``scores`` [B, rows, S] (``index_scores``'
    for the queries from ``q_start``): each row's ``k``-th largest score as
    its ``order_key`` and the column of the last tie at it that the top ``k``
    still hold (ties go to the earlier key, as ``jax.lax.top_k`` breaks them).
    A row that sees fewer than ``k`` keys gets a threshold under every score."""
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
    return out[:, :, :2].swapaxes(1, 2)


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


def select_thresholds(qi, ki, w, segs, k: int, *, interpret=None):
    """Each query's threshold, ``[B, 2, S]`` int32 (``topk_thresholds``), a
    block of queries at a time."""
    b, _, s, _ = qi.shape
    q = _query_block(s)

    def one(q_start):
        with jax.named_scope("sparse.index"):
            scores = index_scores(qi, ki, w, segs, q_start, q, interpret=interpret)
        with jax.named_scope("sparse.select"):
            return topk_thresholds(scores, q_start, k, interpret=interpret)

    out = jax.lax.map(one, jnp.arange(s // q, dtype=jnp.int32) * q)  # [n, B, 2, Q]
    return jnp.moveaxis(out, 0, 2).reshape(b, 2, s)


def selection_mask(qi, ki, w, segs, thresholds, k: int, *, interpret=None):
    """``(mask int8 [B, S, S], counts int32 [3])`` from the thresholds, with
    the index scores made again: the pairs selected, the pairs visible (at or
    before the query, in its document) and the queries whose set is not
    ``min(k, visible)`` keys."""
    b, _, s, _ = qi.shape
    q = _query_block(s)

    def one(q_start):
        with jax.named_scope("sparse.index"):
            scores = index_scores(qi, ki, w, segs, q_start, q, interpret=interpret)
            keep = selection_from(scores, jax.lax.dynamic_slice_in_dim(thresholds, q_start, q, axis=2))
            n_keep = keep.sum(-1, dtype=jnp.int32)
            n_visible = (scores > _NEG_INF).sum(-1, dtype=jnp.int32)
            counts = jnp.stack([
                n_keep.sum(), n_visible.sum(), (n_keep != jnp.minimum(n_visible, k)).sum(dtype=jnp.int32),
            ])
            return keep.astype(jnp.int8), counts

    mask, counts = jax.lax.map(one, jnp.arange(s // q, dtype=jnp.int32) * q)  # [n, B, Q, S]
    return jnp.moveaxis(mask, 0, 1).reshape(b, s, s), counts.sum(0)


def select(qi, ki, w, segs, k: int, *, interpret=None):
    """``(mask, counts)`` of ``selection_mask`` for the top ``k`` keys a query:
    the thresholds first, named so that a recompute policy keeps them
    (``SPARSE_RESIDUALS``), then the mask from them. Nothing here has a
    gradient: the indexer learns from ``index_loss`` alone."""
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    thresholds = checkpoint_name(select_thresholds(qi, ki, w, segs, k, interpret=interpret), SPARSE_RESIDUALS[0])
    return selection_mask(qi, ki, w, segs, thresholds, k, interpret=interpret)


# ------------------------------------------------------------- the indexer's loss

LOSS_BLOCK = 256  # queries the loss takes at a time: [B, heads of a group, 256, S] float32 of scores
LOSS_BANDS = 4  # runs of query blocks, each against the keys up to its own end: 5/8 of the square, not all of it


def _loss_pass(q, k, lse, qi, ki, w, mask, segs, weight, with_grads: bool):
    """``(loss, d qi, d ki, d w)`` (the gradients None unless asked for): a
    block of queries at a time against the keys up to its band's end
    (``LOSS_BANDS``; a selection is causal). ``q`` [B, S, H, D], ``k``
    [B, S, Kh, D] (no gradient goes there), ``qi`` [B, J, S, Dj], ``ki``
    [B, S, Dj], ``w`` [B, S, J] float32, ``mask`` int8 [B, S, S] or None
    (every visible key), ``weight`` [B, S] float32 (a real query's share of
    the mean, zero on padding). ``lse`` [B, H, S]: the heads' log-sum-exp
    over the kept pairs where the attention's kernels gave it, so that a
    head's probabilities are one pass over its scores; None: a softmax here,
    three."""
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
    if lse is not None:
        xs["lse"] = split(lse.reshape(b, kh, h // kh, s), 3)  # [n, B, Kh, G, rows]

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
            if lse is None:
                p = jax.nn.softmax(jnp.where(vis[:, None], sc, NEG_INF), axis=-1)
            else:
                p = jnp.exp(sc - x["lse"][:, g, :, :, None])
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


@jax.custom_vjp
def _index_loss(qi, ki, w, q, k, lse, mask, segs, weight):
    return _loss_pass(q, k, lse, qi, ki, w, mask, segs, weight, False)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse, mask, segs, weight):
    loss, *grads = _loss_pass(q, k, lse, qi, ki, w, mask, segs, weight, True)
    return loss, tuple(checkpoint_name(g, SPARSE_RESIDUALS[1]) for g in grads)


def _index_loss_bwd(grads, g):
    return (*(g.astype(a.dtype) * a for a in grads), *(None,) * 6)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, ki, w, q, k, lse, mask, segs, real):
    """The indexer's loss (module docstring) over the queries ``real`` marks
    ([B, S] bool; a mean over them), differentiable in ``qi``, ``ki`` and
    ``w`` only: the target and the selection are constants to it. ``mask``
    None: every visible key is selected. ``lse`` [B, H, S] or None
    (``_loss_pass``)."""
    weight = real.astype(jnp.float32) / jnp.maximum(real.sum(), 1).astype(jnp.float32)
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    lse = None if lse is None else jax.lax.stop_gradient(lse)
    return _index_loss(qi, ki, w, q, k, lse, mask, segs, weight)
