"""Pallas TPU flash attention — forward AND backward (training-grade).

Same online-softmax math as :mod:`maggy_tpu.ops.attention`, hand-tiled for the
MXU. The forward runs grid (batch*heads, q_blocks, k_blocks) with fp32 running
statistics in VMEM scratch; the [S, S] score matrix never leaves VMEM tiles.
The backward (FlashAttention-2's recurrence) recomputes the probabilities
from the saved per-row log-sum-exp instead of storing them, and visits a
score tile once: one kernel, ``flash_bwd``, on the grid (batch*heads, k
blocks, q blocks) computes p and dS of a tile and adds ``p^T dO`` to dV and
``dS^T q`` to dK, accumulated across the inner axis, and ``dS k`` to dQ,
which for the whole row of the head stays in float32 in VMEM across both
axes and leaves once a head in ``q.dtype`` (``_bwd_kernel``; five products
and one pass of vector work a tile). A row too long for that
(``backward_form``: by ``sq`` and the head's width alone, past S 32,768 at
width 128) takes the two-kernel split whose VMEM does not grow with S: a dQ
kernel accumulating over KV blocks and a dK/dV kernel accumulating over Q
blocks, each recomputing p and dS (seven products, two passes); at equal
tiles both forms give the same bits. ``delta = rowsum(dO * O)`` is
recomputed per tile from the O/dO blocks so the only extra residual is the
[BH, S] LSE (the kernels read and write it as a column, ``[BH, n_q, block_q,
1]``, so none needs a sublane<->lane relayout inside; between the passes it
is kept as rows of 128, see ``FLASH_RESIDUALS``).

**The tile-visit table.** The grid is static (shapes decide it); which of its
tiles hold an unmasked pair is data. Before each kernel
``needed_tiles`` marks, for every batch row and outer block, the reduction
blocks that are not wholly above the causal diagonal and whose range of
segment ids overlaps the outer block's, and ``visit_bounds`` keeps the first
and the last of them: a few hundred int32s that reach the kernel by scalar
prefetch. The body computes only inside first..last (a wholly masked tile
leaves the accumulators as they were, so results are bit for bit those of
visiting every tile), and the index maps of the reduction-side operands name
the nearer end outside it, which is the block already in VMEM, so a step that
computes nothing copies nothing either. One compiled step serves every
packing. Calls with no segment ids get the same table from the diagonal
alone (one row of ``n_outer`` bounds). A sliding window (``window``, static)
is a second bound of the same table: the tiles it masks wholly are dropped
from first..last at either end, whichever axis is outermost. A causal bound a
query (``bound``, data: a block-causal or a strict cross-stream mask) stands in
the diagonal's place, in the tiles' masks and in the table alike.

Off-TPU the kernels run under the Pallas interpreter so tests run on CPU
meshes, and shapes that do not tile evenly fall back to
``blockwise_attention`` (differentiable). Compiled (on a TPU, or with
``interpret=False``) there is no fallback: a shape Mosaic cannot tile raises,
naming the dimension.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from maggy_tpu.ops.attention import NEG_INF, _repeat_kv, blockwise_attention

_LANES = 128


def lane_fill(head_dim: int) -> Optional[str]:
    """How heads of this width fill the kernels' 128 lanes; None: a width the
    kernels do not take. A multiple of 128 fills them ("full"). Width 64 runs
    "half": each tile of q, k, v, o and the
    accumulators is ``[rows, 64]`` in half-filled vector registers, the two
    products of a tile contract over or produce 64 of the MXU's 128 columns.
    Two heads to a tile would fill the registers and HBM tiles of q and o, but
    not the MXU (the heads' scores are separate products whichever way they
    are laid, and a pair's ``[rows, 128]`` operand against a 64-wide key block
    is the same half-filled pass), and the kernels' time is in the
    ``[block_q, block_k]`` score tile, whose exponentials and masks do not
    depend on the width: PERF.md section 6, PR 30. The fused backward passes
    over that tile once for all three gradients (PR 31); its resident dq is
    counted at 128 lanes a row at this width."""
    if head_dim % _LANES == 0:
        return "full"
    return "half" if head_dim == _LANES // 2 else None

# the forward kernel's two results as the backward rule keeps them, named
# (``checkpoint_name``) so that a recompute policy can keep them too, as all of
# ``models.transformer.REMAT_POLICIES`` do: a layer under ``nn.remat`` then
# runs ``flash_fwd`` once a step and not again inside its replayed forward
FLASH_RESIDUALS = ("flash_o", "flash_lse")

# the grid is (batch*heads, outer blocks, reduction blocks): in the forward and
# the split backward kernels only the last axis carries the VMEM accumulators
# from one step to the next (the fused backward's dq lives across both)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)
# with a selection's tile beside the others (int8, two buffers, and its int32
# form beside the scores) 1,024 x 1,024 tiles pass Mosaic's default 16 MiB
_MASKED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 2**20,
)


def _tile_mask(q_start, k_start, block_q, block_k, window=0, hi=None):
    """The causal pairs of a tile; with a ``window`` those of them whose key
    lies fewer than ``window`` positions before the query (its own counts).
    ``hi`` (int32 ``[block_q]``, a bound a query: ``flash_attention``'s
    ``bound``) stands in the place of the query's own row index: the pairs
    whose key's index is at most its query's bound."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if hi is not None:
        return hi[:, None] - (k_start + cols) >= 0
    ahead = (q_start + rows) - (k_start + cols)
    if window:
        return (ahead >= 0) & (ahead < window)
    return ahead >= 0


# ------------------------------------------------------------- tile-visit table


def needed_tiles(segs, *, causal, sq, sk, block_q, block_k, selected=None, window=0, bound=None):
    """bool [rows, sq // block_q, sk // block_k]: the tiles that can hold an
    unmasked pair. A tile is needed when it is not wholly above the causal
    diagonal and the two blocks' ranges of segment ids (minimum and maximum
    over the block) overlap. With ids that never decrease along a row that is
    exactly the tiles with an unmasked pair; with any other ids it is a
    superset, so no pair is lost. Padding (id 0) closes a packed row, so it
    is ordered last. ``segs`` is [B, 1, S] (numpy on the host, or traced) or
    None, which gives one row that every batch row shares. ``selected``
    ([B, sq, sk], traced: the pairs a selection keeps, nonzero) drops the
    tiles that hold no selected pair, a row of the table a batch row. A
    ``window`` (static, causal calls only; 0: none) is a second bound on the
    same table: a tile whose last key lies ``window`` positions or more before
    its first query is wholly outside it and not needed, so a query block's
    first needed key block is the later of its documents' first and the block
    of ``q_start - window + 1``, and in the key-major visit a key block's last
    needed query block the earlier of its documents' last and the block of
    ``k_last + window - 1``. ``bound`` ([B, 1, sq] int32, numpy or traced,
    causal calls only: the last key index each query may see) takes the
    diagonal's place: a tile is needed when its first key lies at or before
    the largest bound of its query block, a row of the table a batch row. With
    bounds that never decrease along a row the needed tiles of a query block
    and of a key block are each one run, as the diagonal's are; with any
    others first..last is a superset, and the kernels mask inside a tile."""
    nq, nk = sq // block_q, sk // block_k
    need = np.ones((1, nq, nk), bool)
    if causal and bound is not None:
        need = np.arange(nk)[None, None, :] * block_k <= bound.reshape(-1, nq, block_q).max(-1)[:, :, None]
    elif causal:
        q_last = np.arange(nq)[:, None] * block_q + block_q - 1
        need = (np.arange(nk)[None, :] * block_k <= q_last)[None]
    if window:
        q_first = np.arange(nq)[:, None] * block_q
        k_last = np.arange(nk)[None, :] * block_k + block_k - 1
        need = need & (q_first - k_last < window)[None]
    if selected is not None:
        need = need & (selected.reshape(-1, nq, block_q, nk, block_k) != 0).any(axis=(2, 4))
    if segs is None:
        return need
    xp = np if isinstance(segs, np.ndarray) else jnp
    ids = xp.where(segs == 0, np.iinfo(np.int32).max, segs)
    q_ids = ids.reshape(-1, nq, 1, block_q)
    k_ids = ids.reshape(-1, 1, nk, block_k)
    return (
        need
        & (q_ids.min(-1) <= k_ids.max(-1))
        & (k_ids.min(-1) <= q_ids.max(-1))
    )


def visit_bounds(segs, outer, **tiles):
    """int32 [rows * outer blocks * 2], flat for SMEM: the first and the last
    needed reduction block of every (row, outer block), q blocks outermost
    (``outer="q"``: forward, dq) or k blocks (``"k"``: the fused backward,
    dkv); ``tiles`` as ``needed_tiles`` takes them, a ``window`` among them:
    the tiles it leaves needed are a run of blocks in either order, so first
    and last still say all. The kernels visit first..last and nothing else; a
    row with no needed block reads (0, -1)."""
    need = needed_tiles(segs, **tiles)
    if outer == "k":
        need = need.swapaxes(1, 2)
    xp = np if isinstance(need, np.ndarray) else jnp
    first = xp.argmax(need, axis=-1)
    last = need.shape[-1] - 1 - xp.argmax(need[..., ::-1], axis=-1)
    last = xp.where(need.any(axis=-1), last, -1)
    return xp.stack([first, last], axis=-1).astype(xp.int32).reshape(-1)


def _bounds_at(bounds_ref, row, outer, n_outer):
    at = (row * n_outer + outer) * 2
    return bounds_ref[at], bounds_ref[at + 1]


def _visits(bounds_ref, heads, red):
    """In a kernel: whether reduction step ``red`` of this grid row lies in
    its first-to-last needed block. A tile outside is wholly masked (above
    the diagonal, or between two documents), so skipping it leaves every
    accumulator as it was. ``heads`` grid rows share a table row; 0 means the
    table has one row (no segment ids and no selection)."""
    row = pl.program_id(0) // heads if heads else 0
    first, last = _bounds_at(bounds_ref, row, pl.program_id(1), pl.num_programs(1))
    return (first <= red) & (red <= last)


def _resident(bounds_ref, row, outer, n_outer, red):
    """In an index map: the reduction block to name at grid step ``red``:
    itself inside the row's first-to-last needed block, else the nearer end,
    which is the block already in VMEM, so a step that computes nothing
    copies nothing."""
    first, last = _bounds_at(bounds_ref, row, outer, n_outer)
    return jnp.clip(red, first, jnp.maximum(last, first))


def tiles_visited_share(segment_ids, *, causal=True, block_q=None, block_k=None, head_dim=128, window=0):
    """Of the tiles in the forward kernel's grid for a packed host batch
    (``segment_ids`` [B, S], numpy), the share the kernel visits, at the
    tile sizes chosen automatically for heads of ``head_dim`` unless others
    are given, under a ``window`` where the layer has one. None where the
    tiles do not divide S."""
    seg = np.asarray(segment_ids)
    s = seg.shape[-1]
    auto = _auto_blocks(s, s, True, head_dim)
    block_q, block_k = block_q or auto[0], block_k or auto[1]
    if s % block_q or s % block_k:
        return None
    first, last = visit_bounds(
        seg.reshape(-1, 1, s), "q", causal=causal, sq=s, sk=s,
        block_q=block_q, block_k=block_k, window=window,
    ).reshape(-1, 2).T
    return float((last - first + 1).sum()) / (len(first) * (s // block_k))


# --------------------------------------------------------------------- forward


def _optional_refs(refs, n, segmented, masked, bounded=False):
    """A kernel's references apart: its ``n`` fixed operands, then the two
    segment-id blocks, the queries' bounds and the selection's tile, each
    where the call has one, then results and scratch."""
    ins, rest = refs[:n], list(refs[n:])
    qseg_ref, kseg_ref = (rest.pop(0), rest.pop(0)) if segmented else (None, None)
    hi_ref = rest.pop(0) if bounded else None
    sel_ref = rest.pop(0) if masked else None
    return ins, qseg_ref, kseg_ref, sel_ref, hi_ref, rest


def _selected(sel_ref):
    """The selection's tile as a mask: int8 in HBM and VMEM, compared as int32."""
    return sel_ref[0].astype(jnp.int32) != 0


def _fwd_kernel(
    bounds_ref, *refs,
    scale, causal, block_q, block_k, segmented, heads, masked=False, window=0, bounded=False,
):
    (q_ref, k_ref, v_ref), qseg_ref, kseg_ref, sel_ref, hi_ref, rest = _optional_refs(refs, 3, segmented, masked, bounded)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # a skipped tile leaves m, l and the accumulator as they were (corr = 1, p = 0)
    @pl.when(_visits(bounds_ref, heads if segmented or masked or bounded else 0, ki))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(q_start, k_start, block_q, block_k, window, hi_ref[0, 0] if bounded else None) if causal else None
        if segmented:
            smask = qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :]
            mask = smask if mask is None else (mask & smask)
        if masked:
            mask = _selected(sel_ref) if mask is None else (mask & _selected(sel_ref))
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_cur)
        l_new = l_ref[:, :1] * corr + p.sum(axis=1, keepdims=True)
        # stats stored replicated across lanes (full-width VMEM stores)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l_fin = l_ref[:, :1]
        denom = jnp.maximum(l_fin, 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # rows with no visible key get lse=+inf so the backward's
        # exp(s - lse) is exactly zero for them
        lse_ref[0, 0] = jnp.where(
            l_fin > 0, m_ref[:, :1] + jnp.log(denom), jnp.inf
        )


def _specs(block_q, block_k, d, group, heads, segmented, outer, n_outer, masked=False, bounded=False):
    """BlockSpecs of one kernel's grid (batch*heads, outer blocks, reduction
    blocks) with q rows (``outer="q"``: forward, dq) or k rows (``"k"``: the
    fused backward, dkv) outermost. Every index map also gets the visit
    bounds (scalar prefetch): the reduction side names ``_resident``'s block,
    the outer side its own.

    GQA lives in the index map: q-head row i reads KV row i // group, so the
    repeated [B,S,H,D] K/V never materialize in HBM (review finding r2);
    segment ids are per (batch, seq) — row i // heads — shared by all heads.
    They arrive as [B, 1, S]: Mosaic wants a block's last two dims to be
    (8, 128)-aligned or the array's full extent, which a (1, block) tile of
    [B, S] is only at B == 1. The queries' bounds ([B, 1, S] too) lie as the
    query side's segment ids do (``qseg``). A selection's tile (``sel``, int8
    ``[B, sq, sk]``, shared by a batch row's heads) lies at both blocks."""
    rows = segmented or masked or bounded  # the visit table has a row a batch row

    def at(which, place):
        def index_map(i, o, r, bounds_ref):
            if which == outer:
                return place(i, o)
            row = i // heads if rows else 0
            return place(i, _resident(bounds_ref, row, o, n_outer, r))
        return index_map

    def sel_map(i, o, r, bounds_ref):
        red = _resident(bounds_ref, i // heads, o, n_outer, r)
        return (i // heads, o, red) if outer == "q" else (i // heads, red, o)

    def spec(shape, which, place):
        return pl.BlockSpec(shape, at(which, place), memory_space=pltpu.VMEM)

    return dict(
        q=spec((1, block_q, d), "q", lambda i, b: (i, b, 0)),
        kv=spec((1, block_k, d), "k", lambda i, b: (i // group, b, 0)),
        # dk/dv leave per q head; the caller sums each GQA group
        dkv=spec((1, block_k, d), "k", lambda i, b: (i, b, 0)),
        lse=spec((1, 1, block_q, 1), "q", lambda i, b: (i, b, 0, 0)),
        qseg=spec((1, 1, block_q), "q", lambda i, b: (i // heads, 0, b)),
        kseg=spec((1, 1, block_k), "k", lambda i, b: (i // heads, 0, b)),
        sel=pl.BlockSpec((1, block_q, block_k), sel_map, memory_space=pltpu.VMEM),
    )


def _fwd_call(
    q, k, v, segs, bounds, sel=None, hi=None,
    *, causal, block_q, block_k, group, heads, interpret, window=0,
):
    bh, sq, d = q.shape
    sk = k.shape[1]
    segmented, masked, bounded = segs is not None, sel is not None, hi is not None
    sp = _specs(block_q, block_k, d, group, heads, segmented, "q", sq // block_q, masked, bounded)
    in_specs = [sp["q"], sp["kv"], sp["kv"]]
    operands = [q, k, v]
    if segmented:
        in_specs += [sp["qseg"], sp["kseg"]]
        operands += [segs, segs]
    if bounded:
        in_specs.append(sp["qseg"])
        operands.append(hi)
    if masked:
        in_specs.append(sp["sel"])
        operands.append(sel)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel,
            scale=1.0 / d**0.5,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            segmented=segmented,
            heads=heads,
            masked=masked,
            window=window,
            bounded=bounded,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, sq // block_q, sk // block_k),
            in_specs=in_specs,
            out_specs=[sp["q"], sp["lse"]],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq // block_q, block_q, 1), jnp.float32),
        ],
        compiler_params=_MASKED_COMPILER_PARAMS if masked else _COMPILER_PARAMS,
        name="flash_fwd",
        interpret=interpret,
    )(bounds, *operands)


# -------------------------------------------------------------------- backward


def _recompute_p_ds(
    q, k, v, o, do, lse, *, scale, causal, q_start, k_start, qseg=None, kseg=None, sel=None, window=0, hi=None
):
    """Shared tile math: probabilities from the saved LSE, then
    dS = P * (dP - delta) * scale with delta recomputed from the O/dO tiles.
    The full forward mask (causal, window AND segments) must be re-applied —
    exp(s - lse) is not zero for positions the forward masked out."""
    block_q, block_k = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    p = jnp.exp(s - lse)  # lse [block_q, 1]
    mask = _tile_mask(q_start, k_start, block_q, block_k, window, hi) if causal else None
    if qseg is not None:
        smask = qseg[:, None] == kseg[None, :]
        mask = smask if mask is None else (mask & smask)
    if sel is not None:
        mask = sel if mask is None else (mask & sel)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=1, keepdims=True
    )
    ds = p * (dp - delta) * scale
    return p, ds


def _dq_kernel(
    bounds_ref, *refs,
    scale, causal, block_q, block_k, segmented, heads, masked=False, window=0, bounded=False,
):
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref), qseg_ref, kseg_ref, sel_ref, hi_ref, rest = _optional_refs(
        refs, 6, segmented, masked, bounded
    )
    dq_ref, acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_visits(bounds_ref, heads if segmented or masked or bounded else 0, ki))
    def _compute():
        k = k_ref[0]
        _, ds = _recompute_p_ds(
            q_ref[0], k, v_ref[0], o_ref[0], do_ref[0], lse_ref[0, 0],
            scale=scale, causal=causal,
            q_start=qi * block_q, k_start=ki * block_k,
            qseg=qseg_ref[0, 0] if segmented else None,
            kseg=kseg_ref[0, 0] if segmented else None,
            sel=_selected(sel_ref) if masked else None, window=window,
            hi=hi_ref[0, 0] if bounded else None,
        )
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(
    bounds_ref, *refs,
    scale, causal, block_q, block_k, segmented, heads, masked=False, window=0, bounded=False,
):
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref), qseg_ref, kseg_ref, sel_ref, hi_ref, rest = _optional_refs(
        refs, 6, segmented, masked, bounded
    )
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    # a KV block receives gradient only from the Q blocks at or after the
    # diagonal that share a document with it: its first-to-last needed block
    @pl.when(_visits(bounds_ref, heads if segmented or masked or bounded else 0, qi))
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k_ref[0], v_ref[0], o_ref[0], do, lse_ref[0, 0],
            scale=scale, causal=causal,
            q_start=qi * block_q, k_start=ki * block_k,
            qseg=qseg_ref[0, 0] if segmented else None,
            kseg=kseg_ref[0, 0] if segmented else None,
            sel=_selected(sel_ref) if masked else None, window=window,
            hi=hi_ref[0, 0] if bounded else None,
        )
        # dV += P^T dO ; dK += dS^T Q — contract the q dim of both operands
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc_ref[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd_kernel(
    bounds_ref, *refs,
    scale, causal, block_q, block_k, segmented, heads, masked=False, window=0, bounded=False,
):
    """dq, dk and dv from one visit of a tile: the grid is ``_dkv_kernel``'s
    (k blocks outer, q blocks inner, dk and dv accumulated across the inner
    axis), and dq for the whole row of the head, ``[n_q, block_q, d]``
    float32, stays in VMEM across both axes: q block ``qi`` is zeroed at the
    first k block, gets ``ds k`` from every visited tile (k blocks in
    ascending order, as ``_dq_kernel`` adds them) and is cast into the
    head's output block at the last."""
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref), qseg_ref, kseg_ref, sel_ref, hi_ref, rest = _optional_refs(
        refs, 6, segmented, masked, bounded
    )
    dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref, dv_acc_ref = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(ki == 0)
    def _init_q():
        dq_acc_ref[qi] = jnp.zeros(dq_acc_ref.shape[1:], dq_acc_ref.dtype)

    @pl.when(_visits(bounds_ref, heads if segmented or masked or bounded else 0, qi))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k, v_ref[0], o_ref[0], do, lse_ref[0, 0],
            scale=scale, causal=causal,
            q_start=qi * block_q, k_start=ki * block_k,
            qseg=qseg_ref[0, 0] if segmented else None,
            kseg=kseg_ref[0, 0] if segmented else None,
            sel=_selected(sel_ref) if masked else None, window=window,
            hi=hi_ref[0, 0] if bounded else None,
        )
        ds = ds.astype(q.dtype)
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc_ref[qi] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finalize_q():
        dq_ref[0, qi] = dq_acc_ref[qi].astype(dq_ref.dtype)


# The fused backward keeps a head's whole dq in VMEM: a float32 accumulator of
# ``sq * max(d, 128)`` numbers (width 64 pads to the lanes) and the output
# block it is cast into, which the pipeline holds twice. Counted at its worst
# (float32 out: 12 bytes a number) that may take 48 MiB of a v5e's 128 MiB of
# VMEM, which leaves the tiles (41 MiB as ``_fused_vmem_bytes`` counts them at
# 1,024 x 1,024, width 256, float32) and the compiler their room: rows up to S 32,768 at width 128 and
# 16,384 at 256, so every shape the benchmark's cells, the serve prefill and
# the tests run. A longer row takes the two split kernels, whose VMEM does
# not grow with S.
_FUSED_DQ_VMEM_BYTES = 48 * 2**20


# the kernels each form launches, by the names the trace and the lowered
# program carry
BACKWARD_KERNELS = {"fused": ("flash_bwd",), "split": ("flash_dq", "flash_dkv")}


def _resident_dq_bytes(sq, d, itemsize=4):
    """VMEM a head's dq holds in ``flash_bwd``: the float32 accumulator and
    the output block's two buffers (``itemsize``: float32 unless given)."""
    return sq * max(d, _LANES) * (4 + 2 * itemsize)


def backward_form(sq: int, head_dim: int) -> str:
    """Which backward a call of this row length and head width runs:
    ``"fused"`` (``flash_bwd``: one kernel, a tile visited once) where the
    head's dq fits ``_FUSED_DQ_VMEM_BYTES``, else ``"split"`` (``flash_dq``
    and ``flash_dkv``). Nothing else decides it."""
    return "fused" if _resident_dq_bytes(sq, head_dim) <= _FUSED_DQ_VMEM_BYTES else "split"


def _fused_vmem_bytes(sq, d, block_q, block_k, itemsize, masked=False):
    """What ``flash_bwd`` may use of VMEM (Mosaic's default is 16 MiB), from
    the sizes the call sees: the resident dq and beside it a step's tiles. At the GLM cell's
    shape and tiles this counts 36 MiB; Mosaic took the kernel at 32 and not
    at 24 (ISSUE 31's sketch)."""
    dpad = max(d, _LANES)
    tiles = (
        6 * block_q * block_k * 4  # s, p, dp, ds and the masks and casts between them
        + 2 * (3 * block_q + 4 * block_k) * dpad * itemsize  # q, o, do; k, v, dk, dv: two buffers each
        + 2 * block_k * dpad * 4  # the dk and dv accumulators
        + 2 * block_q * _LANES * 4  # the LSE column, padded to the lanes
    )
    if masked:  # a selection's tile: int8 in two buffers, and its int32 form
        tiles += block_q * block_k * (2 + 4)
    return _resident_dq_bytes(sq, d, itemsize) + tiles


def _bwd_pallas(
    kernel, name, outer, grid, outs, scratch_shapes, compiler_params,
    q, k, v, o, do, lse, segs, bounds, sel=None, hi=None,
    *, causal, block_q, block_k, group, heads, interpret, window=0,
):
    """One backward kernel over ``grid`` with q rows (``outer="q"``) or k rows
    (``"k"``) outermost; ``outs`` pairs each result's BlockSpec (a name of
    ``_specs`` or a spec) with its shape."""
    d = q.shape[2]
    segmented, masked, bounded = segs is not None, sel is not None, hi is not None
    sp = _specs(block_q, block_k, d, group, heads, segmented, outer, grid[1], masked, bounded)
    in_specs = [sp["q"], sp["kv"], sp["kv"], sp["q"], sp["q"], sp["lse"]]
    operands = [q, k, v, o, do, lse]
    if segmented:
        in_specs += [sp["qseg"], sp["kseg"]]
        operands += [segs, segs]
    if bounded:
        in_specs.append(sp["qseg"])
        operands.append(hi)
    if masked:
        in_specs.append(sp["sel"])
        operands.append(sel)
    return pl.pallas_call(
        functools.partial(
            kernel, scale=1.0 / d**0.5, causal=causal, block_q=block_q,
            block_k=block_k, segmented=segmented, heads=heads, masked=masked, window=window,
            bounded=bounded,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[sp[spec] if isinstance(spec, str) else spec for spec, _ in outs],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=[shape for _, shape in outs],
        compiler_params=compiler_params,
        name=name,
        interpret=interpret,
    )(bounds, *operands)


def _bwd_split(q, k, v, o, do, lse, segs, bounds, sel=None, hi=None, *, block_q, block_k, **kw):
    """The two-kernel backward (FlashAttention-2's): ``flash_dq`` sums over k
    blocks, ``flash_dkv`` over q blocks, each recomputing p and ds on every
    tile it visits. Its VMEM does not grow with the row's length."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    kw = dict(kw, block_q=block_q, block_k=block_k)
    params = _COMPILER_PARAMS if sel is None else _MASKED_COMPILER_PARAMS
    (dq,) = _bwd_pallas(
        _dq_kernel, "flash_dq", "q", (bh, sq // block_q, sk // block_k),
        [("q", jax.ShapeDtypeStruct((bh, sq, d), q.dtype))],
        [pltpu.VMEM((block_q, d), jnp.float32)], params,
        q, k, v, o, do, lse, segs, bounds("q"), sel, hi, **kw,
    )
    dk, dv = _bwd_pallas(
        _dkv_kernel, "flash_dkv", "k", (bh, sk // block_k, sq // block_q),
        [
            ("dkv", jax.ShapeDtypeStruct((bh, sk, d), k.dtype)),
            ("dkv", jax.ShapeDtypeStruct((bh, sk, d), v.dtype)),
        ],
        [pltpu.VMEM((block_k, d), jnp.float32)] * 2, params,
        q, k, v, o, do, lse, segs, bounds("k"), sel, hi, **kw,
    )
    return dq, dk, dv


def _bwd_fused(q, k, v, o, do, lse, segs, bounds, sel=None, hi=None, *, block_q, block_k, **kw):
    """``flash_bwd``: the grid and visit table of ``flash_dkv``, dq besides
    (``_bwd_kernel``). dq leaves as ``[BH, n_q, block_q, D]`` in ``q.dtype``,
    one block a head, which is ``[BH, S, D]`` read another way: no buffer the
    split form does not have."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    n_q = sq // block_q
    dq, dk, dv = _bwd_pallas(
        _bwd_kernel, "flash_bwd", "k", (bh, sk // block_k, n_q),
        [
            (
                pl.BlockSpec(
                    (1, n_q, block_q, d), lambda i, o, r, bounds_ref: (i, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                jax.ShapeDtypeStruct((bh, n_q, block_q, d), q.dtype),
            ),
            ("dkv", jax.ShapeDtypeStruct((bh, sk, d), k.dtype)),
            ("dkv", jax.ShapeDtypeStruct((bh, sk, d), v.dtype)),
        ],
        [
            pltpu.VMEM((n_q, block_q, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        # dq lives across both inner axes, so neither may be split or reordered
        pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_fused_vmem_bytes(sq, d, block_q, block_k, q.dtype.itemsize, sel is not None),
        ),
        q, k, v, o, do, lse, segs, bounds("k"), sel, hi,
        block_q=block_q, block_k=block_k, **kw,
    )
    return dq.reshape(bh, sq, d), dk, dv


def _bwd_call(q, k, v, o, do, lse, segs, bounds, sel=None, hi=None, **kw):
    """dq and, per q head, dk and dv (the caller sums each GQA group: a KV
    block cannot accumulate across grid rows). ``bounds(outer)`` gives the
    visit table with q or k blocks outermost; the form chosen from the row's
    length and the head's width (``backward_form``) asks for the one or two
    it reads."""
    fused = backward_form(q.shape[1], q.shape[2]) == "fused"
    return (_bwd_fused if fused else _bwd_split)(q, k, v, o, do, lse, segs, bounds, sel, hi, **kw)


def sum_groups(dk_h, dv_h, group: int, k_dtype, v_dtype):
    """The kernels emit dk and dv per q head ``[B*H, S, D]``: each GQA group
    summed in float32, ``[B*Kh, S, D]`` in the keys' and values' types."""
    if group == 1:
        return dk_h, dv_h
    bh, sk, d = dk_h.shape

    def gsum(x, dtype):
        x = x.reshape(bh // group, group, sk, d).astype(jnp.float32)
        return x.sum(axis=1).astype(dtype)

    return gsum(dk_h, k_dtype), gsum(dv_h, v_dtype)


@functools.lru_cache(maxsize=None)
def _flash_core(
    causal: bool, block_q: int, block_k: int, bwd_block_q: int,
    bwd_block_k: int, group: int, heads: int, interpret: bool,
    segmented: bool, masked: bool = False, with_lse: bool = False, reselects: bool = False,
    window: int = 0, bounded: bool = False,
):
    """Differentiable flash attention on q [B*H, S, D], k/v [B*Kh, S, D]
    (GQA group = H // Kh handled by kernel index maps — the repeated K/V
    never exist, in HBM or as residuals). With ``segmented``, a fourth
    [B, 1, S] int32 operand masks attention across packed-sequence
    boundaries (zero cotangent), and with ``masked`` a fifth, int8
    [B, S, S], the pairs a selection keeps (nonzero; no cotangent either: a
    selection is no function of the scores it masks), and with ``reselects``
    a sixth, what gives that selection again when called
    (``flash_attention``'s ``reselect``): the backward calls it, and the
    mask is no residual. ``window`` (static): the causal mask keeps a key
    fewer than ``window`` positions before its query only, in every kernel
    and in the visit tables. With ``bounded`` an operand follows the segment
    ids, int32 [B, 1, S]: the last key index each query may see, in the
    diagonal's place in every kernel and visit table (no cotangent). ``with_lse``: the
    result is ``(o, lse)``, the rows' log-sum-exp as the backward keeps it
    (``[BH, S / 128, 128]`` float32, a constant to whoever reads it: its
    cotangent is dropped). Each kernel gets its visit bounds, computed
    here from what the call is given: they are data, so one compiled step
    serves every packing. The backward is ``_bwd_call``'s: one fused kernel
    reading the k-outer table, or for a row over the VMEM budget the split
    pair reading one table each; only the tables the form reads are built.
    Backward tiles are independent of the forward's — the backward holds 6+
    operands per tile, so its VMEM sweet spot can differ (tools/tune_flash.py
    sweeps both on the chip)."""

    kw = dict(causal=causal, block_q=block_q, block_k=block_k, group=group,
              heads=heads, interpret=interpret, window=window)
    bwd_kw = dict(kw, block_q=bwd_block_q, block_k=bwd_block_k)

    def bounds(q, k, segs, hi, sel, block_q, block_k, outer):
        tiles = dict(causal=causal, sq=q.shape[1], sk=k.shape[1], block_q=block_q, block_k=block_k, window=window)
        if sel:
            tiles["selected"] = sel[0]
        if hi is not None:
            tiles["bound"] = hi
        return jnp.asarray(visit_bounds(segs if segmented else None, outer, **tiles))

    def apart(more):
        """The queries' bounds (None without) and what follows them: the selection's operands."""
        return (more[0], more[1:]) if bounded else (None, more)

    def forward(q, k, v, segs, *more):
        hi, sel = apart(more)
        sel = sel[:1]  # the selection itself; what may follow it is the backward's (``reselect``)
        return _fwd_call(
            q, k, v, segs if segmented else None,
            bounds(q, k, segs, hi, sel, block_q, block_k, "q"), *sel, hi=hi, **kw,
        )

    def rows_of_lanes(lse, sq):
        lanes = _LANES if sq % _LANES == 0 else 1
        return lse.reshape(-1, sq // lanes, lanes)

    @jax.custom_vjp
    def core(q, k, v, segs, *sel):
        o, lse = forward(q, k, v, segs, *sel)
        return (o, rows_of_lanes(lse, q.shape[1])) if with_lse else o

    def core_fwd(q, k, v, segs, *sel):
        o, lse = forward(q, k, v, segs, *sel)
        # q, k, v stay unnamed: three times o's size, and a replay rebuilds
        # them from the layer's input without the kernel. The LSE as the
        # kernels have it, a column, is padded to 128 lanes by a TPU layout
        # (as many bytes as ``o`` for 1/128 of the numbers): kept as rows of
        # 128, which the chip reshapes faster than [BH, S] (PERF.md section 6,
        # PR 29; S that 128 does not divide is interpreter-only). Of a
        # selection that came with the means to make it again, those are kept
        # and the mask is not: a byte a pair of the row, which a recomputed
        # layer's replay would have to select again to have
        o = checkpoint_name(o, FLASH_RESIDUALS[0])
        lse = checkpoint_name(rows_of_lanes(lse, q.shape[1]), FLASH_RESIDUALS[1])
        hi, sel = apart(sel)
        return ((o, lse) if with_lse else o), (q, k, v, segs, o, lse, hi, *(sel[1:] if reselects else sel))

    def core_bwd(res, g):
        q, k, v, segs, o, lse, hi, *sel = res
        if reselects:
            sel = [sel[0]()]
        g = g[0] if with_lse else g
        # back to a column, chunked by the backward's q tile
        lse = lse.reshape(-1, q.shape[1] // bwd_block_q, bwd_block_q, 1)
        dq, dk_h, dv_h = _bwd_call(
            q, k, v, o, g.astype(o.dtype), lse,
            segs if segmented else None,
            functools.partial(bounds, q, k, segs, hi, sel, bwd_block_q, bwd_block_k),
            *sel, hi=hi, **bwd_kw,
        )
        dk_h, dv_h = sum_groups(dk_h, dv_h, group, k.dtype, v.dtype)
        # int segment ids, the queries' bounds, an int8 selection and what makes it again: no cotangent
        return (dq, dk_h, dv_h, None, *(None,) * (bounded + masked + reselects))

    core.defvjp(core_fwd, core_bwd)
    return core


def _pick_divisor(s: int, cap: int) -> int:
    """Largest power-of-two-stepped divisor of ``s`` that is ≤ cap (floor 8;
    the floor can be a non-divisor for odd/tiny s, which ``_untileable``
    then reports)."""
    b = min(cap, s)
    while s % b:
        b //= 2
    return max(b, 8)


def _auto_blocks(sq: int, sk: int, segmented: bool = False, head_dim: int = 128) -> tuple:
    """(block_q, block_k, bwd_block_q, bwd_block_k): the tile sizes measured
    fastest on one v5e that divide the sequence, chosen from what the call
    can see (the lengths, whether it carries segment ids, and the heads'
    width).

    Round 2 (2026-07-29, full train step): 512-row q tiles ~2.7x faster than
    the FlashAttention-conventional 128 (66.9k vs 24.6k tok/s at S=1024 —
    small tiles leave the MXU idle between grid steps); k tiles of 512,
    widening to 1024 at long S. PR 25 (``tools/tune_flash.py --packed``, B 2,
    S 4,096, 32/8 heads of 128, the packed4k rows, with the tile-visit table
    in): a grid step costs more than the tiles a finer grid skips, so no
    tile under 512 x 1,024 wins; the forward is fastest at 1,024 x 1,024
    (3.10 ms a call against 3.36 at 512 x 1,024 and 4.37 at 512 x 512), the
    backward the same within 0.5% at 512 x 1,024 and 1,024 x 1,024 (7.32,
    7.28 ms), so it keeps the smaller. Calls with no segment ids keep round
    2's tiles. PR 26 (the same tool at B 2, S 8,192, 20/20 heads of 256, the
    packed8k rows: latent attention's heads): 1,024 x 1,024 does not compile
    at that width (16.74M of the 16M of VMEM a kernel may use, forward and
    backward alike), so the width is part of what the choice sees; the
    forward is fastest at 1,024 x 512 (7.53 ms a call against 7.65 at 512 x
    1,024 and 8.40 at 512 x 512), the backward at 512 x 1,024 as at width 128
    (20.14 ms against 20.49 at 1,024 x 512 and 20.58 at 512 x 512). Calls at
    width 128 keep their tiles to the number. PR 31 (the fused backward, the
    same tool at the three cells' shapes and rows; backward ms a call, the
    split pair at the same tiles beside it): width 128 (B 2, S 4,096, 32/8)
    4.88 at 512 x 1,024 (split 7.77), 4.97 at 512 x 512, 5.02 at 1,024 x
    512, 5.07 at 1,024 x 1,024; width 256 (B 2, S 8,192, 20/20) 13.38 at
    512 x 1,024 (split 20.14), 13.53 at 1,024 x 1,024, which the raised VMEM
    limit now admits, 13.57 at 512 x 512; both keep 512 x 1,024. Width 64 (B
    4, S 8,192, 32/8) 25.89 at 1,024 x 1,024 against 27.05 at 512 x 1,024
    (split 41.36) and 27.46 at 1,024 x 512: half-wide operands make a grid
    step cheaper to fetch and no cheaper to start, so the backward takes the
    forward's larger q tile there. No tile under 512 wins anywhere. PERF.md
    section 6 has the sweeps."""
    bq, bk = _pick_divisor(sq, 512), _pick_divisor(sk, 1024 if sk >= 4096 else 512)
    if segmented and sq >= 4096:
        fq = _pick_divisor(sq, 1024)
        if head_dim < 128:
            return fq, bk, fq, bk
        if head_dim == 128:
            return fq, bk, bq, bk
        return fq, _pick_divisor(sk, 512), bq, bk
    return bq, bk, bq, bk


def _untileable(sq, sk, d, block_q, block_k, bwd_block_q, bwd_block_k,
                segmented, compiled):
    """Why Mosaic cannot tile this call, or None: blocks must divide the
    sequence and stay sublane-aligned (multiples of 8 rows), head_dim must
    fill the 128 lanes or half of them (``lane_fill``: a block's last
    dimension is then the array's whole extent, which Mosaic admits), and
    compiled segmented runs put the segment-id block in the lane dim, so they
    need 128-aligned blocks too."""
    if lane_fill(d) is None:
        return f"head_dim {d} is not a multiple of {_LANES} (nor {_LANES // 2}, which half fills the lanes)"
    need = _LANES if (segmented and compiled) else 8
    for name, blk, s in (
        ("block_q", block_q, sq), ("block_k", block_k, sk),
        ("bwd_block_q", bwd_block_q, sq), ("bwd_block_k", bwd_block_k, sk),
    ):
        if s % blk:
            return f"{name}={blk} does not divide sequence length {s}"
        if blk % need:
            return f"{name}={blk} (sequence length {s}) is not a multiple of {need}"
    return None


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "bwd_block_q", "bwd_block_k", "interpret", "return_lse", "window",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    segment_ids=None,
    selected=None,
    reselect=None,
    return_lse: bool = False,
    window: int = 0,
    bound=None,
) -> jax.Array:
    """q [B,S,H,D], k/v [B,S,Kh,D] → [B,S,H,D]. Differentiable (custom VJP).
    The four tile sizes default to the measured-fastest tiling for the
    sequence lengths and for whether the call is segmented (``_auto_blocks``,
    swept with tools/tune_flash.py); a forward tile the caller gives is the
    backward's too unless it gives that as well (the backward carries 6+
    operand tiles, so its VMEM sweet spot differs). ``segment_ids``
    [B, S] masks attention across packed-sequence boundaries in-kernel, and
    the tiles it masks wholly are not visited (the module docstring).
    ``selected`` [B, Sq, Sk] (int8, nonzero: kept) is a selection of pairs
    made outside, one for all the heads of a batch row
    (``ops/sparse_select.py``): a pair counts where the causal and the segment
    masks and the selection all keep it, a tile of it is an operand of every
    kernel, and a tile with no selected pair is not visited. ``reselect``, a
    ``jax.tree_util.Partial`` over arrays that gives ``selected`` again when
    called: the backward calls it for its mask (and differentiates nothing
    there) where it would else keep the forward's, [B, Sq, Sk] bytes that a
    recomputed layer's replay would have to make once more. ``return_lse``:
    ``(out, lse)`` with the rows' log-sum-exp over the pairs kept, [B, H, Sq]
    float32 (+inf on a row that keeps none), as a constant; from the kernels
    only (a shape that falls back raises). ``window`` (static, with
    ``causal``; 0: none): a query at row position ``t`` sees the keys at
    ``s <= t`` with ``t - s < window``, its own among them, inside its
    document where there are segment ids: a pair counts where all the masks
    keep it, exactly, in the output, the log-sum-exp and the three gradients,
    and a tile wholly outside the window is not visited, in the forward's
    query-major and the backward's key-major order alike (``needed_tiles``).
    ``window=0`` is the call without one, bit for bit. ``bound`` ([B, Sq]
    int32, with ``causal`` and no window): a causal bound a query. The query
    at row index ``t`` sees the keys at ``s <= bound[t]`` (inside its document
    where there are segment ids) in place of ``s <= t``: a block-causal mask
    (``bound[t]`` the last index of ``t``'s block), a strict one over another
    stream's keys (the last index before ``t``'s block), any staircase. Exact
    in the output, the log-sum-exp and the three gradients; the bound of a
    query block (its largest) takes the diagonal's place in the visit table in
    both orders (``needed_tiles``), and a query that sees no key reads 0 with
    a log-sum-exp of +inf. ``bound[t] = t`` is the causal call, bit for bit.

    ``interpret`` defaults to the Pallas interpreter off-TPU and the compiled
    kernel on a TPU. Interpreted, a shape that does not tile falls back to
    ``blockwise_attention``; compiled, it raises ``ValueError`` naming the
    dimension — callers that want a silent choice use
    ``models.transformer.auto_attention``, which checks the shape first and
    records what it chose."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    sk = k.shape[1]
    segmented = segment_ids is not None
    auto = _auto_blocks(sq, sk, segmented, d)
    # a forward tile the caller chose is the backward's too, unless it
    # chooses that as well
    bwd_block_q = min(bwd_block_q or block_q or auto[2], sq)
    bwd_block_k = min(bwd_block_k or block_k or auto[3], sk)
    block_q = min(block_q or auto[0], sq)
    block_k = min(block_k or auto[1], sk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = _untileable(
        sq, sk, d, block_q, block_k, bwd_block_q, bwd_block_k,
        segmented or bound is not None, compiled=not interpret,
    )
    if window and not causal:
        raise ValueError("a window bounds a causal call: keys before the query, its own among them")
    if bound is not None and (window or not causal):
        raise ValueError("a bound a query stands in the diagonal's place: a causal call with no window")
    if why is not None:
        if not interpret or selected is not None or return_lse or window:
            raise ValueError(
                f"flash_attention cannot compile for q{q.shape} k{k.shape}: "
                f"{why}. Pad the sequence, pass tiles that fit, or call "
                "auto_attention, which routes such shapes to the XLA path."
            )
        return blockwise_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, bound=bound
        )  # repeats GQA itself

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)

    segs = (
        segment_ids.astype(jnp.int32).reshape(b, 1, sq)
        if segmented
        else jnp.zeros((b, 1, sq), jnp.int32)  # placeholder, never read
    )
    sel = () if selected is None else (selected.astype(jnp.int8),)
    if sel and reselect is not None:
        sel += (reselect,)
    hi = () if bound is None else (bound.astype(jnp.int32).reshape(b, 1, sq),)
    out = _flash_core(
        causal, block_q, block_k, bwd_block_q, bwd_block_k, h // kh, h,
        interpret, segmented, selected is not None, return_lse, len(sel) == 2, int(window), bound is not None,
    )(qr, kr, vr, segs, *hi, *sel)
    if return_lse:
        out, lse = out
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), jax.lax.stop_gradient(lse.reshape(b, h, sq))
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def sharded_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    interpret: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
    selected: Optional[jax.Array] = None,
    reselect=None,
    window: int = 0,
):
    """Run the Pallas kernel per-shard under ``shard_map`` over ``mesh``.

    A ``pallas_call`` has no SPMD partitioning rule, so inside a GSPMD-sharded
    jit it must run in a manual (shard_map) region: batch shards over
    (data, fsdp), heads over tensor, seq/head_dim stay local. Returns ``None``
    when the mesh layout is incompatible (seq/stage axes in use, or shapes not
    divisible) — the caller falls back to the XLA dense path. sp>1 meshes
    should use ring attention instead.
    """
    from jax.sharding import PartitionSpec as P

    from maggy_tpu.parallel.spec import (
        AXIS_DATA,
        AXIS_FSDP,
        AXIS_SEQ,
        AXIS_STAGE,
        AXIS_TENSOR,
    )

    shape = dict(mesh.shape)
    dpf = shape.get(AXIS_DATA, 1) * shape.get(AXIS_FSDP, 1)
    tp = shape.get(AXIS_TENSOR, 1)
    b, sq, h, d = q.shape
    kh = k.shape[2]
    if (
        shape.get(AXIS_SEQ, 1) != 1
        or shape.get(AXIS_STAGE, 1) != 1
        or b % dpf
        or h % tp
        or kh % tp
    ):
        return None
    batch = (AXIS_DATA, AXIS_FSDP)
    spec = P(batch, None, AXIS_TENSOR, None)
    fn = functools.partial(flash_attention, causal=causal, interpret=interpret, window=window)
    # what follows its batch row, where the call has it (``reselect``: every array it holds leads with the batch)
    by_row = lambda a: P(batch, *(None,) * (a.ndim - 1))
    rows = {"segment_ids": segment_ids, "selected": selected, "reselect": reselect}
    rows = {name: (row, jax.tree.map(by_row, row)) for name, row in rows.items() if row is not None}
    return shard_map(
        lambda q, k, v, *more: fn(q, k, v, **dict(zip(rows, more))),
        mesh=mesh, in_specs=(spec, spec, spec, *(s for _, s in rows.values())), out_specs=spec,
        check_vma=False,
    )(q, k, v, *(a for a, _ in rows.values()))
