"""Attention op correctness: blockwise == reference, ring == reference on a
seq-sharded mesh, Ulysses == reference, flash kernel (interpret mode) ==
reference, and gradients flow through blockwise/ring."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models.transformer import default_attention
from maggy_tpu.ops.attention import blockwise_attention
from maggy_tpu.ops.flash import flash_attention
from maggy_tpu.parallel.mesh import make_mesh
from maggy_tpu.parallel.ringattention import ring_attention
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.parallel.ulysses import ulysses_attention


def qkv(b=2, s=64, h=4, kh=None, d=16, seed=0, dtype=jnp.float32):
    kh = kh or h
    rng = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(rng, 3)
    return (
        jax.random.normal(k1, (b, s, h, d), dtype),
        jax.random.normal(k2, (b, s, kh, d), dtype),
        jax.random.normal(k3, (b, s, kh, d), dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [16, 64, 50])
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = qkv()
    ref = default_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_gqa():
    q, k, v = qkv(h=8, kh=2)
    ref = default_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_grads_match():
    q, k, v = qkv(s=32)

    def loss_ref(q, k, v):
        return default_attention(q, k, v, causal=True).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_k=8).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(causal, h, kh):
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=64, h=h, kh=kh, d=16)
    ref = default_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=causal)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
def test_ring_grads_match_reference(h, kh):
    """dq, dk and dv through the ppermute ring, with grouped heads too: k and
    v rotate at their own head count and are repeated on the compute side, so
    their cotangents must sum over the group on the way back."""
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=64, h=h, kh=kh, d=16)
    # a weighted sum, so every output element carries its own cotangent
    w = jax.random.normal(jax.random.key(7), q.shape, q.dtype)

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh=mesh, causal=True) * w).sum()

    def loss_ref(q, k, v):
        return (default_attention(q, k, v, causal=True) * w).sum()

    with mesh:
        g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


@pytest.mark.slow
def test_ring_gqa_and_grads():
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=32, h=8, kh=4, d=8)
    ref = default_attention(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        g = jax.grad(
            lambda q: ring_attention(q, k, v, mesh=mesh, causal=True).sum()
        )(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g_ref = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(causal):
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=64, h=8, d=16)
    ref = default_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(
            lambda q, k, v: ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_head_divisibility():
    mesh = make_mesh(ShardingSpec(sp=8))
    q, k, v = qkv(h=4)  # 4 heads, 8 shards
    with pytest.raises(ValueError, match="divide the head count"):
        with mesh:
            ulysses_attention(q, k, v, mesh=mesh)


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal, d):
    # d must be a multiple of the 128 lanes for the kernel path, or half of them
    q, k, v = qkv(b=1, s=256, h=2, d=d)
    ref = default_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_flash_auto_blocks():
    """Default tiles (forward q, k, backward q, k): the sizes measured fastest
    on one v5e that divide the sequence."""
    from maggy_tpu.ops.flash import _auto_blocks

    assert _auto_blocks(1024, 1024) == (512, 512, 512, 512)
    assert _auto_blocks(8192, 8192) == (512, 1024, 512, 1024)  # wide k tiles at long S
    assert _auto_blocks(1280, 1280) == (256, 256, 256, 256)  # halved until they divide
    assert _auto_blocks(128, 128) == (128, 128, 128, 128)
    # segmented long rows choose by the heads' width too (the sweeps of PR 25, 26, 31)
    assert _auto_blocks(8192, 8192, True, 128) == (1024, 1024, 512, 1024)
    assert _auto_blocks(8192, 8192, True, 256) == (1024, 512, 512, 1024)
    assert _auto_blocks(8192, 8192, True, 64) == (1024, 1024, 1024, 1024)
    # segmented calls choose again from the same shapes; every choice divides
    for s in (128, 1024, 1280, 4096, 8192):
        assert all(s % b == 0 for b in _auto_blocks(s, s, True))


def test_flash_default_blocks_match_reference():
    """The auto-tuned default tiling (block_q/k=None) stays correct, fwd+bwd."""
    q, k, v = qkv(b=1, s=256, h=2, d=128)
    ref = default_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)

    g_ref = jax.grad(lambda q: (default_attention(q, k, v, causal=True) ** 2).sum())(q)
    g_fl = jax.grad(lambda q: (flash_attention(q, k, v, causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal, d):
    """The Pallas backward kernels (dQ + dK/dV split) against jax.grad through
    the XLA dense path — the round-1 gap (forward-only kernel). Width 64: the
    same kernels with half-filled lanes (``ops.flash.lane_fill``)."""
    q, k, v = qkv(b=1, s=256, h=2, d=d)

    def loss_ref(q, k, v):
        return (default_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=causal, block_q=128, block_k=128) ** 2
        ).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


def test_flash_independent_bwd_tiles():
    """bwd_block_q/bwd_block_k different from the forward's tiles: the LSE
    residual re-chunks and gradients stay exact (the silicon tuning knob,
    tools/tune_flash.py)."""
    q, k, v = qkv(b=1, s=256, h=2, d=128)

    def loss(bq, bk, bbq, bbk):
        def f(q, k, v):
            o = flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                bwd_block_q=bbq, bwd_block_k=bbk,
            )
            return (o ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    base = loss(128, 128, 128, 128)
    mixed = loss(128, 128, 64, 32)   # smaller bwd tiles
    wider = loss(64, 64, 128, 256)   # larger bwd tiles than fwd
    for a, b in zip(base, mixed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    for a, b in zip(base, wider):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_backward_gqa_bf16():
    """GQA grads sum back over the head group; bf16 within bf16 tolerance."""
    q, k, v = qkv(b=2, s=128, h=4, kh=2, d=128, dtype=jnp.bfloat16)

    def loss_ref(q, k, v):
        return default_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
            .astype(jnp.float32)
            .sum()
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-1, rtol=1e-1
        )


def test_flash_under_remat():
    """flash_attention composes with jax.checkpoint (the training config)."""
    q, k, v = qkv(b=1, s=128, h=2, d=128)

    def loss(q, k, v):
        f = jax.checkpoint(
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            policy=jax.checkpoint_policies.nothing_saveable,
        )
        return (f(q, k, v) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (default_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


# ------------------------------------------------ the tile-visit table (PR 25)

_S = 256


def _packing(name):
    """[2, _S] segment ids: the packings the flash kernels' visit table has to
    be right for. Ids restart at 1 in each row; 0 is the padded tail."""
    rng = np.random.default_rng(7)

    def row(*docs):
        ids = np.zeros(_S, np.int32)
        at = 0
        for j, n in enumerate(docs):
            ids[at:at + n] = j + 1
            at += n
        return ids

    return np.stack({
        "one_document": [row(_S), row(_S)],
        "many_short": [row(*[32] * 8), row(17, 40, 9, 61, 30, 55, 44)],
        "padded_tail": [row(70, 70, 70), row(200)],
        "mixed_rows": [row(_S), row(20, 31, 45, 64, 50, 46)],
        "all_padding_row": [row(), row(100, 100)],
        # ids that go up and down: the table may only be a superset
        "not_monotone": [rng.integers(0, 3, _S).astype(np.int32), row(90, 90)[::-1].copy()],
    }[name])


_PACKINGS = ("one_document", "many_short", "padded_tail", "mixed_rows", "all_padding_row", "not_monotone")


def _brute_force_tiles(seg, causal, block_q, block_k):
    """any() over each tile's full mask."""
    s = seg.shape[1]
    mask = seg[:, :, None] == seg[:, None, :]
    if causal:
        mask &= np.tril(np.ones((s, s), bool))[None]
    return mask.reshape(-1, s // block_q, block_q, s // block_k, block_k).any(axis=(2, 4))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(32, 64), (64, 32), (128, 128)])
@pytest.mark.parametrize("packing", _PACKINGS)
def test_flash_tile_table_against_brute_force(packing, blocks, causal):
    """``needed_tiles`` (block ranges of ids overlap, not above the diagonal)
    equals a brute-force any() over each tile's mask for the ids packed rows
    carry and holds every such tile for any other ids; the bounds the kernels
    visit hold every needed tile, and every clamped block index lies inside
    the row's first-to-last needed block."""
    from maggy_tpu.ops.flash import _resident, needed_tiles, visit_bounds

    seg = _packing(packing)
    bq, bk = blocks
    kw = dict(causal=causal, sq=_S, sk=_S, block_q=bq, block_k=bk)
    truth = _brute_force_tiles(seg, causal, bq, bk)
    need = needed_tiles(seg.reshape(2, 1, _S), **kw)
    assert need.shape == truth.shape
    if packing == "not_monotone":
        assert (need | ~truth).all()  # a superset: no pair is lost
    else:
        np.testing.assert_array_equal(need, truth)
    # traced ids give the same table as host ids
    traced = jax.jit(lambda s: needed_tiles(s, **kw))(jnp.asarray(seg).reshape(2, 1, _S))
    np.testing.assert_array_equal(np.asarray(traced), need)

    for outer, tiles in (("q", need), ("k", need.swapaxes(1, 2))):
        bounds = visit_bounds(seg.reshape(2, 1, _S), outer, **kw)
        rows, n_outer, n_red = tiles.shape
        first, last = bounds.reshape(rows, n_outer, 2).transpose(2, 0, 1)
        red = np.arange(n_red)
        visited = (first[..., None] <= red) & (red <= last[..., None])
        assert (visited | ~tiles).all()
        if packing != "not_monotone":
            np.testing.assert_array_equal(visited, tiles)  # needed blocks are contiguous
        for r in range(rows):
            for o in range(n_outer):
                at = [int(_resident(bounds, r, o, n_outer, i)) for i in red]
                lo, hi = first[r, o], max(last[r, o], first[r, o])
                assert all(lo <= a <= hi for a in at), (outer, r, o, at)
                assert all(a == i for a, i in zip(at, red) if visited[r, o, i])


def test_flash_tile_table_without_segments_is_the_causal_clamp():
    from maggy_tpu.ops.flash import needed_tiles, visit_bounds

    kw = dict(sq=_S, sk=_S, block_q=64, block_k=32)
    assert needed_tiles(None, causal=True, **kw).shape == (1, 4, 8)  # one row for every batch row
    np.testing.assert_array_equal(
        visit_bounds(None, "q", causal=True, **kw).reshape(4, 2),
        [[0, 1], [0, 3], [0, 5], [0, 7]],
    )
    np.testing.assert_array_equal(
        visit_bounds(None, "k", causal=True, **kw).reshape(8, 2),
        [[0, 3], [0, 3], [1, 3], [1, 3], [2, 3], [2, 3], [3, 3], [3, 3]],
    )
    assert needed_tiles(None, causal=False, **kw).all()


def _kernels_with_table(q, k, v, do, seg, table_seg, fwd_blocks, bwd_blocks, *, causal=True, backward="_bwd_call"):
    """o, lse, dq, dk, dv from the kernels (interpreted), masking by ``seg``
    (None: no segment ids) and visiting the tiles ``table_seg`` leaves; the
    gradients from ``backward``: ``_bwd_call``, which chooses the form as the
    public path does, or ``_bwd_fused`` / ``_bwd_split``."""
    from maggy_tpu.ops import flash

    b, s, h, d = q.shape
    kh = k.shape[2]
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, s, d)
    q, k, v, do = flat(q), flat(k), flat(v), flat(do)
    segs = None if seg is None else jnp.asarray(seg).reshape(b, 1, s)
    table = None if table_seg is None else jnp.asarray(table_seg).reshape(b, 1, s)

    def bounds(blocks, outer):
        return jnp.asarray(flash.visit_bounds(
            table, outer, causal=causal, sq=s, sk=s, block_q=blocks[0], block_k=blocks[1]
        ))

    kw = dict(causal=causal, group=h // kh, heads=h, interpret=True)
    o, lse = flash._fwd_call(
        q, k, v, segs, bounds(fwd_blocks, "q"),
        block_q=fwd_blocks[0], block_k=fwd_blocks[1], **kw,
    )
    lse_b = lse.reshape(b * h, s // bwd_blocks[0], bwd_blocks[0], 1)
    grads = getattr(flash, backward)(
        q, k, v, o, do, lse_b, segs, functools.partial(bounds, bwd_blocks),
        block_q=bwd_blocks[0], block_k=bwd_blocks[1], **kw,
    )
    return [np.asarray(x) for x in (o, lse, *grads)]


_KERNEL_GEOMETRIES = pytest.mark.parametrize(
    "kh,fwd_blocks,bwd_blocks,d",
    [(4, (64, 64), (64, 64), 128), (1, (64, 64), (64, 64), 128), (1, (32, 128), (64, 32), 128),
     (1, (64, 64), (64, 64), 64)],
    ids=["group1", "group4", "group4-bwd-tiles-unlike-fwd", "group4-width64"],
)


@_KERNEL_GEOMETRIES
@pytest.mark.parametrize("packing", _PACKINGS + ("unsegmented", "non_causal"))
def test_flash_fused_backward_equals_the_split_kernels(packing, kh, fwd_blocks, bwd_blocks, d):
    """At equal tiles ``flash_bwd`` gives the dq, dk and dv of ``flash_dq`` and
    ``flash_dkv``, bit for bit: it visits by the k-outer table alone, a tile
    in only one of the two tables is wholly masked and adds zero, and every q
    block gets its k blocks in ascending order. Over the packings, with no
    segment ids (the causal diagonal alone) and non-causal (documents alone)."""
    q, k, v = qkv(b=2, s=_S, h=4, kh=kh, d=d, seed=3)
    do = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    seg = None if packing == "unsegmented" else _packing("mixed_rows" if packing == "non_causal" else packing)
    run = functools.partial(
        _kernels_with_table, q, k, v, do, seg, seg, fwd_blocks, bwd_blocks, causal=packing != "non_causal"
    )
    fused, split = run(backward="_bwd_fused"), run(backward="_bwd_split")
    for name, a, b in zip(("dq", "dk", "dv"), fused[2:], split[2:]):
        assert np.abs(b).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize(
    "s,d,form",
    [(8192, 256, "fused"), (4096, 128, "fused"), (8192, 64, "fused"), (32768, 128, "fused"),
     (65536, 128, "split"), (32768, 256, "split")],
    ids=["glm-cell", "mistral-cell", "lfm2-cell", "at-the-budget", "over-the-budget", "over-the-budget-width256"],
)
def test_flash_backward_form_follows_the_row_and_the_width(s, d, form):
    """A head's dq stays in VMEM where it fits the stated budget, and a longer
    row keeps the two split kernels: chosen by ``sq`` and the width alone,
    and counted by kernel name in the jaxpr of a gradient (nothing runs)."""
    from maggy_tpu.ops.flash import BACKWARD_KERNELS, backward_form
    from tests.test_flash_residuals import count, kernels

    assert backward_form(s, d) == form
    assert BACKWARD_KERNELS == {"fused": ("flash_bwd",), "split": ("flash_dq", "flash_dkv")}
    x = jax.ShapeDtypeStruct((1, s, 1, d), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    launched = kernels(count(jax.make_jaxpr(grad)(x, x, x).jaxpr))
    assert launched == {"flash_fwd": 1, **dict.fromkeys(BACKWARD_KERNELS[form], 1)}


@_KERNEL_GEOMETRIES
@pytest.mark.parametrize("packing", _PACKINGS)
def test_flash_skipped_tiles_change_no_bit(packing, kh, fwd_blocks, bwd_blocks, d):
    """Output, LSE, dq, dk and dv with the visit table made from the segment
    ids equal, bit for bit, the same kernels made to visit every causal tile
    (a table made from ids that are all one document)."""
    q, k, v = qkv(b=2, s=_S, h=4, kh=kh, d=d, seed=3)
    do = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    seg = _packing(packing)
    skipping = _kernels_with_table(q, k, v, do, seg, seg, fwd_blocks, bwd_blocks)
    every = _kernels_with_table(q, k, v, do, seg, np.ones_like(seg), fwd_blocks, bwd_blocks)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), skipping, every):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # and the public path, which makes its own table, is those kernels
    out = flash_attention(
        q, k, v, causal=True, segment_ids=jnp.asarray(seg), block_q=fwd_blocks[0],
        block_k=fwd_blocks[1], bwd_block_q=bwd_blocks[0], bwd_block_k=bwd_blocks[1],
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(out.transpose(0, 2, 1, 3).reshape(-1, _S, d)), every[0]
    )


@pytest.mark.parametrize("d,kh", [(128, 2), (64, 1)], ids=["width128", "width64-gqa"])
def test_flash_two_packings_one_trace(d, kh):
    """The visit table is data: batches of different packing run the jitted
    function with one trace, each right against the dense reference with the
    same segment ids: the output and the gradients of q, k and v."""
    q, k, v = qkv(b=2, s=_S, h=2, kh=kh, d=d, seed=5)
    traces = []

    def square(attn, seg):
        def f(q, k, v):
            out = attn(q, k, v, segment_ids=seg)
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    @jax.jit
    def flash(q, k, v, seg):
        traces.append(1)
        kernel = lambda q, k, v, segment_ids: flash_attention(
            q, k, v, segment_ids=segment_ids, block_q=64, block_k=64, interpret=True
        )
        return square(kernel, seg)(q, k, v)

    for packing in ("many_short", "padded_tail", "one_document"):
        seg = jnp.asarray(_packing(packing))
        (_, out), grads = flash(q, k, v, seg)
        (_, want), ref = square(default_attention, seg)(q, k, v)
        real = np.asarray(seg > 0)[..., None, None]  # a padded position attends to padding: nobody reads it
        np.testing.assert_allclose(np.asarray(out) * real, np.asarray(want) * real, atol=2e-3, rtol=2e-3)
        for a, b in zip(grads, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)
    assert len(traces) == 1


def test_tiles_visited_share():
    from maggy_tpu.ops.flash import tiles_visited_share

    # 4 q blocks x 4 k blocks of 64: one document visits the 10 causal tiles
    assert tiles_visited_share(_packing("one_document"), block_q=64, block_k=64) == 10 / 16
    # four documents of 64 visit the diagonal only; a padded tail is one more document
    four = np.repeat(np.arange(1, 5, dtype=np.int32), 64)[None]
    assert tiles_visited_share(four, block_q=64, block_k=64) == 4 / 16
    assert tiles_visited_share(np.where(four == 4, 0, four), block_q=64, block_k=64) == 4 / 16
    assert tiles_visited_share(np.ones((1, 200), np.int32), block_q=64, block_k=64) is None
    assert 0 < tiles_visited_share(_packing("many_short")) <= 1  # the automatic tiles


def test_sharded_flash_matches_reference():
    """The shard_map wrap that auto_attention uses on multi-device meshes —
    a pallas_call has no GSPMD partitioning rule, so this is the only legal
    multi-chip route; exercised here on the CPU mesh in interpret mode."""
    from maggy_tpu.ops.flash import sharded_flash_attention

    mesh = make_mesh(ShardingSpec(dp=2, fsdp=2, tp=2))
    q, k, v = qkv(b=4, s=128, h=2, d=128)
    ref = default_attention(q, k, v, causal=True)
    with mesh:
        out = jax.jit(
            lambda q, k, v: sharded_flash_attention(
                q, k, v, mesh=mesh, causal=True, interpret=True
            )
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
    # gradients flow through the shard_map'd custom VJP
    with mesh:
        g = jax.jit(
            jax.grad(
                lambda q: sharded_flash_attention(
                    q, k, v, mesh=mesh, causal=True, interpret=True
                ).sum()
            )
        )(q)
    g_ref = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-2, rtol=2e-2)


def test_sharded_flash_refuses_incompatible_mesh():
    from maggy_tpu.ops.flash import sharded_flash_attention

    q, k, v = qkv(b=2, s=128, h=4, d=128)
    sp_mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    assert sharded_flash_attention(q, k, v, mesh=sp_mesh) is None  # sp in use
    dp_mesh = make_mesh(ShardingSpec(dp=8))
    q3, k3, v3 = qkv(b=3, s=128, h=4, d=128)
    assert sharded_flash_attention(q3, k3, v3, mesh=dp_mesh) is None  # 3 % 8


def test_flash_fallback_on_odd_shapes():
    q, k, v = qkv(b=1, s=60, h=2, d=16)  # not tileable -> blockwise fallback
    ref = default_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_compiled_refuses_odd_shapes_by_name():
    """interpret=False (what a TPU picks by itself) never falls back: the
    caller asked for the kernel, so an untileable shape raises, naming the
    dimension — before any Mosaic lowering, so this runs off-TPU too."""
    q, k, v = qkv(b=1, s=60, h=2, d=16)
    with pytest.raises(ValueError, match="head_dim 16 is not a multiple of 128"):
        flash_attention(q, k, v, causal=True, interpret=False)
    q, k, v = qkv(b=1, s=100, h=2, d=128)
    with pytest.raises(ValueError, match=r"block_q=100 \(sequence length 100\)"):
        flash_attention(q, k, v, causal=True, interpret=False)
    # packed segments put the block in the lane dim: 8-aligned is not enough
    q, k, v = qkv(b=2, s=200, h=2, d=128)
    seg = jnp.zeros((2, 200), jnp.int32)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        flash_attention(q, k, v, segment_ids=seg, interpret=False)


def test_auto_attention_records_its_choice():
    """The automatic dispatch is never silent: one attention.kernel event
    per trace says which kernel it took and, off the flash path, why."""
    from maggy_tpu import telemetry
    from maggy_tpu.models.transformer import auto_attention

    tel = telemetry.Telemetry(worker="t")
    q, k, v = qkv(b=1, s=128, h=2, d=128)
    with telemetry.current(tel):
        auto_attention(q, k, v)
    (event,) = [e for e in tel.drain_events() if e["name"] == "attention.kernel"]
    assert event["attrs"]["kernel"] == "xla_dense"
    assert event["attrs"]["reason"] == "backend is cpu"
    assert event["attrs"]["q"] == [1, 128, 2, 128]
    assert "block_q" not in event["attrs"]  # the dense path has no tiles


def test_flash_kernel_event_carries_the_tiles():
    from maggy_tpu import telemetry
    from maggy_tpu.models.transformer import record_attention_kernel
    from maggy_tpu.ops.flash import _auto_blocks

    tel = telemetry.Telemetry(worker="t")
    q, k, _ = qkv(b=1, s=4096, h=1, d=128)
    with telemetry.current(tel):
        record_attention_kernel("flash", q, k, jnp.ones((1, 4096), jnp.int32))
    (event,) = [e for e in tel.drain_events() if e["name"] == "attention.kernel"]
    tiles = tuple(event["attrs"][n] for n in ("block_q", "block_k", "bwd_block_q", "bwd_block_k"))
    assert tiles == _auto_blocks(4096, 4096, True)
    assert (event["attrs"]["head_dim"], event["attrs"]["lanes"]) == (128, "full")
    assert event["attrs"]["backward"] == "fused"  # what ``_bwd_call`` asks: ``backward_form``
    q, k, _ = qkv(b=1, s=4096, h=4, kh=1, d=64)
    with telemetry.current(tel):
        record_attention_kernel("flash", q, k, jnp.ones((1, 4096), jnp.int32))
    (event,) = [e for e in tel.drain_events() if e["name"] == "attention.kernel"]
    assert (event["attrs"]["head_dim"], event["attrs"]["lanes"]) == (64, "half")
    assert event["attrs"]["block_q"] == _auto_blocks(4096, 4096, True, 64)[0]


@pytest.mark.slow
def test_decoder_with_ring_attention_e2e():
    """Decoder runs unchanged with ring attention as its attention_fn on an
    sp mesh — the long-context config."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.ringattention import make_ring_attention
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    ctx = TrainContext.create(ShardingSpec(sp=4, dp=2))
    cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 4, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    first = last = None
    for _ in range(15):
        state, m = trainer.step(state, trainer.shard_batch(next(data)))
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert np.isfinite(last) and last < first
