"""The kernels under ``sdar-30b-a3b-chat`` (block diffusion: a clean and a
noised stream through one layer, a block-wise mask between them): the flash
kernels' causal bound a query (``flash_attention(bound=)``) against explicit
scores, output, log-sum-exp and the three gradients, on packed documents that
are not multiples of the block long, with blocks that straddle a tile; the
bound ``t`` bit for bit the causal call; the visit table against a brute-force
count in both orders; and the noised stream's attention (``ops/blockdiff.py``:
the flash kernels on the clean keys, the band kernels on the own block, which
continue the flash call's softmax) against the explicit ``[L, 2L]`` mask, with
the joint log-sum-exp and every gradient, on rows whose blocks and documents
meet the band kernels' tiles every way.

Both sides compute in float32 here, so what differs is the order of the sums.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from maggy_tpu.ops import blockdiff, flash  # noqa: E402
from maggy_tpu.ops.attention import blockwise_attention  # noqa: E402
from maggy_tpu.models.transformer import default_attention  # noqa: E402

B, L, H, KH, D, BLOCK = 2, 256, 4, 2, 64, 4
TILE = 64  # toy tiles in the interpreter: a row is four of them
# documents not multiples of the block long; 61 + 70 puts a block across the tile edge at 128 (positions 64..67 of
# the second document lie at rows 125..128), and the second row ends in padding
DOCS = [[61, 70, 125], [19, 90, 33, 50]]


def rows_of(docs, l=L):
    pos, seg = np.zeros((len(docs), l), np.int32), np.zeros((len(docs), l), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
    return pos, seg


POS, SEG = rows_of(DOCS)
LAY = blockdiff.layout(POS, SEG, BLOCK)


def qkv(seed, kh=KH):
    rng = np.random.default_rng(seed)
    draw = lambda heads: jnp.asarray(rng.standard_normal((B, L, heads, D)), jnp.float32)
    return draw(H), draw(kh), draw(kh)


def explicit(q, k, v, mask):
    """Softmax attention on an explicit mask [B, Sq, Sk]: ``(out, lse [B, H, Sq])``; a query that sees nothing reads 0."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(b, sq, kh, h // kh, d), k, precision="highest") / jnp.sqrt(jnp.float32(d))
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(mask[:, None, None], jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision="highest").reshape(b, sq, h, d)
    return out, lse.reshape(b, h, sq)


def bound_mask(hi, seg):
    same = seg[:, :, None] == seg[:, None, :]
    return jnp.asarray(same & (np.arange(seg.shape[1])[None, None, :] <= np.asarray(hi)[:, :, None]))


BOUNDS = {"block_causal": LAY.hi_clean, "before_the_block": LAY.hi_noised}


def test_the_layout_of_a_packed_row():
    """A block is four positions of one document, wherever they lie in the row."""
    for r in range(B):
        for t in range(L):
            mates = [t + off for j, off in enumerate(range(1 - BLOCK, BLOCK)) if LAY.own[r, t, j]]
            assert t in mates  # a query is its own block-mate, a padding position too
            if SEG[r, t] > 0:
                assert mates == [s for s in range(L) if SEG[r, s] == SEG[r, t] and POS[r, s] // BLOCK == POS[r, t] // BLOCK]
                assert LAY.hi_clean[r, t] == t - POS[r, t] % BLOCK + BLOCK - 1
                assert LAY.hi_noised[r, t] == t - POS[r, t] % BLOCK - 1


@pytest.mark.parametrize("name", sorted(BOUNDS))
def test_bounded_flash_matches_explicit_scores(name):
    hi = BOUNDS[name]
    q, k, v = qkv(1)
    seg, mask = jnp.asarray(SEG), bound_mask(hi, SEG)
    kw = dict(segment_ids=seg, bound=jnp.asarray(hi), block_q=TILE, block_k=TILE, interpret=True)
    out, lse = flash.flash_attention(q, k, v, return_lse=True, **kw)
    want, want_lse = explicit(q, k, v, mask)
    np.testing.assert_allclose(out, want, atol=2e-5)
    sees = np.isfinite(np.asarray(want_lse))
    assert not sees.all() or name == "block_causal"  # a document's first block sees no clean key from the noised stream
    np.testing.assert_allclose(np.asarray(lse)[sees], np.asarray(want_lse)[sees], atol=2e-5)
    assert np.all(np.asarray(lse)[~sees] == np.inf) and np.all(np.asarray(out).transpose(0, 2, 1, 3)[~sees] == 0)

    w = jnp.asarray(np.random.default_rng(2).standard_normal(out.shape), jnp.float32)
    got = jax.grad(lambda q, k, v: (flash.flash_attention(q, k, v, **kw) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda q, k, v: (explicit(q, k, v, mask)[0] * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_, what in zip(got, ref, "qkv"):
        np.testing.assert_allclose(a, b_, atol=1e-4, err_msg=f"d{what}")


@pytest.mark.parametrize("backward", ["fused", "split"])
def test_the_bound_t_is_the_causal_call_bit_for_bit(backward, monkeypatch):
    if backward == "split":
        monkeypatch.setattr(flash, "_FUSED_DQ_VMEM_BYTES", 0)
        flash._flash_core.cache_clear()
    q, k, v = qkv(3)
    seg = jnp.asarray(SEG)
    kw = dict(segment_ids=seg, block_q=TILE, block_k=TILE, interpret=True)
    own = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    w = jnp.asarray(np.random.default_rng(4).standard_normal(q.shape), jnp.float32)

    def both(**more):
        out, lse = flash.flash_attention(q, k, v, return_lse=True, **kw, **more)
        grads = jax.grad(lambda q, k, v: (flash.flash_attention(q, k, v, **kw, **more) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        return (out, lse, *grads)

    for a, b_ in zip(both(), both(bound=own)):
        assert np.array_equal(np.asarray(a), np.asarray(b_))
    flash._flash_core.cache_clear()


@pytest.mark.parametrize("outer", ["q", "k"])
@pytest.mark.parametrize("name", sorted(BOUNDS))
def test_needed_tiles_against_a_brute_force_count(name, outer):
    hi = BOUNDS[name]
    mask = np.asarray(bound_mask(hi, SEG)) & (SEG[:, :, None] > 0)
    n = L // TILE
    brute = mask.reshape(B, n, TILE, n, TILE).any(axis=(2, 4))
    tiles = dict(causal=True, sq=L, sk=L, block_q=TILE, block_k=TILE, bound=hi.reshape(B, 1, L))
    need = flash.needed_tiles(SEG.reshape(B, 1, L), **tiles)
    real = (SEG.reshape(B, n, TILE) > 0).any(-1)
    # every tile with a pair is needed, and among the real tokens' tiles no other (the ids never decrease along a row)
    assert np.all(need[brute])
    extra = need & ~brute & real[:, :, None] & real[:, None, :]
    # a tile needed with no pair: only where the block's bound reaches one key past a document's end
    assert extra.sum() <= B * n
    first, last = flash.visit_bounds(SEG.reshape(B, 1, L), outer, **tiles).reshape(B, n, 2).transpose(2, 0, 1)
    run = need if outer == "q" else need.swapaxes(1, 2)
    for r in range(B):
        for o in range(n):
            cols = np.flatnonzero(run[r, o])
            assert (first[r, o], last[r, o]) == ((cols[0], cols[-1]) if len(cols) else (0, -1))
            assert len(cols) == 0 or np.array_equal(cols, np.arange(cols[0], cols[-1] + 1))  # one run: first..last says all
    # traced, the table is the host's
    traced = jax.jit(lambda s, h: flash.visit_bounds(s, outer, **dict(tiles, bound=h)))(
        jnp.asarray(SEG.reshape(B, 1, L)), jnp.asarray(hi.reshape(B, 1, L)))
    assert np.array_equal(np.asarray(traced), flash.visit_bounds(SEG.reshape(B, 1, L), outer, **tiles))


def test_the_bound_on_the_xla_paths():
    q, k, v = qkv(5)
    hi, seg = jnp.asarray(LAY.hi_clean), jnp.asarray(SEG)
    want, _ = explicit(q, k, v, bound_mask(LAY.hi_clean, SEG))
    np.testing.assert_allclose(default_attention(q, k, v, segment_ids=seg, bound=hi), want, atol=2e-5)
    np.testing.assert_allclose(blockwise_attention(q, k, v, segment_ids=seg, bound=hi, block_k=64), want, atol=2e-5)


def test_a_bound_takes_no_window_and_a_causal_call():
    q, k, v = qkv(6)
    hi = jnp.asarray(LAY.hi_clean)
    with pytest.raises(ValueError, match="diagonal's place"):
        flash.flash_attention(q, k, v, bound=hi, window=8, interpret=True)
    with pytest.raises(ValueError, match="diagonal's place"):
        flash.flash_attention(q, k, v, bound=hi, causal=False, interpret=True)


def noised_reference(q, k_c, v_c, k_n, v_n):
    mask = blockdiff.noised_mask(jnp.asarray(POS), jnp.asarray(SEG), LAY, BLOCK)
    return explicit(q, jnp.concatenate([k_c, k_n], 1), jnp.concatenate([v_c, v_n], 1), mask)[0]


@pytest.mark.parametrize("path", ["kernels", "xla"])
def test_noised_attention_is_one_softmax_over_both_sets(path, monkeypatch):
    monkeypatch.setattr(flash, "_auto_blocks", lambda *a, **k: (TILE, TILE, TILE, TILE))
    blockdiff._core.cache_clear()
    q, k_c, v_c = qkv(7)
    _, k_n, v_n = qkv(8)
    pos, seg = jnp.asarray(POS), jnp.asarray(SEG)
    lay = blockdiff.layout(pos, seg, BLOCK)
    if path == "kernels":
        fn = lambda *a: blockdiff.noised_attention(*a, seg, lay, block=BLOCK, interpret=True)
    else:
        mask = blockdiff.noised_mask(pos, seg, lay, BLOCK)  # what the dispatch hands the XLA attention off the chip
        fn = lambda q, k_c, v_c, k_n, v_n: default_attention(
            q, jnp.concatenate([k_c, k_n], 1), jnp.concatenate([v_c, v_n], 1), causal=False, selected=mask)
    args = (q, k_c, v_c, k_n, v_n)
    # the real queries: a padding position sees padding, by the row's neighbours here and by block 0 there
    real = jnp.asarray(SEG > 0)[:, :, None, None]
    np.testing.assert_allclose(fn(*args) * real, noised_reference(*args) * real, atol=2e-5)
    w = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape), jnp.float32) * real
    got = jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=range(5))(*args)
    ref = jax.grad(lambda *a: (noised_reference(*a) * w).sum(), argnums=range(5))(*args)
    for a, b_, what in zip(got, ref, ("q", "k_clean", "v_clean", "k_noised", "v_noised")):
        np.testing.assert_allclose(a, b_, atol=1e-4, err_msg=f"d{what}")
    blockdiff._core.cache_clear()


# what a row of the band kernels' grid can hold, two rows a case (tiles of 64 or 128 rows, products of 64 queries)
BAND_ROWS = {
    # positions 64..67 of the second document lie at rows 125..128: the queries at 125..127 have a mate in the
    # next tile's first row, the query at 128 its three mates in the tile before
    "a_block_cut_by_a_tile_edge": DOCS,
    # the second document ends at row 63 in a block of two, the third at row 192 in a block of one that opens a tile
    "a_document_ends_at_a_tile_edge": [[50, 14, 129, 63], [64, 64, 128]],
    "documents_shorter_than_a_block": [[3, 1, 2, 3, 2, 1, 244], [1] * 9 + [2] * 5 + [3] * 3 + [228]],
    "a_padded_tail": [[19, 90, 33, 50], [61, 3]],
}


@pytest.mark.parametrize("rows,heads,kv_heads,band", [
    ("a_block_cut_by_a_tile_edge", 4, 2, (64, 64, 8)),
    ("a_block_cut_by_a_tile_edge", 8, 1, (128, 64, 8)),
    ("a_document_ends_at_a_tile_edge", 2, 2, (64, 64, 8)),
    ("a_document_ends_at_a_tile_edge", 4, 2, (128, 64, 8)),
    ("documents_shorter_than_a_block", 2, 2, (128, 64, 8)),
    ("documents_shorter_than_a_block", 8, 1, (64, 64, 8)),
    ("a_padded_tail", 4, 2, (64, 64, 8)),
    ("a_padded_tail", 2, 2, (128, 64, 8)),
])
def test_the_band_kernels_against_the_explicit_mask(rows, heads, kv_heads, band, monkeypatch):
    """The noised stream on the kernels in the interpreter (the flash call on the clean keys, ``own_block_fwd``
    and ``own_block_bwd`` on the own block) against the explicit ``[L, 2L]`` mask: output, joint log-sum-exp and
    the five gradients, at groups of 1, 2 and 8 heads a key head and two tile sizes."""
    monkeypatch.setattr(flash, "_auto_blocks", lambda *a, **k: (TILE, TILE, TILE, TILE))
    monkeypatch.setattr(blockdiff, "band_tiles", lambda l, block: band)
    blockdiff._core.cache_clear()
    pos, seg = (jnp.asarray(a) for a in rows_of(BAND_ROWS[rows]))
    lay = blockdiff.layout(pos, seg, BLOCK)
    rng = np.random.default_rng(11)
    draw = lambda n: jnp.asarray(rng.standard_normal((B, L, n, D)), jnp.float32)
    args = (draw(heads), draw(kv_heads), draw(kv_heads), draw(kv_heads), draw(kv_heads))
    mask = blockdiff.noised_mask(pos, seg, lay, BLOCK)
    both = lambda c, n: jnp.concatenate([c, n], 1)
    fn = lambda *a: blockdiff.noised_attention(*a, seg, lay, block=BLOCK, interpret=True)
    ref = lambda q, k_c, v_c, k_n, v_n: default_attention(q, both(k_c, k_n), both(v_c, v_n), causal=False, selected=mask)
    # the real queries: a padding position sees padding, by the row's neighbours here and by block 0 there
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(fn(*args)[real], ref(*args)[real], atol=2e-5)
    _, lse = blockdiff._forward(
        (TILE,) * 4, band, True, *args, seg.reshape(B, 1, L), lay.hi_noised.reshape(B, 1, L), blockdiff.block_ids(lay, seg),
    )
    want = explicit(args[0], both(args[1], args[3]), both(args[2], args[4]), mask)[1]
    np.testing.assert_allclose(lse.reshape(B, heads, L).transpose(0, 2, 1)[real], want.transpose(0, 2, 1)[real], atol=2e-5)
    w = jnp.asarray(rng.standard_normal(args[0].shape), jnp.float32) * real[:, :, None, None]
    got = jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=range(5))(*args)
    exp = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=range(5))(*args)
    for a, b_, what in zip(got, exp, ("q", "k_clean", "v_clean", "k_noised", "v_noised")):
        np.testing.assert_allclose(a, b_, atol=1e-4, err_msg=f"d{what}")
    blockdiff._core.cache_clear()


def test_block_ids_number_the_runs_of_block_mates():
    """Two real positions are block-mates (``Layout.own``) exactly where their numbers are equal; a padding position
    (all at position 0 here) is its own block."""
    for docs in BAND_ROWS.values():
        pos, seg = rows_of(docs)
        lay = blockdiff.layout(pos, seg, BLOCK)
        ids = np.asarray(blockdiff.block_ids(blockdiff.layout(jnp.asarray(pos), jnp.asarray(seg), BLOCK), jnp.asarray(seg)))
        assert ids.min() == 1
        for j, off in enumerate(range(1 - BLOCK, BLOCK)):
            same = np.roll(ids, -off, axis=1) == ids
            at = np.arange(L)[None, :] + off
            np.testing.assert_array_equal((same & (at >= 0) & (at < L))[seg > 0], lay.own[:, :, j][seg > 0])
        assert (np.diff(ids, axis=1)[(seg == 0)[:, 1:]] == 1).all()
        far = np.abs(np.subtract.outer(np.arange(L), np.arange(L))) >= BLOCK  # no number comes back past the band
        assert not (far[None] & (ids[:, :, None] == ids[:, None, :])).any()


def test_the_pairs_the_mask_keeps():
    pos, seg = jnp.asarray(POS), jnp.asarray(SEG)
    kept, causal = (float(a) for a in blockdiff.pairs(pos, seg, blockdiff.layout(pos, seg, BLOCK), BLOCK))
    real = SEG > 0
    clean = np.asarray(bound_mask(LAY.hi_clean, SEG))[real].sum()
    noised = np.asarray(blockdiff.noised_mask(pos, seg, LAY, BLOCK))[real].sum()
    assert kept == clean + noised
    assert causal == sum(n * (n + 1) // 2 for row in DOCS for n in row)
    assert 1.9 < kept / causal < 2.2


def test_tiles_visited_share_counts_both_grids(monkeypatch):
    monkeypatch.setattr(flash, "_auto_blocks", lambda *a, **k: (TILE, TILE, TILE, TILE))
    n = L // TILE
    visited = 0
    for hi in (LAY.hi_clean, LAY.hi_noised):
        need = flash.needed_tiles(SEG.reshape(B, 1, L), causal=True, sq=L, sk=L, block_q=TILE, block_k=TILE, bound=hi.reshape(B, 1, L))
        visited += need.sum()  # each query block's needed tiles are one run
    assert blockdiff.tiles_visited_share(SEG, block=BLOCK, head_dim=D) == visited / (2 * B * n * n)
