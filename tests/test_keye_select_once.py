"""``ops/sparse_select.py`` ``select`` since PR 36: a block's index scores are
made once in the forward and give the thresholds, the mask, the counts and
the log-sum-exp, and the flash kernels' backward makes the mask again from
the kept thresholds (``selection_mask`` as ``reselect``). Held against the
form it replaced, a pass of ``index_scores`` for the thresholds and a pass for
the mask, written here: equal bits in the selection, in the step's loss and
in every gradient. A file of its own so that the workers of a test run share
``test_keye_sparse.py``'s minutes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_keye_sparse import (  # noqa: F401  (the fixtures are used by name)
    batch, indexer, program_objective, seeded, selecting_decoder, tiny,
)
from maggy_tpu.models import transformer
from maggy_tpu.ops import sparse_select
from maggy_tpu.ops.flash import flash_attention
from test_flash_residuals import count, kernels


def bits(a):
    """An array for a comparison that tells -0.0 from 0.0 and holds NaNs equal."""
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def select_in_two_passes(qi, ki, w, segs, k):
    """The selection as PR 32 made it: a pass of ``index_scores`` a block of
    queries for the thresholds, then the mask from the thresholds with the
    scores made again (``selection_mask``, which the backward still runs) and
    the counts from that second block of scores."""
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    s = qi.shape[2]
    q = sparse_select._query_block(s)
    blocks = lambda: [sparse_select.index_scores(qi, ki, w, segs, at, q) for at in range(0, s, q)]
    thresholds = jnp.concatenate([
        sparse_select.topk_thresholds(scores, at, k) for at, scores in zip(range(0, s, q), blocks())
    ], axis=2)
    mask = sparse_select.selection_mask(qi, ki, w, segs, thresholds)
    n_keep = (mask != 0).sum(-1, dtype=jnp.int32)
    n_visible = (jnp.concatenate(blocks(), axis=1) > -jnp.inf).sum(-1, dtype=jnp.int32)
    counts = jnp.stack([n_keep.sum(), n_visible.sum(), (n_keep != jnp.minimum(n_visible, k)).sum(dtype=jnp.int32)])
    return mask, counts, jax.lax.bitcast_convert_type(thresholds[:, 2], jnp.float32), thresholds


@pytest.mark.parametrize("case", ["packed", "plain", "tied", "several-blocks", "tied-several-blocks", "at-most-k-keys"])
def test_one_block_of_scores_gives_what_two_passes_gave(indexer, case, monkeypatch):
    """``select`` makes a block's scores once and takes thresholds, mask,
    counts and log-sum-exp from them; bit for bit what a pass for the
    thresholds and a pass for the mask gave. Tied: operands of -1, 0 and 1
    under weights of a half and one, so a row's scores are a few values and
    the threshold cuts through a run of ties in nearly every row."""
    qi, ki, w, seg = indexer
    segs = None if case == "plain" else seg[:, None]
    k = 256 if case == "at-most-k-keys" else 32
    if case.startswith("tied"):
        qi, ki = jnp.round(qi.clip(-1, 1)), jnp.round(ki.clip(-1, 1))
        w = jnp.where(w > 0, 1.0, 0.5)
    if case.endswith("several-blocks"):
        monkeypatch.setattr(sparse_select, "_query_block", lambda s: 64)
    got, want = sparse_select.select(qi, ki, w, segs, k), select_in_two_passes(qi, ki, w, segs, k)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(bits(a), bits(b))
    mask, counts, _lse, thresholds = got
    assert int(counts[2]) == 0 and 0 < int(counts[0]) <= int(counts[1])
    if case.startswith("tied"):  # a tie was left out: some row's last admitted column is not the row's end
        assert int((thresholds[:, 1] < 255).sum()) > 100
    if case == "at-most-k-keys":  # every visible key, and no threshold over the score of a key out of sight
        assert int(counts[0]) == int(counts[1])
        assert int((thresholds[:, 0] > sparse_select.order_key(jnp.float32(-jnp.inf))).sum()) == 0


@pytest.mark.parametrize("model", ["experts-xla-attention", "flash-kernels-recomputed", "rows-within-topk"])
def test_loss_and_gradients_are_the_two_pass_forms_bit_for_bit(tiny, batch, seeded, model, monkeypatch):
    """The step with ``select`` as it is against the step with PR 32's two
    passes in its place: the same loss and the same gradient of every leaf,
    to the bit (the kernels' backward makes its mask by ``selection_mask`` on
    both sides). Rows of at most ``sparse_topk`` positions select nothing on
    either side."""
    if model == "experts-xla-attention":
        _leaves, program, params = seeded
        fn = jax.value_and_grad(lambda p: program_objective(program, p, batch)[0])
    else:
        monkeypatch.setattr(transformer, "flash_tileable", lambda *a: None)
        fields = dict(remat=True, remat_policy="nothing") if model == "flash-kernels-recomputed" else {}
        fn, params = selecting_decoder(**fields, **(dict(sparse_topk=256) if model == "rows-within-topk" else {}))
    got = fn(params)
    calls = []
    monkeypatch.setattr(sparse_select, "select", lambda *a: calls.append(a) or select_in_two_passes(*a))
    want = fn(params)
    assert bool(calls) == (model != "rows-within-topk")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(bits(a), bits(b))
    assert all(np.isfinite(a).all() for a in jax.tree.leaves(got)) and float(got[0]) > 0


def test_the_backward_reselects_inside_its_shard(indexer):
    """``reselect`` under ``shard_map``: each shard's backward makes its rows'
    mask again from its rows of the indexer's operands and thresholds, and the
    gradient is the one-chip call's that kept the mask."""
    from maggy_tpu.ops.flash import sharded_flash_attention
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    qi, ki, w, seg = indexer
    b, s, h, kh, d = 2, 256, 2, 1, 128
    q, k, v = (jax.random.normal(key, (b, s, n, d), jnp.float32)
               for key, n in zip(jax.random.split(jax.random.key(0), 3), (h, kh, kh)))
    mask, _counts, _lse, thresholds = sparse_select.select(qi, ki, w, seg[:, None], 32)
    again = jax.tree_util.Partial(sparse_select.selection_mask, qi, ki, w, seg[:, None], thresholds)
    mesh = make_mesh(ShardingSpec(fsdp=2), jax.devices()[:2])
    cot = jnp.cos(jnp.arange(b * s * h * d, dtype=jnp.float32)).reshape(b, s, h, d)
    sharded = lambda q, k, v: sharded_flash_attention(
        q, k, v, mesh=mesh, segment_ids=seg, selected=mask, reselect=again, interpret=True
    )
    kept = lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, selected=mask, interpret=True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: (sharded(*a) * cot).sum(), (0, 1, 2)))(q, k, v)
    assert kernels(count(jaxpr.jaxpr)) == dict(flash_fwd=1, index_scores=1, flash_bwd=1)
    got = jax.grad(lambda *a: (sharded(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (kept(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(a, b_)
