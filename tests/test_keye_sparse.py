"""The language model of ``KeyeVL2`` (grouped-query attention over the keys a
learned indexer selects, heads wider than ``d_model / n_heads``, softmax-routed
dropless experts, an untied head) against its plain reference
``benchmark/references/sparse_gqa_moe.py``, on seeded weights at small sizes
with the published ratios (``benchmark/checks/tiny.keye-vl-2.0-30b-a3b.json``),
and the pieces under it: the index scores, the exact selection and the
indexer's loss (``ops/sparse_select.py``), the flash kernels with a selection
as an operand (``ops/flash.py``), the residuals a recomputed layer keeps.

Both sides compute in float32 here, so what differs is the order of the sums;
a selection is discontinuous, so the rows are chosen without near-ties (the
seeded scores are apart by far more than a rounding). The chip run's
comparison, in bfloat16, is the cell's (``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, run as bench_run, weights  # noqa: E402
from benchmark.references import sparse_gqa_moe as reference  # noqa: E402
from benchmark.references.decoder import adamw_apply  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.ops import sparse_select  # noqa: E402
from maggy_tpu.ops.flash import backward_form, flash_attention  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402
from test_flash_residuals import count, kernels  # noqa: E402  (Pallas kernels and checkpoint names in a jaxpr)

KIND = "train_packed_ref"
SEED = 13
S = 128


def load(**over):
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.keye-vl-2.0-30b-a3b.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(bench_run.merge(configs.load("benchmark/configs/keye-vl-2.0-30b-a3b.json"), small), over)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def tiny():
    return load()


def packed(docs, rng):
    tok = rng.integers(1, 512, size=(len(docs), S), dtype=np.int32)
    pos, seg = np.zeros((len(docs), S), np.int32), np.zeros((len(docs), S), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
        tok[r, at:] = 0
    return {k: jnp.asarray(v) for k, v in
            dict(tokens=tok, positions=pos, segment_ids=seg, loss_mask=(seg > 0).astype(np.int32)).items()}


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 128 under 32 keys a query: a document of 70 and one
    of 50 (both select) before padding, and one of 20 (keeps all) before one
    of 108."""
    return packed([[70, 50], [20, 108]], np.random.default_rng(3))


def seed_program(ref, sizes, pcfg, batch):
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat])
    assert sorted(ref.ref_name(p) for p, _ in flat) == sorted(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    _cfg, ref, sizes, pcfg = tiny
    return seed_program(ref, sizes, pcfg, batch)


def program_outputs(model, params, batch):
    return model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )


def program_objective(model, params, batch):
    logits, mods = program_outputs(model, params, batch)
    return trainer_mod.lm_loss_fn(logits, batch) + sown.collect_aux_losses(mods), mods


def layer_leaves(leaves, layer):
    return {n[len("moe."):]: a[layer] for n, a in leaves.items() if n.startswith("moe.")}


# ---------------------------------------------------------------- the selection


def scores_by_hand(qi, ki, w, seg):
    z = jnp.einsum("bjqd,bsd->bqjs", qi, ki, precision="highest")
    index = (w[..., None] * jnp.maximum(z, 0.0)).sum(2)
    index = jnp.where(index == 0.0, 0.0, index)
    s = index.shape[-1]
    vis = jnp.arange(s)[:, None] >= jnp.arange(s)[None]
    if seg is not None:
        vis = vis & (seg[:, :, None] == seg[:, None, :])
    return jnp.where(vis, index, -jnp.inf)


def top_k_by_hand(scores, k):
    vals, idx = jax.lax.top_k(scores, k)
    put = jax.vmap(jax.vmap(lambda row, i, val: row.at[i].set(val > -jnp.inf)))
    return put(jnp.zeros(scores.shape, bool), idx, vals)


@pytest.fixture(scope="module")
def indexer():
    b, heads, s, width = 2, 4, 256, 16
    keys = jax.random.split(jax.random.key(1), 3)
    qi = jax.random.normal(keys[0], (b, heads, s, width), jnp.float32)
    ki = jax.random.normal(keys[1], (b, s, width), jnp.float32)
    w = jax.random.normal(keys[2], (b, s, heads), jnp.float32)
    seg = jnp.asarray(np.stack([np.repeat([1, 2], [100, 156]), np.repeat([1, 0], [200, 56])]), jnp.int32)
    return qi, ki, w, seg


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
def test_index_scores_kernel_against_the_products_by_hand(indexer, segmented):
    qi, ki, w, seg = indexer
    seg = seg if segmented else None
    got = sparse_select.index_scores(qi, ki, w, None if seg is None else seg[:, None], 0, 256, block_q=64, block_k=128)
    want = scores_by_hand(qi, ki, w, seg)
    np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
    np.testing.assert_allclose(np.where(np.isneginf(want), 0, got), np.where(np.isneginf(want), 0, want), rtol=1e-5, atol=1e-5)
    # a later block of queries, by its offset
    part = sparse_select.index_scores(qi, ki, w, None if seg is None else seg[:, None], 128, 128, block_q=64, block_k=128)
    np.testing.assert_array_equal(part, got[:, 128:])


@pytest.mark.parametrize("ties", [False, True], ids=["apart", "tied"])
@pytest.mark.parametrize("k", [32, 100])
def test_selected_sets_are_lax_top_ks(indexer, k, ties):
    """Every query's set is ``jax.lax.top_k``'s of its visible scores (ties to
    the earlier key: scores rounded to halves tie in every row), inside its
    document, and holds min(k, visible) keys."""
    qi, ki, w, seg = indexer
    scores = sparse_select.index_scores(qi, ki, w, seg[:, None], 0, 256)
    if ties:
        scores = jnp.where(jnp.isneginf(scores), scores, jnp.round(scores * 2) / 2)
    got = sparse_select.selection_from(scores, sparse_select.topk_thresholds(scores, 0, k))
    np.testing.assert_array_equal(got, top_k_by_hand(scores, k))
    visible = np.asarray((scores > -jnp.inf).sum(-1))
    np.testing.assert_array_equal(np.asarray(got.sum(-1)), np.minimum(visible, k))
    assert (visible > k).any() and (visible < k).any()


def test_select_counts_its_pairs_and_leaves_no_row_off_k(indexer):
    qi, ki, w, seg = indexer
    mask, counts, lse, _thresholds = sparse_select.select(qi, ki, w, seg[:, None], 32)
    want = top_k_by_hand(scores_by_hand(qi, ki, w, seg), 32)
    np.testing.assert_array_equal(mask != 0, want)
    by_hand = jax.nn.logsumexp(jnp.where(want, scores_by_hand(qi, ki, w, seg), -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, by_hand, rtol=1e-5)
    np.testing.assert_allclose(sparse_select.index_lse(qi, ki, w, seg[:, None], mask), by_hand, rtol=1e-5)
    visible = int((scores_by_hand(qi, ki, w, seg) > -jnp.inf).sum())
    assert [int(c) for c in counts] == [int(want.sum()), visible, 0] and mask.dtype == jnp.int8


def heads_scores(q, k):
    return jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, q.shape[2] // k.shape[2], 2), precision="highest") / np.sqrt(q.shape[3])


def loss_written_whole(qi, ki, w, q, k, seg, keep, real):
    """The indexer's loss as the module states it, on explicit ``[S, S]`` arrays."""
    index = scores_by_hand(qi, ki, w, seg)
    target = jnp.where(keep, jax.nn.softmax(jnp.where(keep[:, None], heads_scores(q, k), -1e30), -1).mean(1), 0.0)
    logq = jax.nn.log_softmax(jnp.where(keep, index, -1e30), -1)
    kl = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-37)) - logq), 0.0).sum(-1)
    return (kl * real).sum() / real.sum()


@pytest.fixture(scope="module")
def heads():
    b, s, h, kh, d = 2, 256, 4, 2, 32
    return jax.random.normal(jax.random.key(3), (b, s, h, d)), jax.random.normal(jax.random.key(4), (b, s, kh, d))


@pytest.mark.parametrize("given", [False, True], ids=["softmax-here", "lse-given"])
@pytest.mark.parametrize("selected", [True, False], ids=["selected", "every-visible-key"])
def test_index_loss_and_its_gradient_against_autodiff(indexer, heads, monkeypatch, selected, given):
    """The one pass that gives the loss and the gradients of the indexer's
    three inputs against the same loss written whole and differentiated by
    jax: with the heads' log-sum-exp given (what the flash kernels keep: the
    ``index_loss`` kernel, interpreted, tiles of 32 x 128) and without (the
    blockwise form with its own softmax, four bands of keys)."""
    monkeypatch.setattr(sparse_select, "LOSS_BLOCK", 32)
    monkeypatch.setattr(sparse_select, "LOSS_TILE", (32, 128))
    qi, ki, w, seg = indexer
    q, k = heads
    real = seg > 0
    scores_all = scores_by_hand(qi, ki, w, seg)
    keep = top_k_by_hand(scores_all, 32) if selected else scores_all > -jnp.inf
    whole = lambda qi, ki, w: loss_written_whole(qi, ki, w, q, k, seg, keep, real)
    mask = keep.astype(jnp.int8) if selected else None
    lse = jax.nn.logsumexp(jnp.where(keep[:, None], heads_scores(q, k), -jnp.inf), axis=-1) if given else None
    one_pass = lambda qi, ki, w: sparse_select.index_loss(qi, ki, w, q, k, lse, mask, seg[:, None], real)
    assert count(jax.make_jaxpr(one_pass)(qi, ki, w).jaxpr)["index_loss"] == int(given)
    np.testing.assert_allclose(one_pass(qi, ki, w), whole(qi, ki, w), rtol=1e-5)
    got, want = jax.grad(one_pass, (0, 1, 2))(qi, ki, w), jax.grad(whole, (0, 1, 2))(qi, ki, w)
    for a, w_ in zip(got, want):
        np.testing.assert_allclose(a, w_, rtol=1e-4, atol=1e-5 * float(jnp.abs(w_).max()))
    assert all(g is None or float(jnp.abs(g).max()) == 0 for g in jax.grad(
        lambda q, k: sparse_select.index_loss(qi, ki, w, q, k, None, mask, seg[:, None], real), (0, 1))(q, k))


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("selected", [True, False], ids=["selected", "every-visible-key"])
def test_index_loss_kernel_over_several_blocks_and_tiles(indexer, heads, selected, segmented):
    """The kernel by itself at 32 x 64 tiles over rows of 256: eight blocks of
    queries against up to four tiles of keys each, so ``d ki`` sums across
    blocks and tiles, a tile above a block's diagonal computes nothing, the
    first queries of a document see fewer than the 32 keys a query keeps, and
    a tail of the row weighs nothing (padding where the rows are packed)."""
    qi, ki, w, seg = indexer
    q, k = heads
    real = seg > 0 if segmented else jnp.arange(256)[None] < jnp.asarray([[256], [200]])
    seg = seg if segmented else None
    scores_all = scores_by_hand(qi, ki, w, seg)
    keep = top_k_by_hand(scores_all, 32) if selected else scores_all > -jnp.inf
    assert (keep.sum(-1) < 32).any() and (keep.sum(-1) > 32).any() != selected
    weight = real / real.sum()
    lse = jax.nn.logsumexp(jnp.where(keep[:, None], heads_scores(q, k), -jnp.inf), axis=-1)
    lse_i = jax.nn.logsumexp(jnp.where(keep, scores_all, -jnp.inf), axis=-1)
    loss, *got = sparse_select._loss_kernel_pass(
        q, k, lse, qi, ki, w, lse_i, keep.astype(jnp.int8) if selected else None, None if seg is None else seg[:, None],
        weight, block_q=32, block_k=64,
    )
    whole = lambda qi, ki, w: loss_written_whole(qi, ki, w, q, k, seg, keep, real)
    np.testing.assert_allclose(loss, whole(qi, ki, w), rtol=1e-5)
    for a, w_ in zip(got, jax.grad(whole, (0, 1, 2))(qi, ki, w)):
        np.testing.assert_allclose(a, w_, rtol=1e-4, atol=1e-5 * float(jnp.abs(w_).max()))
    if not segmented:  # nothing reaches a query that weighs nothing
        assert float(jnp.abs(got[0][1, :, 200:]).max()) == 0 and float(jnp.abs(got[2][1, 200:]).max()) == 0


# ------------------------------------------------------- the kernels with a mask


def attention_by_hand(q, k, v, seg, sel):
    b, s, h, d = q.shape
    group = h // k.shape[2]
    sc = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, group, 2), precision="highest") / np.sqrt(d)
    m = jnp.tril(jnp.ones((s, s), bool))[None, None] & (sel[:, None] != 0)
    if seg is not None:
        m = m & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(m, sc, -1e30), -1) * m.any(-1, keepdims=True)
    return jnp.einsum("bhqs,bshd->bqhd", p, jnp.repeat(v, group, 2), precision="highest")


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
def test_flash_forward_and_fused_backward_take_a_selection(segmented):
    """Forward and the fused backward in the interpreter, 64 x 64 tiles over
    rows of 256, a selection that leaves one tile under the diagonal empty
    (the visit table drops it) against attention under an explicit mask."""
    b, s, h, kh, d = 2, 256, 4, 2, 128
    q, k, v = (jax.random.normal(key, (b, s, n, d), jnp.float32)
               for key, n in zip(jax.random.split(jax.random.key(0), 3), (h, kh, kh)))
    seg = jnp.asarray(np.stack([np.repeat([1, 2], [100, 156]), np.repeat([1, 0], [250, 6])]), jnp.int32) if segmented else None
    sel = jax.random.uniform(jax.random.key(5), (b, s, s)) < 0.3
    sel = sel.at[:, 128:, :64].set(False) | jnp.eye(s, dtype=bool)[None]
    assert backward_form(s, d) == "fused"
    flash = lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, selected=sel.astype(jnp.int8), block_q=64, block_k=64)
    hand = lambda q, k, v: attention_by_hand(q, k, v, seg, sel)
    np.testing.assert_allclose(flash(q, k, v), hand(q, k, v), rtol=1e-5, atol=1e-5)
    cot = jnp.cos(jnp.arange(b * s * h * d, dtype=jnp.float32)).reshape(b, s, h, d)
    got = jax.grad(lambda *a: (flash(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (hand(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for a, w_ in zip(got, want):
        np.testing.assert_allclose(a, w_, rtol=1e-4, atol=1e-4 * float(jnp.abs(w_).max()))


def test_the_cells_row_keeps_the_fused_backward():
    assert backward_form(32768, 128) == "fused" and backward_form(65536, 128) == "split"


# ------------------------------------------------------------------- the layers


def test_attention_layer_and_index_loss_against_the_reference(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(7), (2, S, sizes["d_model"]), jnp.float32)
    attn = jax.tree.map(lambda a: a[0], params["layers"]["layer"]["attn"])
    assert attn["wq"]["kernel"].shape == (64, 4, 32) and attn["index_q"]["kernel"].shape == (64, 4, 16)
    got, mods = transformer.Attention(pcfg).apply(
        {"params": attn}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )
    want, index_loss, pairs = reference.selected_attention(
        x, layer_leaves(leaves, 0), batch["positions"], batch["segment_ids"], sizes
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mods["intermediates"]["index_aux_loss"][0], index_loss, rtol=1e-5)
    selected, visible, off = (int(c) for c in mods["intermediates"]["sparse_counts"][0])
    assert (selected, visible, off) == (int(pairs[0]), int(pairs[1]), 0) and selected < visible


def test_rows_no_longer_than_topk_take_the_dense_path_bit_for_bit(tiny, batch, seeded):
    """``sparse_topk >= S``: every query keeps every visible key, nothing is
    selected, and the output is that of the same layer with no indexer."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(8), (2, S, sizes["d_model"]), jnp.float32)
    attn = jax.tree.map(lambda a: a[0], params["layers"]["layer"]["attn"])
    all_keys = dataclasses.replace(pcfg, sparse_topk=S)
    got, mods = transformer.Attention(all_keys).apply(
        {"params": attn}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )
    dense = dataclasses.replace(pcfg, sparse_topk=0, index_heads=0, index_head_dim=0)
    want = transformer.Attention(dense).apply(
        {"params": {k: v for k, v in attn.items() if not k.startswith("index_")}}, x, batch["positions"], batch["segment_ids"]
    )
    np.testing.assert_array_equal(got, want)
    assert "sparse_counts" not in mods["intermediates"] and float(mods["intermediates"]["index_aux_loss"][0]) > 0
    selecting = transformer.Attention(pcfg).apply({"params": attn}, x, batch["positions"], batch["segment_ids"])
    assert float(jnp.abs(selecting - want).max()) > 1e-4  # and 32 keys of up to 108 is another result


def test_softmax_router_against_the_reference(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, _params = seeded
    assert pcfg.router == "softmax" and pcfg.n_shared_experts == 0 and pcfg.top_k == 8
    xn = jax.random.normal(jax.random.key(8), (2, S, sizes["d_model"]), jnp.float32)
    router = leaves["moe.router"][1]
    sel, w = moe.softmax_route(jnp.einsum("bsd,de->bse", xn, router, precision="highest"), pcfg.top_k)
    sel_ref, w_ref = reference.route(xn, router, sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(w, w_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all 8 shares of a
    softmax-routed layer give are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert e // held == 8
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *shape))
            for i, (n, shape) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, S, d), jnp.float32)
    base = jax.tree.map(lambda a: a[0], params["layers"]["layer"]["moe"])
    total, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        y, mods = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share)).apply(
            {"params": mine}, xn, mutable=["intermediates"]
        )
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()}}
    uncut = dict(sizes, held=e, offset=0)
    want, slots = reference.routed_part(xn, w, *reference.route(xn, w["router"], uncut), uncut)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=1e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * S * sizes["top_k"]  # every slot on exactly one share


# ------------------------------------------------- the whole model and its step


def test_logits_loss_index_loss_and_slots(tiny, batch, seeded):
    _cfg, _ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    logits, mods = program_outputs(model, params, batch)
    np.testing.assert_allclose(logits, reference.logits_of(leaves, batch, sizes), rtol=1e-4, atol=2e-5)
    _, parts = reference.losses(leaves, batch, sizes)
    np.testing.assert_allclose(trainer_mod.lm_loss_fn(logits, batch), parts["main"], rtol=1e-5)
    np.testing.assert_allclose(sown.collect_aux_losses(mods), parts["index"], rtol=1e-5)
    counters = sown.step_counters(mods)
    assert float(counters["moe_slots"]) == float(parts["slots"]) > 0 and float(counters["moe_slots_dropped"]) == 0
    np.testing.assert_allclose(counters["index_loss"], parts["index"], rtol=1e-5)
    np.testing.assert_allclose(counters["sparse_selected_share"], parts["pairs"][0] / parts["pairs"][1], rtol=1e-6)
    assert float(counters["sparse_rows_off_k"]) == 0 and 0.3 < float(counters["sparse_selected_share"]) < 0.9


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's; then two AdamW steps on both sides from those
    gradients. The indexer's leaves are trained (by its loss alone), and the
    cross entropy's gradient does not reach them."""
    cfg, ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    hp = cfg[KIND]["optimizer"]

    def worst_gap(got, want):
        got = {n: np.asarray(a).reshape(want[n].shape) for n, a in ref.named_leaves(got).items()}
        assert set(got) == set(want)
        norms = {n: float(np.linalg.norm(a)) for n, a in want.items()}
        assert all(v > 0 for v in norms.values())
        floor = float(np.median(list(norms.values())))
        return max((float(np.linalg.norm(got[n] - want[n])) / max(norms[n], floor), n) for n in want)

    p, r, gp, gr = params, leaves, [], []
    for _ in range(2):
        gp.append(jax.grad(lambda q: program_objective(model, q, batch)[0])(p))
        gr.append(jax.grad(lambda q: reference.losses(q, batch, sizes)[0])(r))
        assert worst_gap(gp[-1], gr[-1])[0] < 2e-4, worst_gap(gp[-1], gr[-1])
        p = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), params, *gp)
        r = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), leaves, *gr)
    change = lambda new, old: jax.tree.map(lambda a, b: a - b, new, old)
    assert worst_gap(change(p, params), change(r, leaves))[0] < 1e-3  # AdamW divides by the gradient's size
    entropy_only = jax.grad(lambda q: trainer_mod.lm_loss_fn(program_outputs(model, q, batch)[0], batch))(params)
    attn = entropy_only["layers"]["layer"]["attn"]
    assert all(float(jnp.abs(a).max()) == 0 for n in attn if n.startswith("index_") for a in jax.tree.leaves(attn[n]))
    assert float(jnp.abs(attn["wq"]["kernel"]).max()) > 0


# ------------------------------------------------------- what a replay keeps


def selecting_decoder(**fields):
    """Two scanned selected-key layers whose heads the flash kernels tile
    (width 128), one row of 256 under 64 keys a query, and their objective."""
    cfg = transformer.DecoderConfig(**{**dict(
        vocab_size=64, d_model=128, n_layers=2, n_heads=2, n_kv_heads=1, head_width=128, d_ff=64, max_seq_len=256,
        dtype=jnp.float32, sparse_topk=64, index_heads=2, index_head_dim=16,
    ), **fields})
    tokens = jnp.asarray(np.arange(256)[None] % 64, jnp.int32)
    params = nn.meta.unbox(transformer.Decoder(cfg).init(jax.random.key(0), tokens)["params"])

    def fn(p):
        logits, mods = transformer.Decoder(cfg).apply({"params": p}, tokens, mutable=["intermediates"])
        return jnp.square(logits).mean() + sown.collect_aux_losses(mods)

    return jax.value_and_grad(fn), params


@pytest.mark.parametrize("policy", ["nothing", "dots"])
def test_recomputed_layer_selects_once_and_keeps_the_thresholds(tiny, batch, seeded, policy, monkeypatch):
    """The thresholds and the indexer's gradients are named residuals that
    every recompute policy keeps, and the loss and every gradient are the
    unrecomputed model's. Where the flash kernels run (heads of 128, the
    interpreter told that the shape tiles) a layer's body launches
    ``index_scores`` twice a step and selects once: the forward takes
    thresholds and mask from one block of scores, the kernels' backward rule
    makes the mask again from the thresholds (``reselect``), and nothing the
    backward reads needs the forward's mask, so the replay drops the whole
    selection; recomputed or not. The loss is the ``index_loss`` kernel there,
    and the backward holds no call of it. The XLA attention (the tiny model's
    heads of 32) keeps the mask as a residual of its own: unrecomputed it
    selects once and has one pass, and its replay selects again."""
    _cfg, _ref, _sizes, pcfg = tiny
    _leaves, model, params = seeded
    assert set(sparse_select.SPARSE_RESIDUALS) <= set(transformer.KEPT_RESIDUALS)
    remat = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy=policy))
    fn = jax.value_and_grad(lambda p: program_objective(remat, p, batch)[0])
    counted = count(jax.make_jaxpr(fn)(params).jaxpr)
    assert counted["sparse_select"] == 2 and counted["index_scores"] == 2
    assert counted["name:sparse_threshold"] == 0 and counted["name:sparse_index_grads"] == 3  # no reader of the thresholds there
    plain = count(jax.make_jaxpr(jax.value_and_grad(lambda p: program_objective(model, p, batch)[0]))(params).jaxpr)
    assert plain["sparse_select"] == 1 and plain["index_scores"] == 1
    got, want = jax.jit(fn)(params), jax.jit(jax.value_and_grad(lambda p: program_objective(model, p, batch)[0]))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)

    monkeypatch.setattr(transformer, "flash_tileable", lambda *a: None)
    launches = dict(index_scores=2, sparse_select=1, index_loss=1, flash_fwd=1, flash_bwd=1)
    (fn, params), (plain, _) = selecting_decoder(remat=True, remat_policy=policy), selecting_decoder(remat=False)
    counted = count(jax.make_jaxpr(fn)(params).jaxpr)  # the scan's body once forward, once backward
    assert kernels(counted) == launches
    assert counted["name:sparse_threshold"] == 1 and counted["name:sparse_index_grads"] == 3
    assert kernels(count(jax.make_jaxpr(plain)(params).jaxpr)) == launches
    got, want = fn(params), plain(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_attention_dispatch_hands_the_selection_to_the_flash_kernels(monkeypatch):
    """Where the shape tiles (here: the interpreter told that it does), the
    dispatch gives the kernels the mask and records ``selected``."""
    from maggy_tpu import telemetry

    cfg = transformer.DecoderConfig(
        vocab_size=64, d_model=128, n_layers=1, n_heads=2, n_kv_heads=1, head_width=128, d_ff=64, max_seq_len=256,
        dtype=jnp.float32, sparse_topk=64, index_heads=2, index_head_dim=16, qk_norm=True,
    )
    x = jax.random.normal(jax.random.key(2), (1, 256, 128), jnp.float32)
    pos = jnp.arange(256, dtype=jnp.int32)[None]
    layer = transformer.Attention(cfg)
    params = layer.init(jax.random.key(0), x, pos)
    want = layer.apply(params, x, pos)  # the XLA path under the same mask
    monkeypatch.setattr(transformer, "flash_tileable", lambda *a: None)
    events = []

    class Recorder(telemetry.Telemetry):
        def event(self, name, **attrs):
            events.append((name, attrs))
            super().event(name, **attrs)

    with telemetry.current(Recorder(worker="t")):
        jaxpr = jax.make_jaxpr(lambda p: layer.apply(p, x, pos))(params)
        got = layer.apply(params, x, pos)
    assert count(jaxpr.jaxpr)["flash_fwd"] == 1
    kernel = [a for n, a in events if n == "attention.kernel"][-1]
    assert kernel["kernel"] == "flash" and kernel["selected"] == 64 and kernel["backward"] == "fused"
    assert kernel["index_loss"] == "kernel" and count(jaxpr.jaxpr)["index_loss"] == 1
    assert kernel["index_passes"] == 2  # the forward's here, the backward's in the gradient below
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the indexer's loss reads the kernels' log-sum-exp there, and its own softmax on the XLA path
    objective = lambda p: sum(jax.tree.leaves(layer.apply(p, x, pos, mutable=["intermediates"])[1]["intermediates"]["index_aux_loss"]))
    with_lse = jax.value_and_grad(objective)(params)
    monkeypatch.undo()
    with telemetry.current(Recorder(worker="t")):
        by_softmax = jax.value_and_grad(objective)(params)
    kernel = [a for n, a in events if n == "attention.kernel"][-1]
    assert kernel["kernel"] == "xla_dense" and kernel["selected"] == 64 and kernel["index_loss"] == "blockwise"
    assert kernel["index_passes"] == 1
    np.testing.assert_allclose(with_lse[0], by_softmax[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(with_lse[1]), jax.tree.leaves(by_softmax[1])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6 * float(jnp.abs(b).max()) + 1e-9)


def test_trainer_step_reports_the_selection_and_fit_publishes_the_gauges(tiny, batch):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name.startswith("sparse."):
                seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    with telemetry.current(Recorder(worker="t")):
        tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    assert {"loss", "aux_loss", "index_loss", "sparse_selected_share", "sparse_rows_off_k", "moe_slots"} <= set(out)
    assert out["sparse_rows_off_k"] == 0 and out["index_loss"] == out["aux_loss"] > 0
    assert abs(out["total_loss"] - out["loss"] - out["index_loss"]) < 1e-5
    assert seen == {"sparse.index_loss": out["index_loss"], "sparse.selected_share": out["sparse_selected_share"],
                    "sparse.rows_off_k": 0.0}


@pytest.mark.parametrize("bad", [
    dict(decode=True), dict(index_heads=0), dict(index_head_dim=15), dict(attention_fn=transformer.default_attention),
    dict(kv_lora_rank=32, q_lora_rank=32, qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128, n_kv_heads=4),
    dict(router="sparsemax"), dict(select_bias_std=0.1),
])
def test_config_refuses_what_the_layers_cannot_do(tiny, bad):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError):
        dataclasses.replace(pcfg, **bad)


def test_dense_decoder_scans_selected_key_layers_too():
    """``Decoder`` (no experts) takes ``sparse_topk`` under its scan: the
    indexer's loss and the counts come back a layer each."""
    cfg = transformer.DecoderConfig(
        vocab_size=64, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, head_width=32, d_ff=64, max_seq_len=S,
        dtype=jnp.float32, sparse_topk=16, index_heads=2, index_head_dim=16,
    )
    model = transformer.Decoder(cfg)
    tokens = jnp.asarray(np.arange(2 * S).reshape(2, S) % 64, jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.key(0), tokens)["params"])
    assert params["layers"]["layer"]["attn"]["wq"]["kernel"].shape == (3, 64, 4, 32)
    _logits, mods = model.apply({"params": params}, tokens, mutable=["intermediates"])
    counters = sown.step_counters(mods)
    assert mods["intermediates"]["layers"]["layer"]["attn"]["index_aux_loss"][0].shape == (3,)
    assert float(counters["sparse_rows_off_k"]) == 0 and float(counters["index_loss"]) > 0
    want = sum(min(t + 1, 16) for t in range(S)) / (S * (S + 1) / 2)
    np.testing.assert_allclose(counters["sparse_selected_share"], want, rtol=1e-6)


def test_selection_follows_its_batch_row_under_shard_map():
    """``sharded_flash_attention`` hands each shard its rows of the selection."""
    from maggy_tpu.ops.flash import sharded_flash_attention
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    b, s, h, kh, d = 2, 128, 2, 1, 128
    q, k, v = (jax.random.normal(key, (b, s, n, d), jnp.float32)
               for key, n in zip(jax.random.split(jax.random.key(0), 3), (h, kh, kh)))
    seg = jnp.asarray(np.stack([np.repeat([1, 2], [60, 68]), np.repeat([1, 0], [120, 8])]), jnp.int32)
    sel = (jax.random.uniform(jax.random.key(5), (b, s, s)) < 0.4) | jnp.eye(s, dtype=bool)[None]
    mesh = make_mesh(ShardingSpec(fsdp=2), jax.devices()[:2])
    got = sharded_flash_attention(q, k, v, mesh=mesh, segment_ids=seg, selected=sel.astype(jnp.int8), interpret=True)
    np.testing.assert_allclose(got, attention_by_hand(q, k, v, seg, sel), rtol=1e-5, atol=1e-5)
    plain = sharded_flash_attention(q, k, v, mesh=mesh, selected=sel.astype(jnp.int8), interpret=True)
    np.testing.assert_allclose(plain, attention_by_hand(q, k, v, None, sel), rtol=1e-5, atol=1e-5)
