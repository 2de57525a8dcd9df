"""The lfm2_moe family (gated short-convolution layers among grouped-query
attention with a per-head query/key norm, in one stack of three kinds of
layer, over the dropless expert share layer with no shared expert, a tied
head) against its plain reference ``benchmark/references/conv_moe.py``, on
seeded weights at small sizes with the published ratios
(``benchmark/checks/tiny.lfm2-24b-a2b.json``).

Both sides compute in float32 here, so what differs is the order of the sums:
tolerances are a few float32 roundings of the compared quantity's scale
(``TOL``), except where a note says otherwise. The chip run's comparison, in
bfloat16, is the cell's (``benchmark/kinds/train_packed_ref.py``).
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
from benchmark.references import conv_moe  # noqa: E402
from benchmark.references.decoder import adamw_apply  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

KIND = "train_packed_ref"
TOL = dict(rtol=2e-5, atol=2e-6)
CONV_TOL = dict(rtol=1e-4, atol=1e-8)  # the conv operator's outputs are of order 1e-5 at these weights
SEED = 13
CONV, ATTN = "conv", "full_attention"


def load(**over):
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.lfm2-24b-a2b.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(bench_run.merge(configs.load("benchmark/configs/lfm2-24b-a2b.json"), small), over)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=64)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def tiny():
    return load()


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 64: documents of uneven length (one of a single
    token, one of two: shorter than the convolution's reach) and padding."""
    rng = np.random.default_rng(3)
    docs = [[20, 1, 30, 2, 5], [40, 3, 14]]
    tok = rng.integers(1, 512, size=(2, 64), dtype=np.int32)
    pos, seg = np.zeros((2, 64), np.int32), np.zeros((2, 64), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
        tok[r, at:] = 0
    return {k: jnp.asarray(v) for k, v in
            dict(tokens=tok, positions=pos, segment_ids=seg, loss_mask=(seg > 0).astype(np.int32)).items()}


def seed_program(ref, sizes, pcfg, batch):
    """The reference's leaves from the seed, and the same numbers in the
    program's tree (as the benchmark's kind puts them there)."""
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(
        treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat]
    )
    assert sorted(ref.ref_name(p) for p, _ in flat) == sorted(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    _cfg, ref, sizes, pcfg = tiny
    return seed_program(ref, sizes, pcfg, batch)


def group(leaves, prefix, layer=None):
    out = conv_moe._group(leaves, prefix)
    return out if layer is None else {n: a[layer] for n, a in out.items()}


def sub(params, *keys):
    for k in keys:
        params = params[k]
    return params


def program_outputs(model, params, batch):
    return model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )


def program_loss(model, params, batch):
    logits, mods = program_outputs(model, params, batch)
    return trainer_mod.lm_loss_fn(logits, batch), mods


# ------------------------------------------------------------ the layer pattern


def test_the_published_layer_types_parse_into_dense_periods_and_rest():
    cfg = configs.load("benchmark/configs/lfm2-24b-a2b.json")
    published = cfg["layer_types"]["published"]
    assert len(published) == 40 and published.count(ATTN) == 10
    period, n, over = moe.layer_plan(published, cfg["num_dense_layers"]["published"])
    assert published[:2] == [CONV, CONV]
    assert (period, n, over) == ((ATTN, CONV, CONV, CONV), 9, (ATTN, CONV))
    assert (list(period), n, list(over)) == tuple(conv_moe.plan(published, 2))  # the reference's own parse
    kept = cfg["layer_types"][KIND]
    assert kept == published[1:6] and moe.layer_plan(kept, 1) == ((ATTN, CONV, CONV, CONV), 1, ())
    assert moe.layer_plan((ATTN,) * 5, 1) == ((ATTN,), 4, ())  # one kind of layer: the scan's body is a layer


def test_two_periods_scanned_agree_with_the_same_layers_unrolled(batch):
    """Nine layers (one dense, two whole periods) and a layer over: the scan
    of periods, the unrolled stack and the reference give the same logits."""
    kinds = [CONV] + [ATTN, CONV, CONV, CONV] * 2 + [ATTN]
    _cfg, ref, sizes, pcfg = load(layer_types={KIND: kinds}, num_hidden_layers={KIND: len(kinds)})
    leaves, model, params = seed_program(ref, sizes, pcfg, batch)
    assert sub(params, "layers", "layer_1", "layer", "conv", "conv").shape == (2, 3, 64)
    assert "tail_0" in params and "layer_4" not in params["layers"]
    scanned, mods = program_outputs(model, params, batch)
    want = conv_moe.logits_of(leaves, batch, sizes)
    np.testing.assert_allclose(scanned, want, rtol=1e-4, atol=2e-5)

    unrolled = {k: v for k, v in params.items() if k != "layers" and k != "tail_0"}
    for i in range(8):
        unrolled[f"layers_{i}"] = jax.tree.map(lambda a: a[i // 4], params["layers"][f"layer_{i % 4}"])
    unrolled["layers_8"] = params["tail_0"]
    flat = moe.MoEDecoder(dataclasses.replace(pcfg, scan_layers=False))
    got, mods_flat = program_outputs(flat, unrolled, batch)
    np.testing.assert_allclose(got, scanned, rtol=1e-5, atol=1e-6)
    slots = [float(sown.step_counters(m)["moe_slots"]) for m in (mods, mods_flat)]
    assert slots[0] == slots[1] > 0


def test_one_period_scanned_and_unrolled_agree_with_recomputation(tiny, batch, seeded):
    """The cell's own stack (one period): scanned with every layer recomputed
    on its own, and unrolled with nothing recomputed, give one loss and one
    gradient."""
    _cfg, _ref, _sizes, pcfg = tiny
    _leaves, model, params = seeded
    remat = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    unrolled = {k: v for k, v in params.items() if k != "layers"}
    for i in range(4):
        unrolled[f"layers_{i}"] = jax.tree.map(lambda a: a[0], params["layers"][f"layer_{i}"])
    flat = moe.MoEDecoder(dataclasses.replace(pcfg, scan_layers=False))
    (l0, _), g0 = jax.value_and_grad(lambda p: program_loss(remat, p, batch), has_aux=True)(params)
    (l1, _), g1 = jax.value_and_grad(lambda p: program_loss(flat, p, batch), has_aux=True)(unrolled)
    (l2, _), g2 = jax.value_and_grad(lambda p: program_loss(model, p, batch), has_aux=True)(params)
    np.testing.assert_allclose([l0, l1], [l2, l2], rtol=1e-6)
    np.testing.assert_allclose(g0["layers"]["layer_2"]["layer"]["conv"]["conv"][0],
                               g1["layers_2"]["layer"]["conv"]["conv"], rtol=1e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


# ------------------------------------------------------------ the operators


def test_short_conv_output_against_the_reference(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(5), (2, 64, sizes["d_model"]), jnp.float32)
    got, mods = transformer.ShortConv(pcfg).apply(
        {"params": sub(params, "dense_0", "layer", "conv")}, x, batch["positions"], batch["segment_ids"],
        mutable=["intermediates"],
    )
    want = conv_moe.short_conv(x, group(leaves, "d0"), batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, **CONV_TOL)
    # taps zeroed: two a document start (one where the document, or the row, has one token left)
    masked, of = (int(v) for v in mods["intermediates"]["taps_masked"][0])
    seg = np.asarray(batch["segment_ids"])
    by_hand = sum(int((np.arange(64) < j).sum()) * 2 + int((seg[:, j:] != seg[:, :-j]).sum()) for j in (1, 2))
    assert (masked, of) == (by_hand, 2 * 64 * 3) and masked > 0


def test_a_document_in_a_packed_row_gives_what_it_gives_alone(tiny, batch, seeded):
    """Output and gradient of the conv operator for the third document of row
    0 (30 tokens from position 21), packed between others and alone."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    conv = {"params": sub(params, "layers", "layer_1", "layer", "conv")}
    conv = jax.tree.map(lambda a: a[0], conv)
    x = jax.random.normal(jax.random.key(6), (2, 64, sizes["d_model"]), jnp.float32)
    lo, n = 21, 30
    cot = jnp.cos(jnp.arange(n * sizes["d_model"], dtype=jnp.float32)).reshape(n, -1)

    def packed(p, x):
        y = transformer.ShortConv(pcfg).apply(p, x, batch["positions"], batch["segment_ids"])
        return (y[0, lo:lo + n] * cot).sum(), y[0, lo:lo + n]

    def alone(p, doc):
        ids = jnp.ones((1, n), jnp.int32)
        y = transformer.ShortConv(pcfg).apply(p, doc[None], ids, ids)[0]
        return (y * cot).sum(), y

    (_, got), (gp, gx) = jax.value_and_grad(packed, argnums=(0, 1), has_aux=True)(conv, x)
    (_, want), (wp, wx) = jax.value_and_grad(alone, argnums=(0, 1), has_aux=True)(conv, x[0, lo:lo + n])
    np.testing.assert_allclose(got, want, **CONV_TOL)
    np.testing.assert_allclose(gx[0, lo:lo + n], wx, **CONV_TOL)
    assert float(jnp.abs(gx[0, :lo]).max()) == 0 == float(jnp.abs(gx[0, lo + n:]).max())  # nothing crosses a boundary
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(wp)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7 * float(jnp.abs(b).max()))
    # and with no segment ids a row is one document: the tap does reach back
    y = transformer.ShortConv(pcfg).apply(conv, x, batch["positions"])
    assert float(jnp.abs(y[0, lo] - got[0]).max()) > 0.1 * float(jnp.abs(got[0]).max())


def test_attention_with_the_per_head_norm_against_the_reference(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(7), (2, 64, sizes["d_model"]), jnp.float32)
    attn = jax.tree.map(lambda a: a[0], sub(params, "layers", "layer_0", "layer", "attn"))
    assert attn["q_norm"]["scale"].shape == (sizes["head_dim"],)
    got = transformer.Attention(pcfg).apply({"params": attn}, x, batch["positions"], batch["segment_ids"])
    want = conv_moe.attention(x, group(leaves, "p0", 0), batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    plain = transformer.Attention(dataclasses.replace(pcfg, qk_norm=False)).apply(
        {"params": {k: v for k, v in attn.items() if not k.endswith("_norm")}}, x, batch["positions"], batch["segment_ids"]
    )
    assert float(jnp.abs(plain - got).max()) > 1e-3  # the norm is not a no-op


def test_router_normalises_by_the_configurations_constant(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, _params = seeded
    assert pcfg.route_norm_eps == sizes["route_eps"] == 1e-6 and pcfg.n_shared_experts == 0
    xn = jax.random.normal(jax.random.key(8), (2, 64, sizes["d_model"]), jnp.float32)
    np.testing.assert_array_equal(pcfg.select_bias(), conv_moe.select_bias(sizes))
    bias = jnp.asarray(pcfg.select_bias()[1])
    router = leaves["p1.router"][0]
    logits = jnp.einsum("bsd,de->bse", xn, router, precision="highest")
    sel, w = moe.sigmoid_route(logits, bias, pcfg.top_k, pcfg.routed_scaling, pcfg.route_norm_eps)
    sel_ref, w_ref = conv_moe.route(xn, router, bias, sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(w, w_ref, **TOL)
    tiny_scores = jnp.full((1, sizes["n_experts"]), -20.0)  # chosen scores of 2e-9: the constant shows
    _, small = moe.sigmoid_route(tiny_scores, None, pcfg.top_k, 1.0, 1e-6)
    _, glm = moe.sigmoid_route(tiny_scores, None, pcfg.top_k, 1.0)
    assert float(small.sum()) < 0.01 and abs(float(glm.sum()) - 1.0) < 1e-5


def test_shares_add_up_to_the_uncut_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all 8 shares give (there
    is no shared expert to count once) are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert e // held == 8
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *s))
            for i, (n, s) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, 64, d), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    base = jax.tree.map(lambda a: a[0], sub(params, "layers", "layer_0", "layer", "moe"))
    assert "shared" not in base
    total, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        y, mods = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share)).apply(
            {"params": mine}, xn, bias, mutable=["intermediates"]
        )
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()}}
    want, slots = conv_moe.expert_layer(xn, w, bias, dict(sizes, held=e, offset=0))
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=1e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * 64 * sizes["top_k"]  # every slot on exactly one share


# ------------------------------------------------- the whole model and its step


def test_logits_loss_and_slots(tiny, batch, seeded):
    _cfg, _ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    logits, mods = program_outputs(model, params, batch)
    np.testing.assert_allclose(logits, conv_moe.logits_of(leaves, batch, sizes), rtol=1e-4, atol=2e-5)
    want, parts = conv_moe.losses(leaves, batch, sizes)
    np.testing.assert_allclose(trainer_mod.lm_loss_fn(logits, batch), want, rtol=1e-5)
    counters = sown.step_counters(mods)
    assert float(counters["moe_slots"]) == float(parts["slots"]) > 0 and float(counters["moe_slots_dropped"]) == 0
    assert 0 < float(counters["conv_taps_masked_share"]) < 0.1


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's. 1e-4: five layers' worth of float32 sums in each
    direction. Then two AdamW steps on both sides from those gradients."""
    cfg, ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    hp = cfg[KIND]["optimizer"]

    def worst_gap(got, want):
        got = {n: np.asarray(a).reshape(want[n].shape) for n, a in ref.named_leaves(got).items()}
        assert set(got) == set(want)
        norms = {n: float(np.linalg.norm(a)) for n, a in want.items()}
        assert all(v > 0 for v in norms.values())  # every leaf is trained: taps, both inner norms, the router
        floor = float(np.median(list(norms.values())))
        return max((float(np.linalg.norm(got[n] - want[n])) / max(norms[n], floor), n) for n in want)

    p, r, gp, gr = params, leaves, [], []
    for _ in range(2):
        gp.append(jax.grad(lambda q: program_loss(model, q, batch)[0])(p))
        gr.append(jax.grad(lambda q: conv_moe.losses(q, batch, sizes)[0])(r))
        assert worst_gap(gp[-1], gr[-1])[0] < 1e-4, worst_gap(gp[-1], gr[-1])
        p = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), params, *gp)
        r = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), leaves, *gr)
    change = lambda new, old: jax.tree.map(lambda a, b: a - b, new, old)
    assert worst_gap(change(p, params), change(r, leaves))[0] < 1e-3  # AdamW divides by the gradient's size


def test_tied_head_puts_its_gradient_into_the_embedding(tiny, batch, seeded):
    """``MoEDecoder`` with ``tie_embeddings`` has no ``lm_head`` leaf, and the
    embedding's gradient is the lookup's plus the head's: rows of tokens that
    never occur in the batch get the head's part alone, which is not zero."""
    _cfg, _ref, _sizes, pcfg = tiny
    _leaves, model, params = seeded
    assert "lm_head" not in params and pcfg.tie_embeddings
    g = jax.grad(lambda p: program_loss(model, p, batch)[0])(params)["embedding"]
    absent = np.setdiff1d(np.arange(pcfg.vocab_size), np.asarray(batch["tokens"]))
    assert len(absent) > 300 and float(jnp.abs(g[absent]).min(axis=0).max()) > 0
    untied = moe.MoEDecoder(dataclasses.replace(pcfg, tie_embeddings=False))
    shapes = jax.eval_shape(lambda: untied.init(jax.random.key(0), batch["tokens"]))["params"]
    assert "lm_head" in shapes
    with pytest.raises(ValueError):
        moe.MoEDecoder(dataclasses.replace(pcfg, mtp_depth=1)).init(jax.random.key(0), batch["tokens"])


def test_trainer_step_reports_the_taps_and_fit_publishes_the_gauge(tiny, batch):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = []

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name == "conv.taps_masked_share":
                seen.append(value)
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    with telemetry.current(Recorder(worker="t")):
        tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    assert {"loss", "moe_slots", "moe_slots_dropped", "conv_taps_masked_share"} <= set(out) and "mtp_loss" not in out
    seg = np.asarray(batch["segment_ids"])
    by_hand = sum(2 * j + int((seg[:, j:] != seg[:, :-j]).sum()) for j in (1, 2)) / (2 * 64 * 3)
    np.testing.assert_allclose(out["conv_taps_masked_share"], by_hand, rtol=1e-6)  # the same in all four conv layers
    # gauged from every step's output (the same batch twice: the same share twice)
    assert seen == [out["conv_taps_masked_share"]] * 2 and out["moe_slots_dropped"] == 0


@pytest.mark.parametrize("bad", [
    dict(decode=True), dict(layer_types=(CONV,) * 4), dict(layer_types=(CONV, ATTN, "window", CONV, CONV)),
])
def test_config_refuses_what_the_layers_cannot_do(tiny, bad):
    """A conv layer has no decode state; layer_types names every layer one of
    the kinds there are."""
    with pytest.raises(ValueError, match="decode state|layer_types"):
        dataclasses.replace(tiny[3], **bad)


def test_dense_decoder_takes_layer_types_unrolled_only():
    cfg = transformer.DecoderConfig.tiny(layer_types=(CONV, ATTN), scan_layers=False)
    tokens = jnp.ones((1, 16), jnp.int32)
    params = transformer.Decoder(cfg).init(jax.random.key(0), tokens)["params"]
    assert "conv" in params["layers_0"]["layer"] and "attn" in params["layers_1"]["layer"]
    with pytest.raises(ValueError, match="one kind"):
        transformer.Decoder(dataclasses.replace(cfg, scan_layers=True)).init(jax.random.key(0), tokens)
    assert transformer.DecoderConfig.tiny().layer_kinds() == (ATTN, ATTN)  # the default: attention everywhere


def test_conv_weights_carry_the_axes_of_the_projections_beside_them(tiny, batch):
    from maggy_tpu.parallel import sharding as shd
    from maggy_tpu.parallel.spec import AXIS_FSDP, AXIS_TENSOR

    boxed = jax.eval_shape(lambda: moe.MoEDecoder(tiny[3]).init(jax.random.key(0), batch["tokens"]))["params"]
    conv, attn = boxed["dense_0"]["layer"]["conv"], boxed["layers"]["layer_0"]["layer"]["attn"]
    axes = lambda box: shd.logical_to_mesh_axes(box.names)
    assert axes(conv["in_proj"]["kernel"]) == (AXIS_FSDP, None, AXIS_TENSOR)
    assert axes(conv["conv"]) == (None, AXIS_TENSOR)
    assert axes(conv["out_proj"]["kernel"]) == (AXIS_TENSOR, AXIS_FSDP)
    assert axes(attn["wq"]["kernel"])[1:] == (AXIS_FSDP, AXIS_TENSOR, None)  # [periods, embed, heads, width]
    assert axes(attn["wo"]["kernel"])[1:] == (AXIS_TENSOR, None, AXIS_FSDP)
