"""The glm4_moe_lite family (latent attention, a sigmoid-routed dropless expert
share layer beside a shared expert, a leading dense layer, multi-token
prediction) against its plain reference ``benchmark/references/mla_moe.py``,
on seeded weights at small sizes with the published ratios
(``benchmark/checks/tiny.glm-4.7-flash.json``).

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
from benchmark.references import mla_moe  # noqa: E402
from maggy_tpu.models import moe, transformer  # noqa: E402
from maggy_tpu.ops.flash import flash_attention  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

KIND = "train_packed_ref"
# float32 sums in another order: a few roundings (1.2e-7 each) of the
# quantity's own scale, over reductions a few hundred long
TOL = dict(rtol=2e-5, atol=2e-6)
SEED = 11


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.glm-4.7-flash.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load("benchmark/configs/glm-4.7-flash.json"), small)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=64)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 64: documents of uneven length and some padding."""
    rng = np.random.default_rng(3)
    docs = [[20, 9, 30, 5], [40, 3, 14]]
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


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    """The reference's leaves from the seed, and the same numbers in the
    program's tree (as the benchmark's kind puts them there)."""
    _cfg, ref, sizes, pcfg = tiny
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"]
    shapes = nn.meta.unbox(shapes)  # the logical-axis boxes: placement only
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(
        treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat]
    )
    assert {ref.ref_name(p) for p, _ in flat} == set(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


def moe_weights(leaves, layer=0):
    return {n: leaves[f"moe.{n}"][layer] for n in mla_moe.MOE_LEAVES}


def sub(params, *keys):
    for k in keys:
        params = params[k]
    return params


def test_latent_attention_output(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(5), (2, 64, sizes["d_model"]), jnp.float32)
    got = transformer.LatentAttention(pcfg).apply(
        {"params": jax.tree.map(lambda a: a[0], sub(params, "layers", "layer", "attn"))},
        x, batch["positions"], batch["segment_ids"],
    )
    want = mla_moe.latent_attention(x, moe_weights(leaves), batch["positions"], batch["segment_ids"], sizes, None)
    np.testing.assert_allclose(got, want, **TOL)


def test_router_choices_and_weights(tiny, seeded):
    """The same experts chosen for every token (no near-tie flips at this
    seed: both sides score in float32) and the same weights on them."""
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, _params = seeded
    xn = jax.random.normal(jax.random.key(6), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    np.testing.assert_array_equal(pcfg.select_bias(), mla_moe.select_bias(sizes))
    logits = jnp.einsum("bsd,de->bse", xn, leaves["moe.router"][0], precision="highest")
    sel, w = moe.sigmoid_route(logits, bias, pcfg.top_k, pcfg.routed_scaling)
    sel_ref, w_ref = mla_moe.route(xn, leaves["moe.router"][0], bias, sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(w, w_ref, **TOL)
    np.testing.assert_allclose(w.sum(-1), sizes["routed_scaling"], rtol=1e-5)  # normalised over the chosen, then scaled


def block_params(params):
    return jax.tree.map(lambda a: a[0], sub(params, "layers", "layer", "moe"))


def test_expert_layer_output_and_counters(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    xn = jax.random.normal(jax.random.key(7), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    got, mods = moe.ExpertShareBlock(pcfg).apply(
        {"params": block_params(params)}, xn, bias, mutable=["intermediates"]
    )
    want, slots = mla_moe.expert_layer(xn, moe_weights(leaves), bias, sizes)
    np.testing.assert_allclose(got, want, **TOL)
    load = mods["intermediates"]["expert_load"][0]
    assert load.shape == (sizes["held"],) and int(load.sum()) == int(slots) > 0
    assert int(mods["intermediates"]["slots_dropped"][0]) == 0


def test_shares_add_up_to_the_uncut_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all the shares give, with
    the shared expert counted once, are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held = sizes["n_experts"], sizes["held"]
    key = jax.random.key(8)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *s))
            for i, (n, s) in enumerate({"gate": (64, 48), "up": (64, 48), "down": (48, 64)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    base = block_params(params)
    total, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        y, mods = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share)).apply(
            {"params": mine}, xn, bias, mutable=["intermediates"]
        )
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
    shared = moe.MLPBlock(dataclasses.replace(pcfg, d_ff=sizes["moe_d_ff"])).apply({"params": base["shared"]}, xn)
    uncut = dict(sizes, held=e, offset=0)
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()},
         **{f"shared_{n}": base["shared"][f"w_{n}"]["kernel"] for n in ("gate", "up", "down")}}
    want, slots = mla_moe.expert_layer(xn, w, bias, uncut)
    np.testing.assert_allclose(total - (e // held - 1) * shared, want, rtol=2e-5, atol=1e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * 64 * sizes["top_k"]  # every slot on exactly one share


def test_token_with_no_held_expert_gets_the_shared_expert_only(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    xn = jax.random.normal(jax.random.key(12), (1, 16, sizes["d_model"]), jnp.float32)
    bias = jnp.where(jnp.arange(sizes["n_experts"]) < sizes["held"], -10.0, 0.0)  # never a held expert
    base = block_params(params)
    y, mods = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias, mutable=["intermediates"])
    shared = moe.MLPBlock(dataclasses.replace(pcfg, d_ff=sizes["moe_d_ff"])).apply({"params": base["shared"]}, xn)
    np.testing.assert_allclose(y, shared, **TOL)
    assert int(mods["intermediates"]["expert_load"][0].sum()) == 0


def program_outputs(model, params, batch):
    logits, mods = model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"],
        mutable=["intermediates"],
    )
    return logits, mods


@pytest.mark.parametrize("head", ["main", "mtp"])
def test_logits_of_both_heads(tiny, batch, seeded, head):
    """Embedding-scale inputs through three layers: the logits are of order
    1, so the absolute tolerance is a few roundings of that."""
    _cfg, _ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    logits, mods = program_outputs(model, params, batch)
    want = mla_moe.logits_of(leaves, batch, sizes)
    got = logits if head == "main" else mods["intermediates"]["mtp_logits"][0]
    np.testing.assert_allclose(got, want[head == "mtp"], rtol=1e-4, atol=2e-5)


def program_loss(model, mtp_weight, params, batch):
    logits, mods = program_outputs(model, params, batch)
    main = trainer_mod.lm_loss_fn(logits, batch)
    mtp = trainer_mod.mtp_loss(mods, batch)
    return main + mtp_weight * mtp, (main, mtp, trainer_mod.expert_counters(mods))


def test_loss_parts_and_slots(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    total, (main, mtp, counters) = program_loss(model, pcfg.mtp_weight, params, batch)
    want, parts = mla_moe.losses(leaves, batch, sizes)
    np.testing.assert_allclose([total, main, mtp], [want, parts["main"], parts["mtp"]], rtol=1e-5)
    assert float(counters["moe_slots"]) == float(parts["slots"]) and float(counters["moe_slots_dropped"]) == 0
    assert float(counters["moe_load_max_over_mean"]) >= 1.0


def test_mtp_targets_stay_in_the_predictors_document(batch):
    """Targets two ahead count only where positions i, i+1 and i+2 share a
    document: a document of n tokens gives n - 2 of them."""
    logits = jnp.zeros((2, 64, 7))
    _, weight = trainer_mod._lm_loss_parts(logits, dict(batch, tokens=batch["tokens"] % 7), ahead=2)
    docs = [20, 9, 30, 5, 40, 3, 14]
    assert int(weight) == sum(n - 2 for n in docs)
    _, weight1 = trainer_mod._lm_loss_parts(logits, dict(batch, tokens=batch["tokens"] % 7))
    assert int(weight1) == sum(n - 1 for n in docs)


def test_gradient_of_every_leaf(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's (some gradients are all but zero). 1e-4: the loss's
    gradient passes three layers' worth of float32 sums in each direction."""
    _cfg, ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    got = jax.grad(lambda p: program_loss(model, pcfg.mtp_weight, p, batch)[0])(params)
    want = jax.grad(lambda p: mla_moe.losses(p, batch, sizes)[0])(leaves)
    got = {n: np.asarray(a).reshape(want[n].shape) for n, a in ref.named_leaves(got).items()}
    assert set(got) == set(want)
    norms = {n: float(np.linalg.norm(a)) for n, a in want.items()}
    floor = float(np.median(list(norms.values())))
    assert all(v > 0 for v in norms.values())  # every leaf is trained, the router and the MTP module too
    worst = max((float(np.linalg.norm(got[n] - want[n])) / max(norms[n], floor), n) for n in want)
    assert worst[0] < 1e-4, worst


def test_selection_bias_gets_no_gradient_and_moves_the_choice(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    logits = jax.random.normal(jax.random.key(2), (4, 16, sizes["n_experts"]))
    bias = jnp.zeros(sizes["n_experts"]).at[3].set(5.0)
    sel, w = moe.sigmoid_route(logits, bias, pcfg.top_k, 1.0)
    assert bool(jnp.all(jnp.any(sel == 3, axis=-1)))  # a large bias wins the selection
    g = jax.grad(lambda b: moe.sigmoid_route(logits, b, pcfg.top_k, 1.0)[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0  # and enters nothing else


# ------------------------------------------------------ the trainer's step


def test_trainer_step_reports_mtp_loss_and_counters(tiny, batch):
    import optax

    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
    host = {k: np.asarray(v) for k, v in batch.items()}
    state = tr.make_state(jax.random.key(0), host)
    state, out = tr.fit(state, iter([host] * 3), num_steps=3)
    assert {"loss", "mtp_loss", "total_loss", "moe_slots", "moe_slots_dropped", "moe_load_max_over_mean"} <= set(out)
    assert out["moe_slots_dropped"] == 0 and out["moe_slots"] > 0
    np.testing.assert_allclose(out["total_loss"], out["loss"] + pcfg.mtp_weight * out["mtp_loss"], rtol=1e-5)


def test_dense_decoder_step_is_untouched():
    """A model that sows nothing gets no new metric (the Mistral cell's step)."""
    import optax

    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    model = transformer.Decoder(transformer.DecoderConfig.tiny())
    tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
    host = {"tokens": np.ones((2, 16), np.int32)}
    state = tr.make_state(jax.random.key(0), host)
    _, out = tr.fit(state, iter([host]), num_steps=1)
    assert set(out) - {"steps_per_sec"} == {"loss", "aux_loss", "total_loss", "grad_norm", "step"}


@pytest.mark.parametrize("bad", [
    dict(v_head_dim=24), dict(q_lora_rank=0), dict(n_kv_heads=2), dict(decode=True),
    dict(experts_held=3), dict(expert_offset=4), dict(moe_d_ff=0), dict(n_dense_layers=3), dict(mtp_depth=2),
    dict(ablated=("attn",)),
])
def test_config_refuses_what_the_layers_cannot_do(tiny, bad):
    with pytest.raises(ValueError):
        dataclasses.replace(tiny[3], **bad)


# ------------------------------------------- the flash kernels at width 256


@pytest.mark.parametrize("packed", [False, True])
def test_flash_at_head_width_256_against_the_dense_path(packed):
    """The three kernels in the Pallas interpreter at the width latent
    attention gives them (192 + 64 = 256 = v), 128-row tiles, against
    ``default_attention``; packed rows mask across documents and skip tiles."""
    b, s, h, d = 2, 256, 2, 256
    q, k, v = (0.5 * jax.random.normal(jax.random.key(i), (b, s, h, d), jnp.float32) for i in range(3))
    seg = None
    if packed:
        seg = jnp.asarray(np.stack([np.repeat([1, 2, 3, 0], [100, 60, 90, 6]), np.repeat([1, 1, 2, 2], [128, 28, 50, 50])]))

    def run(fn, **kw):
        def f(q, k, v):
            out = fn(q, k, v, causal=True, segment_ids=seg, **kw)
            return jnp.sum(out * jnp.cos(jnp.arange(d))), out
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out, grads = run(flash_attention, block_q=128, block_k=128, interpret=True)
    want, want_grads = run(transformer.default_attention)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
