"""``sdar-30b-a3b-chat`` as a whole model against its plain reference
``benchmark/references/blockdiff_gqa_moe.py`` on seeded weights at small sizes
(``benchmark/checks/tiny.sdar-30b-a3b-chat.json``): the step's noise, the
noised stream's logits, the loss, every leaf's gradient, two optimizer steps
through ``Trainer.fit`` with the counters as gauges; the same noise at the
same step after a save and restore; the clean stream unchanged by what is
masked; the shares of all 8 chips add up to the uncut reference's layer on
the two-stream input; every other model's step takes no noise key and sows no
weights; the refusals. The kernels and the mask are
``test_sdar_attention.py``'s.

Both sides compute in float32 here, so what differs is the order of the sums.
Each tolerance says why. The chip run's comparison, in bfloat16, is the cell's
(``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, counts_sdar, run as bench_run, weights  # noqa: E402
from benchmark.references import blockdiff_gqa_moe as reference  # noqa: E402
from benchmark.references.decoder import adamw_apply  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

KIND = "train_packed_ref"
SEED = 17
S = 128
DOCS = [[61, 45], [19, 90, 13]]  # no length a multiple of 4; both rows end in padding


def load(**over):
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.sdar-30b-a3b-chat.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(bench_run.merge(configs.load("benchmark/configs/sdar-30b-a3b-chat.json"), small), over)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def tiny():
    return load()


def packed(docs, rng, s=S):
    tok = rng.integers(1, 512, size=(len(docs), s), dtype=np.int32)
    pos, seg = np.zeros((len(docs), s), np.int32), np.zeros((len(docs), s), np.int32)
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
    return packed(DOCS, np.random.default_rng(3))


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    _cfg, ref, sizes, pcfg = tiny
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat])
    assert sorted(ref.ref_name(p) for p, _ in flat) == sorted(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


def program_outputs(model, params, batch, step=0):
    return model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"],
        mutable=["intermediates"], **sown.step_inputs(model, step),
    )


def program_loss(model, params, batch, step=0):
    logits, mods = program_outputs(model, params, batch, step)
    return trainer_mod.model_loss(trainer_mod.lm_loss_fn, logits, mods, batch), (logits, mods)


# ------------------------------------------------------------- noise and layout


@pytest.mark.parametrize("step", [0, 1, 7])
def test_the_steps_noise_is_the_references(tiny, batch, step):
    _cfg, _ref, sizes, pcfg = tiny
    noised, weights_, counts = moe.block_noise(pcfg, batch["tokens"], batch["positions"], batch["segment_ids"], pcfg.noise_key(step))
    want_noised, want_weights, want_counts = reference.noise(batch, sizes, step)
    np.testing.assert_array_equal(noised, want_noised)
    np.testing.assert_array_equal(weights_, want_weights)
    np.testing.assert_array_equal(counts, want_counts)
    masked = np.asarray(noised) != np.asarray(batch["tokens"])
    real = np.asarray(batch["segment_ids"]) > 0
    assert not masked[~real].any() and 0.2 < masked[real].mean() < 0.8
    assert (np.asarray(noised)[masked] == pcfg.mask_token_id).all()
    # one level a block: the weights of a block's masked tokens are one number, at least 1
    w, pos, seg = (np.asarray(a) for a in (weights_, batch["positions"], batch["segment_ids"]))
    for r in range(w.shape[0]):
        blocks = {}
        for t in np.flatnonzero(w[r] > 0):
            blocks.setdefault((seg[r, t], pos[r, t] // 4), set()).add(float(w[r, t]))
        assert blocks and all(len(v) == 1 and min(v) >= 1.0 for v in blocks.values())
    other = moe.block_noise(pcfg, batch["tokens"], batch["positions"], batch["segment_ids"], pcfg.noise_key(step + 1))[0]
    assert (np.asarray(other) != np.asarray(noised)).any()  # another step, another draw


def test_attention_layer_against_the_reference(tiny, batch, seeded):
    """One layer's attention over the two-stream row: the program's two bounds
    and own block against the reference's four cases."""
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    attn = jax.tree.map(lambda a: a[1], params["layers"]["layer"]["attn"])
    x = jax.random.normal(jax.random.key(4), (2, 2 * S, sizes["d_model"]), jnp.float32)
    two = lambda a: jnp.concatenate([a, a], axis=1)
    got, mods = transformer.Attention(pcfg).apply(
        {"params": attn}, x, two(batch["positions"]), two(batch["segment_ids"]), mutable=["intermediates"]
    )
    w = {n[len("moe."):]: a[1] for n, a in leaves.items() if n.startswith("moe.")}
    want = reference.attention(x, w, two(batch["positions"]), two(batch["segment_ids"]), jnp.arange(2 * S) >= S, sizes)
    real = two(batch["segment_ids"] > 0)[..., None]
    np.testing.assert_allclose(got * real, want * real, rtol=2e-4, atol=2e-5)  # float32 both sides: the sums' order
    kept, causal = (float(a) for a in mods["intermediates"]["blockdiff_pairs"][0])
    assert (kept, causal) == counts_sdar.pairs([n for row in DOCS for n in row], 4)


def test_the_clean_stream_is_unchanged_by_what_is_masked(tiny, batch, seeded):
    """No clean query sees a noised key: the clean half of every layer's
    output is the same whatever the noise."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    layer = moe.MoELayer(pcfg)
    p = jax.tree.map(lambda a: a[0], params["layers"]["layer"])
    two = lambda a: jnp.concatenate([a, a], axis=1)
    clean = jax.random.normal(jax.random.key(5), (2, S, sizes["d_model"]), jnp.float32)

    def run(seed):
        noised = jax.random.normal(jax.random.key(seed), clean.shape, jnp.float32)
        out, _ = layer.apply(
            {"params": p}, jnp.concatenate([clean, noised], axis=1), two(batch["positions"]), two(batch["segment_ids"]),
            mutable=["intermediates"],
        )
        return out

    a, b = run(6), run(7)
    np.testing.assert_array_equal(a[:, :S], b[:, :S])
    assert float(jnp.abs(a[:, S:] - b[:, S:]).max()) > 1e-2


# ------------------------------------------------- the whole model and its step


def test_logits_loss_slots_and_counters(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    loss, (logits, mods) = jax.jit(lambda p: program_loss(model, p, batch, 3))(params)
    want = jax.jit(lambda p: reference.logits_of(p, batch, sizes, 3))(leaves)
    assert logits.shape == want.shape == (2, S, 512) and logits.dtype == jnp.float32
    real = (batch["segment_ids"] > 0)[..., None]
    np.testing.assert_allclose(logits * real, want * real, rtol=2e-4, atol=5e-5)  # the sums' order over four layers
    total, parts = jax.jit(lambda p: reference.losses(p, batch, sizes, 3))(leaves)
    np.testing.assert_allclose(loss, total, rtol=2e-5)
    counters = sown.step_counters(mods)
    assert int(counters["moe_slots"]) == int(parts["slots"]) > 0  # over both streams
    masked, real_n = (float(a) for a in parts["masked"])
    np.testing.assert_allclose(counters["diffusion_masked_share"], masked / real_n, rtol=1e-6)
    kept, causal = counts_sdar.pairs([n for row in DOCS for n in row], sizes["block"])
    np.testing.assert_allclose(counters["blockdiff_pairs_share"], kept / causal, rtol=1e-6)
    assert 1.9 < kept / causal < 2.3
    # the loss is the model's: the shifted next-token loss is another number
    assert abs(float(trainer_mod.lm_loss_fn(logits, batch)) - float(loss)) > 0.1


@pytest.mark.parametrize("step", [0, 3])
def test_a_masked_position_reads_the_mask_vector_and_no_row(tiny, batch, seeded, step):
    """``[MASK]`` is the leaf ``mask_embedding``: the logits of real positions
    do not move with row ``mask_token_id`` of the table (the id the noised row
    shows at a masked position; padding reads the row itself), and do with
    the vector."""
    _cfg, _ref, _sizes, pcfg = tiny
    _leaves, model, params = seeded
    assert params["mask_embedding"].shape == (pcfg.d_model,)
    real = (batch["segment_ids"] > 0)[..., None]
    logits = jax.jit(lambda p: program_outputs(model, p, batch, step)[0])
    want = logits(params)
    other_row = dict(params, embedding=params["embedding"].at[pcfg.mask_token_id].add(1.0))
    np.testing.assert_array_equal(logits(other_row) * real, want * real)
    other_vector = dict(params, mask_embedding=params["mask_embedding"] + 0.01)
    assert float(jnp.abs((logits(other_vector) - want) * real).max()) > 1e-3


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's; then two AdamW steps on both sides from those gradients,
    step ``n``'s noise from ``n``."""
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

    program_grad = jax.jit(jax.grad(lambda q, n: program_loss(model, q, batch, n)[0]))
    reference_grad = jax.jit(jax.grad(lambda q, n: reference.losses(q, batch, sizes, n)[0]))
    p, r, gp, gr = params, leaves, [], []
    for n in range(2):
        gp.append(program_grad(p, n))
        gr.append(reference_grad(r, n))
        # float32 both sides; a near-tie of the router that the sums' order flips would read 1e-2 here
        assert worst_gap(gp[-1], gr[-1])[0] < 5e-4, worst_gap(gp[-1], gr[-1])
        p = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), params, *gp)
        r = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), leaves, *gr)
    change = lambda new, old: jax.tree.map(lambda a, b: a - b, new, old)
    assert worst_gap(change(p, params), change(r, leaves))[0] < 2e-3  # AdamW divides by the gradient's size


def test_eight_shares_add_up_to_the_uncut_layer_on_both_streams(tiny, batch, seeded):
    """The guide's section 4 on the two-stream input: the routed parts that
    all 8 shares give for the ``2L`` positions are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert e // held == 8
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *shape))
            for i, (n, shape) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, 2 * S, d), jnp.float32)  # clean and noised
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
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=1e-5)  # eight partial sums against one
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * 2 * S * sizes["top_k"]  # every slot on exactly one share


def one_chip_trainer(model, hp):
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], weight_decay=hp["weight_decay"])
    return trainer_mod.Trainer(model, opt, make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))


def seeded_state(tr, host, params):
    state = tr.make_state(jax.random.key(0), host)
    boxed, treedef = jax.tree_util.tree_flatten(state.params)  # the state's leaves carry their logical axes
    leaves = [jnp.array(b, a.dtype, copy=True) for a, b in zip(boxed, jax.tree_util.tree_leaves(params))]  # the step donates its state
    return state.replace(params=jax.tree_util.tree_unflatten(treedef, leaves))


def test_two_fit_steps_against_the_reference_and_the_gauges(tiny, batch, seeded):
    """``Trainer.fit`` from the seeded weights, recomputing every layer, beside
    ``blockdiff_gqa_moe.train_steps``: the loss of both steps, the slots, and
    the step's counters as gauges; ``evaluate`` reads the same loss."""
    from maggy_tpu import telemetry

    cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    hp = cfg[KIND]["optimizer"]
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    want = reference.train_steps(lambda n: leaves[n], list(leaves), [batch, batch], sizes, hp)
    with telemetry.current(Recorder(worker="t")):
        tr = one_chip_trainer(model, hp)
        state = seeded_state(tr, host, params)
        held_out = tr.evaluate(state, iter([host]), 1)["loss"]
        outs = []
        for _ in range(2):
            state, out = tr.fit(state, iter([host]), num_steps=1)
            outs.append(out)
    for out, loss, slots, share in zip(outs, want["loss"], want["slots"], want["masked_share"]):
        assert out["loss"] == pytest.approx(loss, rel=5e-5)  # float32 both sides
        assert int(out["moe_slots"]) == slots and out["moe_slots_dropped"] == 0
        assert out["diffusion_masked_share"] == pytest.approx(share, rel=1e-6)
        assert "mtp_loss" not in out and out["total_loss"] == pytest.approx(out["loss"], rel=1e-6)
    assert held_out == pytest.approx(want["loss"][0], rel=5e-5)  # step 0's noise on the untrained state
    assert want["masked_share"][0] != want["masked_share"][1]  # a step, a draw
    assert seen["diffusion.masked_share"] == outs[-1]["diffusion_masked_share"]
    assert seen["attention.blockdiff_pairs_share"] == outs[-1]["blockdiff_pairs_share"] > 1.9
    visited = pcfg.tiles_visited_share(host["segment_ids"])
    assert seen["attention.tiles_visited_share"] == pytest.approx(visited) and 0 < visited <= 1


def test_the_same_noise_at_the_same_step_after_a_save_and_restore(tiny, batch, tmp_path):
    """The noise is a pure function of ``noise_seed`` and the step's count: a
    job saved after its first step and restored draws at step 1 what the
    unbroken job drew there (one layer, freshly initialised: the step's count
    is what is under test)."""
    from maggy_tpu.train.checkpoint import Checkpointer

    cfg, _ref, _sizes, pcfg = tiny
    host = {k: np.asarray(v) for k, v in batch.items()}
    tr = one_chip_trainer(moe.MoEDecoder(dataclasses.replace(pcfg, n_layers=1)), cfg[KIND]["optimizer"])
    state, first = tr.step(tr.make_state(jax.random.key(0), host), tr.shard_batch(host))
    ckpt = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    ckpt.save(1, state)
    ckpt.wait()
    unbroken, out = tr.step(state, tr.shard_batch(host))
    restored = ckpt.restore(tr.make_state(jax.random.key(1), host))
    assert int(restored.step) == 1
    resumed, out_again = tr.step(restored, tr.shard_batch(host))
    assert float(out_again["loss"]) == float(out["loss"])
    assert float(out_again["diffusion_masked_share"]) == float(out["diffusion_masked_share"]) != float(first["diffusion_masked_share"])
    for a, b in zip(jax.tree.leaves(nn.meta.unbox(resumed.params)), jax.tree.leaves(nn.meta.unbox(unbroken.params))):
        np.testing.assert_array_equal(a, b)


OTHERS = {
    "dense": lambda: transformer.Decoder(transformer.DecoderConfig.tiny()),
    "experts": lambda: moe.MoEDecoder(moe.MoEConfig.tiny_moe()),
    "share": lambda: moe.MoEDecoder(moe.MoEConfig.tiny_moe(
        n_experts=8, experts_held=2, moe_d_ff=32, router="softmax", vocab_size=512)),
}


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_every_other_models_step_takes_no_noise_key_and_sows_no_weights(name, batch):
    model = OTHERS[name]()
    assert sown.step_inputs(model, 5) == {}
    tokens = batch["tokens"] % 256
    params = model.init(jax.random.key(0), tokens)["params"]
    _logits, mods = model.apply({"params": params}, tokens, batch["positions"], batch["segment_ids"], mutable=["intermediates"])
    assert sown.target_weights(mods) is None
    assert not {"diffusion_masked_share", "blockdiff_pairs_share"} & set(sown.step_counters(mods))
    step = jax.make_jaxpr(lambda p: model.apply({"params": p}, tokens, mutable=["intermediates"]))(params)
    assert "random_bits" not in str(step) and "threefry" not in str(step)


@pytest.mark.parametrize("bad", [
    dict(decode=True), dict(sparse_topk=32, index_heads=4, index_head_dim=16), dict(mtp_depth=1),
    dict(layer_types=("sliding_attention",) * 4, sliding_window=8), dict(block=0), dict(mask_token_id=512),
    dict(noise_eps=1.0),
])
def test_config_refuses_what_the_step_cannot_do(tiny, bad):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError):
        dataclasses.replace(pcfg, **bad)


def test_the_other_step_builders_refuse_the_objective_by_its_mechanism(tiny, batch, seeded):
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec
    from maggy_tpu.train.pipeline_adapter import decoder_pipeline_parts

    cfg, _ref, _sizes, pcfg = tiny
    hp = cfg[KIND]["optimizer"]
    model = moe.MoEDecoder(pcfg)
    host = {k: np.asarray(v) for k, v in batch.items()}
    tr = trainer_mod.Trainer(
        model, optax.adamw(hp["lr"]), make_mesh(ShardingSpec(dp=2), jax.devices()[:2]), bucket_mb=1.0,
    )
    state = tr.make_state(jax.random.key(0), host)
    with pytest.raises(NotImplementedError, match="weighs its own targets"):
        tr.step(state, tr.shard_batch(host))
    with pytest.raises(ValueError, match="two-stream"):
        decoder_pipeline_parts(moe.MoEDecoder(dataclasses.replace(pcfg, experts_held=0, chunk_of_load=0.0)), 2)
