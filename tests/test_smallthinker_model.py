"""``smallthinker`` against its plain reference
``benchmark/references/prerouted_window_moe.py`` on seeded weights at small
sizes (``benchmark/checks/tiny.smallthinker-21ba3b-instruct.json``): a router
that reads the layer's input ahead of attention (its gradient with and without
the path into ``x``), ReLU-gated experts and their count of zero hidden
activations, global layers with no positional embedding beside windowed ones
that rotate, the shares of a layer, the logits, the loss, every leaf's
gradient, two AdamW steps; and that ``expert_act="silu"`` with the router
behind attention is the program it was, bit for bit.

Both sides compute in float32 here, so what differs is the order of the sums.
The chip run's comparison, in bfloat16, is the cell's
(``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, counts_laguna, counts_smallthinker, run as bench_run  # noqa: E402
from benchmark.references import prerouted_window_moe as reference  # noqa: E402
from benchmark.references.decoder import adamw_apply, swiglu  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402
from test_laguna_window import packed, program_outputs, seeded  # noqa: E402,F401  (``seeded`` takes this file's ``tiny`` and ``batch`` by name)

KIND = "train_packed_ref"
NAME = "smallthinker-21ba3b-instruct"
S = 128


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(REPO, "benchmark", "checks", f"tiny.{NAME}.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load(f"benchmark/configs/{NAME}.json"), small)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 128 under a window of 32: a document of 70 and one
    of 50 (both longer than the window) before padding, and one of 20 (inside
    it) before one of 108."""
    return packed([[70, 50], [20, 108]], np.random.default_rng(5))


def layer_of(tree, j, period=0):
    return jax.tree.map(lambda a: a[period], tree["layers"][f"layer_{j}"]["layer"])


def group(leaves, prefix, period=0):
    return {n[len(prefix) + 1:]: a[period] for n, a in leaves.items() if n.startswith(prefix + ".")}


# ------------------------------------------------------------ the configuration


def test_the_configuration_reads_as_published_and_the_program_takes_its_three_fields(tiny):
    cfg, _ref, sizes, pcfg = tiny
    assert sizes["layer_types"] == (["full_attention"] + ["sliding_attention"] * 3) * 2
    assert sizes["window_layout"] == sizes["rope_layout"] == [0, 1, 1, 1] * 2 and sizes["n_dense"] == 0
    assert (pcfg.route_from, pcfg.expert_act, pcfg.full_rope, pcfg.router) == ("layer_input", "relu", False, "softmax")
    assert (pcfg.n_experts, pcfg.top_k, pcfg.experts_held, pcfg.n_shared_experts, pcfg.n_dense_layers) == (8, 3, 2, 0, 0)
    assert pcfg.attention_windows() == (0, 32, 32, 32) * 2
    full, sliding = pcfg.attention_form("full_attention"), pcfg.attention_form("sliding_attention")
    assert full == (4, 0, (1.5e6, 0, (), 1.0)) and sliding == (4, 32, (1.5e6, 32, (), 1.0))
    published = configs.load(f"benchmark/configs/{NAME}.json")
    assert set(published["reduced"]) == set(published["why_reduced"]) == {
        k for k, v in published.items() if isinstance(v, dict) and "published" in v
    }
    assert {"router", "activation", "attention", "rotary", "window", "weights", "optimizer", "balance_loss"} == set(published["assumed"])


@pytest.mark.parametrize("fields,match", [
    (dict(route_from="layer_input", experts_held=0), "share form"),
    (dict(route_from="layer_input", decode=True, layer_types=()), "decode step"),
    (dict(route_from="nowhere"), "route_from"),
    (dict(expert_act="gelu"), "expert_act"),
    (dict(expert_act="relu", experts_held=0, route_from="mlp_norm"), "routed experts"),
    (dict(expert_act="relu", n_shared_experts=1), "shared"),
    (dict(full_rope=False, rope_share=0.5), "full_rope"),
    (dict(full_rope=False, rope_yarn=(8.0, 64, 32.0, 1.0, 1.2)), "full_rope"),
])
def test_combinations_that_are_not_written_are_refused(tiny, fields, match):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(pcfg, **fields)


# -------------------------------------------------------------- layer by layer


@pytest.mark.parametrize("j,kind", [(0, "full_attention"), (1, "sliding_attention"), (3, "sliding_attention")])
def test_attention_layer_of_each_kind_against_the_reference(tiny, batch, seeded, j, kind):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    attn = layer_of(params, j)["attn"]
    assert set(attn) == {"wq", "wk", "wv", "wo"}  # no norm a head, no gate, no bias
    x = jax.random.normal(jax.random.key(4), (2, S, sizes["d_model"]), jnp.float32)
    got = transformer.Attention(pcfg, kind).apply(
        {"params": attn}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )[0]
    windowed = kind == "sliding_attention"
    w = group(leaves, f"p{j}")
    want = reference.attention(x, w, windowed, windowed, batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the same leaves read as the other form give another result: window and rotary both matter
    for other in ((not windowed, windowed), (windowed, not windowed)):
        elsewhere = reference.attention(x, w, *other, batch["positions"], batch["segment_ids"], sizes)
        assert float(jnp.abs(elsewhere - want).max()) > 2e-4


@pytest.mark.parametrize("kind,moves", [("full_attention", False), ("sliding_attention", True)])
def test_a_global_layer_is_bit_equal_under_a_shift_of_positions_and_a_windowed_one_is_not(tiny, batch, seeded, kind, moves):
    """No positional embedding at all: the global layer's output does not
    read ``positions`` (the mask comes from the row's order and the segment
    ids); the windowed layers rotate by them. A shift that differs by document
    changes relative angles across nothing a query sees, so it is a shift by
    rows here: one constant a row would cancel in the rotary products."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    attn = layer_of(params, 0 if kind == "full_attention" else 1)["attn"]
    x = jax.random.normal(jax.random.key(6), (2, S, sizes["d_model"]), jnp.float32)
    run = lambda pos: transformer.Attention(pcfg, kind).apply(
        {"params": attn}, x, pos, batch["segment_ids"], mutable=["intermediates"]
    )[0]
    stretched = batch["positions"] * 3 + 7  # relative distances change: a rotary layer has to notice
    base, shifted = run(batch["positions"]), run(stretched)
    assert bool(jnp.array_equal(base, shifted)) is (not moves)
    if moves:
        assert float(jnp.abs(base - shifted).max()) > 1e-3


def test_the_router_reads_the_layers_input_and_routes_as_the_reference(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(8), (2, S, sizes["d_model"]), jnp.float32)
    w = group(leaves, "p2")
    logits = jnp.einsum("bsd,de->bse", x, w["router"], precision="highest")
    sel, weights_ = moe.softmax_route(logits, pcfg.top_k)
    sel_ref, w_ref = reference.route(x, w["router"], sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(weights_, w_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(weights_.sum(-1), 1.0, rtol=1e-6)
    layer = moe.MoELayer(pcfg, "sliding_attention")
    got, mods = layer.apply({"params": layer_of(params, 2)}, x, batch["positions"], batch["segment_ids"],
                            mutable=["intermediates"])
    want, counts = reference.layer(x, w, True, True, batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-5)
    load = mods["intermediates"]["moe"]["expert_load"][0]
    assert int(load.sum()) == int(counts[0]) > 0
    # the neighbour's place for the router gives another selection and another layer
    late, _ = reference.layer(x, w, True, True, batch["positions"], batch["segment_ids"], sizes,
                              {"fault": "route_after_attention"})
    assert float(jnp.abs(late - want).max()) > 1e-3


@pytest.mark.parametrize("path_into_x", [True, False])
def test_the_routers_gradient_with_and_without_its_path_into_x(tiny, batch, seeded, path_into_x):
    """The router's cotangent reaches the layer's input directly, a second
    path beside the residual's: with the path cut on both sides (a stop
    gradient on what the router reads) the input's gradient is another one,
    the router's own leaf keeps its gradient, and program and reference agree
    either way."""
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(10), (2, S, sizes["d_model"]), jnp.float32)
    w, p = group(leaves, "p1"), layer_of(params, 1)
    probe = jax.random.normal(jax.random.key(11), x.shape, jnp.float32)
    pos, seg = batch["positions"], batch["segment_ids"]

    class Cut(nn.Module):
        """The layer with the router's input detached."""

        @nn.compact
        def __call__(self, x):
            share = moe.ExpertShareBlock(pcfg, name="moe")
            route = share.route(jax.lax.stop_gradient(x).reshape(-1, x.shape[-1]))
            a = transformer.layer_operator(pcfg, "sliding_attention", x, pos, seg)
            h = x + a
            return h + share(transformer.RMSNorm(pcfg, name="mlp_norm")(h), None, route)

    def program(x, p):
        if path_into_x:
            out = moe.MoELayer(pcfg, "sliding_attention").apply({"params": p}, x, pos, seg, mutable=["intermediates"])[0]
        else:
            out = Cut().apply({"params": p}, x, mutable=["intermediates"])[0]
        return (out * probe).sum()

    def plain(x, w):
        eps = sizes["norm_eps"]
        picked = reference.route(x if path_into_x else jax.lax.stop_gradient(x), w["router"], sizes)
        h = x + reference.attention(reference.rms_norm(x, w["attn_norm"], eps), w, True, True, pos, seg, sizes)
        y, _ = reference.routed_part(reference.rms_norm(h, w["mlp_norm"], eps), w, *picked, sizes)
        return ((h + y) * probe).sum()

    (gx, gp), (rx, rw) = jax.grad(program, (0, 1))(x, p), jax.grad(plain, (0, 1))(x, w)
    np.testing.assert_allclose(gx, rx, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(gp["moe"]["router"]["kernel"], rw["router"], rtol=5e-4, atol=5e-6)
    assert float(jnp.abs(rw["router"]).max()) > 0
    with_path = jax.grad(lambda x: (probe * moe.MoELayer(pcfg, "sliding_attention").apply(
        {"params": p}, x, pos, seg, mutable=["intermediates"])[0]).sum())(x)
    assert (float(jnp.abs(with_path - gx).max()) > 1e-4) is (not path_into_x)


def test_four_shares_of_two_add_up_to_the_uncut_reference_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all 4 shares of the layer
    give (no shared expert to count once) are the uncut layer's result, and
    every slot and every zero hidden activation falls on exactly one share."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert (e, held) == (8, 2)
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *shape))
            for i, (n, shape) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    x = jax.random.normal(jax.random.fold_in(key, 8), (2, S, d), jnp.float32)  # what the router reads
    m = jax.random.normal(jax.random.fold_in(key, 9), (2, S, d), jnp.float32)  # what the experts read
    base = layer_of(params, 0)["moe"]
    total, load, zeros = 0.0, [], []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        block = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share))

        def run(block, x, m):
            return block(m, None, block.route(x.reshape(-1, d)))

        y, mods = nn.apply(run, block, mutable=["intermediates"])({"params": mine}, x, m)
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
        zeros.append(mods["intermediates"]["hidden_zeros"][0])
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()}}
    uncut = dict(sizes, held=e, offset=0)
    routed, counts = reference.routed_part(m, w, *reference.route(x, w["router"], uncut), uncut)
    np.testing.assert_allclose(total, routed, rtol=2e-5, atol=2e-5)
    assert int(jnp.concatenate(load).sum()) == int(counts[0]) == 2 * S * sizes["top_k"]
    zeros = jnp.stack(zeros)
    assert int(zeros[:, 0].sum()) == int(counts[1]) and int(zeros[:, 1].sum()) == int(counts[0]) * f
    assert 0.3 < int(counts[1]) / (int(counts[0]) * f) < 0.7  # about a half on random weights


# ------------------------------------------- silu and the router behind attention


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_chunks_activation_is_static_and_silu_is_the_program_it_was(act):
    """``_chunk_experts`` with ``act="silu"`` is the three products and the
    SiLU gate it always was, bit for bit, and counts nothing; with "relu" the
    gate is ReLU and the zeros are counted in the rows that hold a slot."""
    key = jax.random.key(2)
    a, wg, wu, wd = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) for i, shape in
                     enumerate([(24, 16), (3, 16, 8), (3, 16, 8), (3, 8, 16)]))
    sizes = jnp.array([7, 0, 9], jnp.int32)
    y, zeros = moe._chunk_experts(a, sizes, wg, wu, wd, act)
    gate = (nn.silu if act == "silu" else nn.relu)(moe.grouped_dot(a, wg, sizes))
    was = moe.grouped_dot(gate * moe.grouped_dot(a, wu, sizes), wd, sizes)
    assert bool(jnp.array_equal(y[:16], was[:16]))
    if act == "silu":
        assert zeros is None and bool(jnp.array_equal(y[:16], moe._chunk_experts(a, sizes, wg, wu, wd)[0][:16]))
    else:
        assert int(zeros) == int((gate[:16] == 0).sum()) > 0


def test_silu_and_post_attention_routing_are_the_parents_layer_bit_for_bit(tiny, batch, seeded):
    """With the defaults (``route_from="mlp_norm"``, ``expert_act="silu"``,
    ``full_rope=True``) a layer is what it was before the three fields
    existed: the router on ``mlp_norm``'s output inside the block, SwiGLU
    experts, rotary on the global layers, written out here by hand."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    was = dataclasses.replace(pcfg, route_from="mlp_norm", expert_act="silu", full_rope=True)
    p = layer_of(params, 0)
    x = jax.random.normal(jax.random.key(12), (2, S, sizes["d_model"]), jnp.float32)
    pos, seg = batch["positions"], batch["segment_ids"]
    got, mods = moe.MoELayer(was, "full_attention").apply({"params": p}, x, pos, seg, mutable=["intermediates"])
    assert "hidden_zeros" not in mods["intermediates"]["moe"]

    class Parent(nn.Module):
        @nn.compact
        def __call__(self, x):
            a = transformer.layer_operator(was, "full_attention", x, pos, seg)
            h = x + a
            m = transformer.RMSNorm(was, name="mlp_norm")(h)
            return h + moe.ExpertShareBlock(was, name="moe")(m)

    want = Parent().apply({"params": p}, x, mutable=["intermediates"])[0]
    assert bool(jnp.array_equal(got, want))
    w = {"experts_gate": p["moe"]["w_gate"], "experts_up": p["moe"]["w_up"], "experts_down": p["moe"]["w_down"]}
    h = x + transformer.Attention(was, "full_attention").apply({"params": p["attn"]}, transformer.RMSNorm(was).apply(
        {"params": p["attn_norm"]}, x), pos, seg, mutable=["intermediates"])[0]
    m = transformer.RMSNorm(was).apply({"params": p["mlp_norm"]}, h)
    sel, wts = reference.route(m, p["moe"]["router"]["kernel"], sizes)
    first = sizes["offset"] * sizes["held"]
    coef = jnp.where(sel[..., None] == first + jnp.arange(sizes["held"]), wts[..., None], 0.0).sum(2)
    y = sum(coef[..., e, None] * swiglu(m, w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e], None)
            for e in range(sizes["held"]))
    np.testing.assert_allclose(got, h + y, rtol=2e-4, atol=5e-5)
    # and the new layer differs from it in each of the three ways
    for field, value in (("route_from", "layer_input"), ("expert_act", "relu")):
        other = moe.MoELayer(dataclasses.replace(was, **{field: value}), "full_attention").apply(
            {"params": p}, x, pos, seg, mutable=["intermediates"])[0]
        assert float(jnp.abs(other - got).max()) > 1e-4, field
    nope = moe.MoELayer(dataclasses.replace(was, full_rope=False, layer_types=pcfg.layer_types), "full_attention").apply(
        {"params": p}, x, pos, seg, mutable=["intermediates"])[0]
    assert float(jnp.abs(nope - got).max()) > 1e-4


# ------------------------------------------------- the whole model and its step


def test_logits_loss_slots_and_the_share_of_zero_hidden_activations(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    logits, mods = jax.jit(lambda p: program_outputs(model, p, batch))(params)
    want = jax.jit(lambda p: reference.logits_of(p, batch, sizes))(leaves)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=5e-5)
    _, parts = jax.jit(lambda p: reference.losses(p, batch, sizes))(leaves)
    np.testing.assert_allclose(trainer_mod.lm_loss_fn(logits, batch), parts["main"], rtol=1e-5)
    counters = sown.step_counters(mods)
    assert set(counters) == {"moe_slots", "moe_slots_dropped", "moe_load_max_over_mean", "moe_rows_visited_share", "moe_combine_rows_share",
                             "moe_hidden_zero_share", "window_pairs_share"}
    assert float(counters["moe_slots"]) == float(parts["slots"]) > 0 and float(counters["moe_slots_dropped"]) == 0
    np.testing.assert_allclose(counters["moe_hidden_zero_share"], parts["hidden_zero_share"], rtol=1e-6)
    assert 0.3 < float(counters["moe_hidden_zero_share"]) < 0.7
    docs = [70, 50, 20, 108]
    inside, causal = counts_laguna.pairs(docs, sizes["window"])
    np.testing.assert_allclose(counters["window_pairs_share"], inside / causal, rtol=1e-6)
    # needed operations of the two kinds of layer, and the modules a trace tells them apart by
    assert counts_laguna.window_flash_flops(sizes, docs) == 3 * 4 * 32 * (6 * 4) * inside
    assert counts_laguna.full_flash_flops(sizes, docs) == 3 * 4 * 32 * (2 * 4) * causal
    assert counts_laguna.modules_of(sizes, "sliding_attention") == {"layer_1", "layer_2", "layer_3"}
    assert counts_laguna.modules_of(sizes, "full_attention") == {"layer_0"}
    slots = int(parts["slots"])
    d, f, h, kv, hd = 80, 32, 4, 2, 32
    per_token = 8 * (2 * d * h * hd + 2 * d * kv * hd + d * 8) + d * 512
    assert counts_smallthinker.train_flops(sizes, docs, slots) == 3 * (
        2 * (per_token * sum(docs) + 3 * d * f * slots) + 4 * hd * h * (6 * inside + 2 * causal)
    )


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's; then two AdamW steps on both sides from those gradients."""
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

    program_grad = jax.jit(jax.grad(lambda q: trainer_mod.lm_loss_fn(program_outputs(model, q, batch)[0], batch)))
    reference_grad = jax.jit(jax.grad(lambda q: reference.losses(q, batch, sizes)[0]))
    p, r, gp, gr = params, leaves, [], []
    for _ in range(2):
        gp.append(program_grad(p))
        gr.append(reference_grad(r))
        assert worst_gap(gp[-1], gr[-1])[0] < 2e-4, worst_gap(gp[-1], gr[-1])
        p = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), params, *gp)
        r = jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), leaves, *gr)
    change = lambda new, old: jax.tree.map(lambda a, b: a - b, new, old)
    assert worst_gap(change(p, params), change(r, leaves))[0] < 1e-3  # AdamW divides by the gradient's size
    router = gp[0]["layers"]["layer_0"]["layer"]["moe"]["router"]["kernel"]
    assert router.shape == (2, 80, 8) and float(jnp.abs(router).max()) > 0


def test_trainer_step_reports_the_zero_share_and_fit_publishes_the_gauge(tiny, batch):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name.startswith("moe."):
                seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    with telemetry.current(Recorder(worker="t")):
        tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    assert {"loss", "moe_hidden_zero_share", "window_pairs_share", "moe_slots"} <= set(out) and out["moe_slots_dropped"] == 0
    assert 0.3 < out["moe_hidden_zero_share"] < 0.7 and seen["moe.hidden_zero_share"] == out["moe_hidden_zero_share"]


def test_the_scope_around_a_router_ahead_of_attention(tiny, batch, seeded):
    """The router's operations keep the module's name (flax names the scope of
    a method other than ``__call__`` ``<module>.<method>``: ``moe.route``) and
    the named scopes ``moe.route`` / ``moe.dispatch`` inside one enclosing
    ``moe.preroute``, in the forward and in the backward pass; a layer that
    routes behind attention has no such scope."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    x = jnp.zeros((2, S, sizes["d_model"]), jnp.float32)

    def names(cfg):
        f = lambda x, p: moe.MoELayer(cfg, "sliding_attention").apply(
            {"params": p}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"])[0].sum()
        text = jax.jit(jax.grad(f, (0, 1))).lower(x, layer_of(params, 1)).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    ahead = names(pcfg)
    assert any("moe.preroute/moe.route/moe.route/router" in n for n in ahead)
    assert any("moe.preroute/moe.route/moe.dispatch" in n for n in ahead)
    assert any("transpose" in n and "moe.preroute" in n and "moe.route" in n for n in ahead)  # the backward pass
    routes = [n for n in ahead if "moe.route" in n]
    assert routes and all("moe.preroute" in n for n in routes)  # nothing of the router outside the scope
    assert not any("moe.preroute" in n for n in names(dataclasses.replace(pcfg, route_from="mlp_norm")))
