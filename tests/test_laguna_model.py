"""``laguna`` as a whole model against its plain reference
``benchmark/references/window_gqa_moe.py`` on seeded weights at small sizes
(``benchmark/checks/tiny.laguna-s-2.1.json``): each kind of attention layer,
the scaled softmax route, the shares of a layer beside its shared expert, the
logits, the loss, every leaf's gradient, two AdamW steps, and the trainer's
step with its counters. The kernels, the rotary forms and the refusals are
``test_laguna_window.py``'s, whose helpers and fixtures this file shares."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_laguna_window import KIND, S, batch, program_outputs, seeded, tiny  # noqa: F401  (fixtures by name)

from benchmark import counts_laguna
from benchmark.references import window_gqa_moe as reference
from benchmark.references.decoder import adamw_apply
from maggy_tpu.models import moe, sown, transformer
from maggy_tpu.ops.flash import tiles_visited_share
from maggy_tpu.train import trainer as trainer_mod


# ----------------------------------------------------------- layer by layer


def group(leaves, prefix, layer=None):
    out = {n[len(prefix) + 1:]: a for n, a in leaves.items() if n.startswith(prefix + ".")}
    return out if layer is None else {n: a[layer] for n, a in out.items()}


@pytest.mark.parametrize("prefix,module,kind", [
    ("d0", ("dense_0",), "full_attention"), ("p1", ("layers", "layer_1"), "sliding_attention"),
    ("p3", ("layers", "layer_3"), "full_attention"),
])
def test_attention_layer_of_each_kind_against_the_reference(tiny, batch, seeded, prefix, module, kind):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    attn = params
    for m in (*module, "layer", "attn"):
        attn = attn[m]
    stacked = module[0] == "layers"
    attn = jax.tree.map(lambda a: a[0], attn) if stacked else attn
    heads, window, _rotary = pcfg.attention_form(kind)
    assert (heads, window) == ((6, 32) if kind == "sliding_attention" else (4, 0))
    assert attn["wq"]["kernel"].shape == (80, heads, 32) and attn["w_head_gate"]["kernel"].shape == (80, heads)
    x = jax.random.normal(jax.random.key(4), (2, S, sizes["d_model"]), jnp.float32)
    got = transformer.Attention(pcfg, kind).apply({"params": attn}, x, batch["positions"], batch["segment_ids"],
                                                  mutable=["intermediates"])[0]
    w = group(leaves, prefix, 0 if stacked else None)
    want = reference.attention(x, w, kind, heads, batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    other = "full_attention" if kind == "sliding_attention" else "sliding_attention"
    if other == "full_attention":  # the same leaves read as the other kind give another result: the kind matters
        elsewhere = reference.attention(x, w, other, heads, batch["positions"], batch["segment_ids"], sizes)
        assert float(jnp.abs(elsewhere - want).max()) > 1e-3


def test_softmax_router_scales_its_weights_as_the_reference(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, _params = seeded
    assert (pcfg.router, pcfg.n_shared_experts, pcfg.top_k, pcfg.routed_scaling) == ("softmax", 1, 5, 2.5)
    xn = jax.random.normal(jax.random.key(8), (2, S, sizes["d_model"]), jnp.float32)
    router = leaves["p2.router"][0]
    logits = jnp.einsum("bsd,de->bse", xn, router, precision="highest")
    sel, w = moe.softmax_route(logits, pcfg.top_k, pcfg.routed_scaling)
    sel_ref, w_ref = reference.route(xn, router, sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(w, w_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_array_equal(moe.softmax_route(logits, 5)[1], moe.softmax_route(logits, 5, 1.0)[1])


def test_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all 4 shares of the layer
    give, and the shared expert counted once, are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert e // held == 4
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *shape))
            for i, (n, shape) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, S, d), jnp.float32)
    base = jax.tree.map(lambda a: a[0], params["layers"]["layer_0"]["layer"]["moe"])
    total, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        y, mods = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share)).apply(
            {"params": mine}, xn, mutable=["intermediates"]
        )
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
    shared = {n: base["shared"][f"w_{n}"]["kernel"] for n in ("gate", "up", "down")}
    once = reference.swiglu(xn, shared["gate"], shared["up"], shared["down"], None)
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()}}
    uncut = dict(sizes, held=e, offset=0)
    routed, slots = reference.routed_part(xn, w, *reference.route(xn, w["router"], uncut), uncut)
    np.testing.assert_allclose(total - (e // held - 1) * once, routed + once, rtol=2e-5, atol=2e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * S * sizes["top_k"]  # every slot on exactly one share


# ------------------------------------------------- the whole model and its step


def test_logits_loss_slots_and_the_windows_share_of_the_pairs(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    logits, mods = jax.jit(lambda p: program_outputs(model, p, batch))(params)
    want = jax.jit(lambda p: reference.logits_of(p, batch, sizes))(leaves)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=5e-5)
    _, parts = jax.jit(lambda p: reference.losses(p, batch, sizes))(leaves)
    np.testing.assert_allclose(trainer_mod.lm_loss_fn(logits, batch), parts["main"], rtol=1e-5)
    counters = sown.step_counters(mods)
    assert float(counters["moe_slots"]) == float(parts["slots"]) > 0 and float(counters["moe_slots_dropped"]) == 0
    inside, causal = reference.window_pairs(batch, sizes["window"])
    np.testing.assert_allclose(counters["window_pairs_share"], inside / causal, rtol=1e-6)
    docs = [70, 50, 20, 108]
    assert (inside, causal) == counts_laguna.pairs(docs, sizes["window"]) and inside < causal
    assert pcfg.attention_windows() == (0, 32, 32, 32, 0)
    # needed operations of the two kinds of layer: heads times the pairs each sees
    assert counts_laguna.window_flash_flops(sizes, docs) == 3 * 4 * 32 * (3 * 6) * inside
    assert counts_laguna.full_flash_flops(sizes, docs) == 3 * 4 * 32 * (2 * 4) * causal
    assert counts_laguna.modules_of(sizes, "sliding_attention") == {"layer_0", "layer_1", "layer_2"}
    assert counts_laguna.modules_of(sizes, "full_attention") == {"dense_0", "layer_3"}


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
    gate = gp[0]["layers"]["layer_1"]["layer"]["attn"]["w_head_gate"]["kernel"]
    assert gate.shape == (1, 80, 6) and float(jnp.abs(gate).max()) > 0


def test_trainer_step_reports_the_window_and_fit_publishes_the_gauges(tiny, batch):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name.startswith("attention."):
                seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    with telemetry.current(Recorder(worker="t")):
        tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    inside, causal = reference.window_pairs(batch, sizes["window"])
    assert {"loss", "window_pairs_share", "moe_slots"} <= set(out) and out["moe_slots_dropped"] == 0
    assert out["window_pairs_share"] == pytest.approx(inside / causal, rel=1e-6)
    assert seen["attention.window_pairs_share"] == out["window_pairs_share"]
    # a mean over the five attention layers, the three windowed ones with the window's tiles counted out
    seg = host["segment_ids"]
    plain, windowed = tiles_visited_share(seg, head_dim=32), tiles_visited_share(seg, head_dim=32, window=32)
    assert seen["attention.tiles_visited_share"] == pytest.approx((2 * plain + 3 * windowed) / 5)
