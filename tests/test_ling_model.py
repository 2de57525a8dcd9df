"""``ling-3.0-flash`` against its plain reference
``benchmark/references/kda_mla_moe.py`` on seeded weights at small sizes
(``benchmark/checks/tiny.ling-3.0-flash.json``): a KDA layer
(the chunked delta rule itself is held against the recurrence token by token
in ``tests/test_kda_op.py``) and a gated
latent-attention layer with queries of 24 beside values of 16 (the padded
call); the group-limited router and the shares of a layer; the logits, the
loss, the counters, every leaf's gradient, two AdamW steps; and that a
configuration without the new fields is the program it was, bit for bit.

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

from benchmark import configs, counts_ling, run as bench_run  # noqa: E402
from benchmark.references import kda_mla_moe as reference  # noqa: E402
from benchmark.references.decoder import adamw_apply  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402
from test_kda_op import DOCS, S, batch  # noqa: E402,F401  (the rows the delta rule is tested on)
from test_laguna_window import program_outputs, seeded  # noqa: E402,F401  (``seeded`` takes this file's ``tiny`` and ``batch`` by name)

KIND = "train_packed_ref"
NAME = "ling-3.0-flash"


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(REPO, "benchmark", "checks", f"tiny.{NAME}.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load(f"benchmark/configs/{NAME}.json"), small)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


def layer_of(params, i):
    """Layer ``i`` of the cut: ``dense_0``, then the unrolled ``layers_<i - 1>``."""
    return params["dense_0"]["layer"] if i == 0 else params[f"layers_{i - 1}"]["layer"]


def group(leaves, i):
    return {n[len(f"l{i}."):]: a for n, a in leaves.items() if n.startswith(f"l{i}.")}


def taps_zeroed(seg, taps=4):
    """Taps that reach before the row or into another document, counted one by one."""
    seg = np.asarray(seg)
    return sum(t < j or seg[r, t - j] != seg[r, t] for r in range(seg.shape[0]) for t in range(seg.shape[1]) for j in range(1, taps))


# ------------------------------------------------------------ the configuration


def test_the_configuration_reads_as_published_and_the_program_takes_its_fields(tiny):
    cfg, ref, sizes, pcfg = tiny
    assert sizes["layer_types"] == ["kda", "kda", "kda", "kda", "mla", "kda", "kda"] and sizes["n_dense"] == 1
    assert pcfg.layer_types == ("kda",) * 4 + ("latent_attention",) + ("kda",) * 2
    assert (pcfg.n_experts, pcfg.top_k, pcfg.n_group, pcfg.topk_group, pcfg.experts_held, pcfg.n_shared_experts) == (16, 3, 2, 1, 4, 1)
    assert (pcfg.q_lora_rank, pcfg.kv_lora_rank, pcfg.head_dim, pcfg.v_head_dim, pcfg.attn_gate) == (0, 32, 24, 16, True)
    assert (pcfg.kda_head_dim, pcfg.kda_conv_kernel, pcfg.kda_decay_floor, pcfg.kda_chunk) == (16, 4, -5.0, 16)
    assert pcfg.attention_windows() == (0,)  # one softmax layer; the six others carry a state
    published = configs.load(f"benchmark/configs/{NAME}.json")
    full = ref.sizes(published, KIND)
    assert full["layer_types"] == sizes["layer_types"]  # published layers 1 to 7: layer 5 is the latent one
    assert [i for i in range(42) if (i + 1) % published["layer_group_size"] == 0] == [5, 11, 17, 23, 29, 35, 41]
    assert (full["d_model"], full["kda_dim"], full["d_nope"], full["d_rope"], full["d_v"], full["kv_rank"]) == (2560, 128, 128, 64, 128, 512)
    assert (full["n_experts"], full["n_group"], full["topk_group"], full["top_k"], full["held"], full["vocab"]) == (512, 8, 4, 8, 8, 19648)
    assert set(published["reduced"]) == set(published["why_reduced"]) == {
        k for k, v in published.items() if isinstance(v, dict) and "published" in v
    }
    spec = ref.leaf_spec(full)
    total = sum(int(np.prod(shape)) for shape, _stacked, _std, _mean in spec.values())
    assert total == published["parameters"]["total"]
    with open(os.path.join(REPO, published["control"])) as f:
        assert set(json.load(f)["variants"]) == {"float8_operands", "bfloat16_state", *reference.FAULTS}


@pytest.mark.parametrize("fields,match", [
    (dict(layer_types=("kda",) * 7, kda_head_dim=0), "kda_head_dim"),
    (dict(kda_chunk=24), "kda_chunk"),
    (dict(kda_decay_floor=0.0), "kda_decay_floor"),
    (dict(kda_decay_floor=-9.0, kda_chunk=64), "float32"),
    (dict(decode=True), "decode"),
    (dict(kv_lora_rank=0), "kv_lora_rank"),
    (dict(qk_rope_head_dim=7), "even qk_rope_head_dim"),
    (dict(v_head_dim=0), "v_head_dim"),
    (dict(n_kv_heads=2), "n_kv_heads"),
    (dict(n_group=3), "group limit"),
    (dict(n_group=2, topk_group=3), "group limit"),
    (dict(n_group=8, topk_group=1, top_k=3), "group limit"),
    (dict(n_group=2, router="softmax", select_bias_std=0.0), "group limit"),
    (dict(n_group=2, experts_held=0, n_shared_experts=0), "share form"),
])
def test_combinations_that_are_not_written_are_refused(tiny, fields, match):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(pcfg, **fields)


# -------------------------------------------------------------- layer by layer


def test_a_kda_layer_against_the_reference_and_its_planted_faults(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    p = layer_of(params, 2)["kda"]
    assert set(p) == {"wq", "wk", "wv", "wf", "w_beta", "w_head_gate", "q_conv", "k_conv", "v_conv", "A_log", "dt_bias", "o_norm", "wo"}
    x = jax.random.normal(jax.random.key(4), (2, S, sizes["d_model"]), jnp.float32)
    got, mods = transformer.KDA(pcfg).apply({"params": p}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"])
    w = group(leaves, 2)
    want, decay = jax.jit(lambda x, w: reference.kda(x, w, batch["segment_ids"], sizes))(x, w)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    cut, chunks, a_sum, a_count = mods["intermediates"]["kda_counts"][0]
    assert (int(cut), int(chunks)) == (4, 16) and int(a_count) == int(decay[1]) == 2 * S * 4 * 16
    np.testing.assert_allclose(a_sum, decay[0], rtol=1e-5)
    masked, of = mods["intermediates"]["taps_masked"][0]
    assert (int(masked), int(of)) == (taps_zeroed(batch["segment_ids"]), 2 * S * 4)
    for fault in ("scalar_decay", "no_delta_correction", "state_crosses_documents", "conv_crosses_documents"):
        other, _ = jax.jit(lambda x, w, fault=fault: reference.kda(x, w, batch["segment_ids"], sizes, {"fault": fault}))(x, w)
        assert float(jnp.abs(other - want).max()) > 1e-4, fault


def test_a_gated_latent_layer_at_its_true_widths_against_the_padded_call(tiny, batch, seeded):
    """Queries and keys of 16 + 8 beside values of 16: the program pads all
    three to one width for the kernels' sake and cuts the result; the reference
    computes at 24 and 16. The event says so."""
    from maggy_tpu import telemetry

    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    p = layer_of(params, 4)["attn"]
    assert set(p) == {"wq", "wkv_a", "kv_norm", "wkv_b", "w_head_gate", "wo"} and p["wq"]["kernel"].shape == (64, 4, 24)
    x = jax.random.normal(jax.random.key(5), (2, S, sizes["d_model"]), jnp.float32)
    rec = telemetry.Telemetry(worker="t")
    with telemetry.current(rec):
        got = transformer.LatentAttention(pcfg).apply({"params": p}, x, batch["positions"], batch["segment_ids"])
    want = reference.latent_attention(x, group(leaves, 4), batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    event = [r["attrs"] for r in rec.flight if r.get("name") == "attention.kernel"][-1]
    assert (event["lanes"], event["qk_width"], event["v_width"], event["head_dim"]) == ("padded", 24, 16, 128)


def test_latent_attention_without_the_new_fields_is_the_program_it_was(batch, monkeypatch):
    """``kv_lora_rank`` with a low-rank query, one width and no gate: a layer
    of kind ``latent_attention`` and one of a configuration with no
    ``layer_types`` at all give one result bit for bit, no call is padded, and
    the leaves are the parent's."""
    monkeypatch.setattr(transformer, "padded_attention", lambda *a, **k: pytest.fail("a padded call"))
    was = moe.MoEConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=96, max_seq_len=S, dtype=jnp.float32,
        q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        n_experts=8, top_k=2, experts_held=4, moe_d_ff=32, n_shared_experts=1, select_bias_std=0.1,
    )
    named = dataclasses.replace(was, layer_types=("latent_attention",) * 2)
    x = jax.random.normal(jax.random.key(2), (2, S, 64), jnp.float32)
    layer = moe.MoELayer(was, "full_attention")
    params = jax.jit(layer.init)(jax.random.key(0), x, batch["positions"], batch["segment_ids"])
    assert set(nn.meta.unbox(params)["params"]["attn"]) == {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}
    out = [jax.jit(lambda p, x, c=c, kind=kind: moe.MoELayer(c, kind).apply(
        p, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"])[0])(params, x)
           for c, kind in ((was, "full_attention"), (named, "latent_attention"))]
    assert bool(jnp.array_equal(*out))
    assert moe.layer_plan(named.layer_kinds(), 0) == (("latent_attention",), 2, ())


@pytest.mark.parametrize("groups,kept", [(1, 1), (2, 1), (4, 2), (8, 4)])
def test_the_group_limited_router_against_the_reference(groups, kept):
    key = jax.random.key(groups)
    logits = jax.random.normal(key, (2, 64, 32), jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32,), jnp.float32)
    sel, weights = moe.sigmoid_route(logits, bias, 4, 2.5, 1e-20, groups, kept)
    sizes = dict(n_group=groups, topk_group=kept, top_k=4, routed_scaling=2.5)
    w_r = jnp.eye(32)  # m W_r are the logits themselves
    sel_ref, w_ref = reference.route(logits, w_r, bias, sizes)
    np.testing.assert_array_equal(jnp.sort(sel, -1), jnp.sort(sel_ref, -1))
    np.testing.assert_allclose(jnp.sort(weights, -1), jnp.sort(w_ref, -1), rtol=1e-6)
    of_group = np.sort(np.asarray(sel) // (32 // groups), axis=-1)
    assert int(((np.diff(of_group, axis=-1) != 0).sum(-1) + 1).max()) <= kept  # a token's experts lie in at most ``kept`` groups
    if groups == 1:  # the selection over all the experts, as it was, bit for bit
        scores = jax.nn.sigmoid(logits)
        _, was = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), 4)
        chosen = jnp.take_along_axis(scores, was, axis=-1)
        assert bool(jnp.array_equal(sel, was)) and bool(jnp.array_equal(weights, 2.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)))
    else:
        free, _ = moe.sigmoid_route(logits, bias, 4, 2.5)
        assert not bool(jnp.array_equal(jnp.sort(sel, -1), jnp.sort(free, -1)))  # the limit binds


def test_four_shares_of_four_add_up_to_the_uncut_reference_layer(tiny, seeded):
    """The guide's section 4, at 16 experts in 2 groups: the routed parts that
    all 4 shares of the layer give, with the shared expert counted once, are
    the uncut reference's whole layer, and every slot falls on exactly one share."""
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    e, held, d, f = sizes["n_experts"], sizes["held"], sizes["d_model"], sizes["moe_d_ff"]
    assert (e, held, sizes["n_group"]) == (16, 4, 2)
    key = jax.random.key(9)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *shape))
            for i, (n, shape) in enumerate({"gate": (d, f), "up": (d, f), "down": (f, d)}.items())}
    m = jax.random.normal(jax.random.fold_in(key, 9), (2, S, d), jnp.float32)
    base = layer_of(params, 1)["moe"]
    bias = jnp.asarray(pcfg.select_bias()[0])
    routed, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        block = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share, n_shared_experts=0))
        y, mods = block.apply({"params": {k: v for k, v in mine.items() if k != "shared"}}, m, bias, mutable=["intermediates"])
        routed = routed + y
        load.append(mods["intermediates"]["expert_load"][0])
    shared = transformer.MLPBlock(dataclasses.replace(pcfg, d_ff=f)).apply({"params": base["shared"]}, m)
    w = dict(group(leaves, 1), **{f"experts_{n}": a for n, a in full.items()})
    whole, slots = reference.expert_layer(m, w, bias, dict(sizes, held=e, offset=0))
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-5, atol=2e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * S * sizes["top_k"]


# ------------------------------------------------- the whole model and its step


@pytest.fixture(scope="module")
def first_step(tiny, batch, seeded):
    """Both sides' loss, logits, counts and gradient on the seeded weights, one program a side."""
    _cfg, _ref, sizes, _pcfg = tiny
    leaves, model, params = seeded

    def program(q):
        logits, mods = program_outputs(model, q, batch)
        return trainer_mod.lm_loss_fn(logits, batch), (logits, sown.step_counters(mods))

    def plain(q):
        loss, parts = reference.losses(q, batch, sizes)
        return loss, (reference.logits_of(q, batch, sizes), parts)

    return tuple(jax.jit(jax.value_and_grad(f, has_aux=True)) for f in (program, plain))


def test_logits_loss_slots_and_the_two_counters(tiny, batch, seeded, first_step):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    (loss, (logits, counters)), _ = first_step[0](params)
    (want_loss, (want, parts)), _ = first_step[1](leaves)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert set(counters) == {"moe_slots", "moe_slots_dropped", "moe_load_max_over_mean", "moe_rows_visited_share", "moe_combine_rows_share",
                             "conv_taps_masked_share", "kda_chunks_cut_share", "kda_log_decay_mean"}
    assert float(counters["moe_slots"]) == float(parts["slots"]) > 0 and float(counters["moe_slots_dropped"]) == 0
    cut, chunks = reference.chunks_cut(batch["segment_ids"], sizes["kda_chunk"])
    assert float(counters["kda_chunks_cut_share"]) == float(cut / chunks) == 0.25
    np.testing.assert_allclose(counters["kda_log_decay_mean"], parts["log_decay_mean"], rtol=1e-5)
    assert -5.0 < float(counters["kda_log_decay_mean"]) < 0.0
    np.testing.assert_allclose(counters["conv_taps_masked_share"], taps_zeroed(batch["segment_ids"]) / (2 * S * 4), rtol=1e-6)


def test_the_needed_operations_and_bytes_by_hand(tiny):
    _cfg, _ref, sizes, _pcfg = tiny
    d, h, dk, f, e, docs, slots = 64, 4, 16, 32, 16, sum(DOCS, []), 1000
    kda, mla = 5 * d * h * dk + 2 * d * h, d * h * 24 + d * 40 + 32 * h * 32 + d * h + h * 16 * d
    assert counts_ling.kda_params(sizes) == kda and counts_ling.mla_params(sizes) == mla
    per_token = 6 * kda + mla + 3 * d * 96 + 6 * (d * e + 3 * d * f) + d * 512
    assert counts_ling.matmul_params_per_token(sizes) == per_token
    pairs = sum(n * (n + 1) // 2 for n in docs)
    c = 16
    scan = int(2 * (c * dk + c * c / 6 + (c + 1) / 2 * 2 * dk + 3 * dk * dk + (c + 1) / 2 * dk) * h * 6 * sum(docs))
    assert counts_ling.kda_scan_flops_forward(sizes, sum(docs)) == scan and counts_ling.kda_scan_flops(sizes, 7) == 3 * counts_ling.kda_scan_flops_forward(sizes, 7)
    assert counts_ling.train_flops(sizes, docs, slots) == 3 * (2 * (per_token * sum(docs) + 3 * d * f * slots) + 2 * h * 40 * pairs + scan)
    assert counts_ling.kda_scan_bytes(sizes, 10) == (3 * (10 * dk + 4) + 4 * dk) * h * 6 * 10


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, seeded, first_step):
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

    p, r, gp, gr = params, leaves, [], []
    for _ in range(2):
        gp.append(first_step[0](p)[1])
        gr.append(first_step[1](r)[1])
        assert worst_gap(gp[-1], gr[-1])[0] < 3e-4, worst_gap(gp[-1], gr[-1])
        p, r = (jax.jit(lambda p0, gs: jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp), p0, *gs))(p0, gs)
                for p0, gs in ((params, gp), (leaves, gr)))
    change = lambda new, old: jax.tree.map(lambda a, b: a - b, new, old)
    assert worst_gap(change(p, params), change(r, leaves))[0] < 2e-3  # AdamW divides by the gradient's size
    for leaf in ("A_log", "dt_bias", "q_conv"):
        assert float(jnp.abs(gp[0]["layers_0"]["layer"]["kda"][leaf]).max()) > 0


def test_trainer_step_reports_the_counters_fit_publishes_the_gauges_and_the_event(tiny, batch):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    three = dict(n_layers=3, layer_types=("kda", "kda", "latent_attention"))  # the dense layer, a KDA and a latent one over experts
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing", **three))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name.startswith("kda."):
                seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    rec = Recorder(worker="t")
    with telemetry.current(rec):
        tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    assert {"loss", "kda_chunks_cut_share", "kda_log_decay_mean", "conv_taps_masked_share", "moe_slots"} <= set(out)
    assert out["kda_chunks_cut_share"] == seen["kda.chunks_cut_share"] == 0.25 and out["moe_slots_dropped"] == 0
    assert -5.0 < out["kda_log_decay_mean"] == seen["kda.log_decay_mean"] < 0.0
    events = [r["attrs"] for r in rec.flight if r.get("name") == "kda.kernel"]
    assert events and events[0] == {"chunk": 16, "head_dim": 16, "form": "xla"}


def test_the_five_scopes_of_a_kda_layer_forward_and_backward(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    x = jnp.zeros((2, S, sizes["d_model"]), jnp.float32)
    f = lambda x, p: transformer.KDA(pcfg).apply(
        {"params": p}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"])[0].sum()
    text = jax.jit(jax.grad(f, (0, 1))).lower(x, layer_of(params, 3)["kda"]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("kda.in_proj", "kda.conv", "kda.gate", "kda.scan", "kda.out"):
        assert any(scope in n for n in names), scope
        assert any(scope in n and "transpose" in n for n in names), scope  # the backward pass
    assert any("kda.scan" in n and "while" in n for n in names)  # the recurrence across chunks
