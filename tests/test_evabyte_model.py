"""``evabyte`` as a whole model against its plain reference
``benchmark/references/eva_dense.py`` on seeded weights at small sizes
(``benchmark/checks/tiny.evabyte.json``): the attention layer through the XLA
path and through the interpreted kernels, the eight heads' logits, both
losses, every leaf's gradient, two AdamW steps, what a document cannot see,
and the trainer's step with its counters and gauges. The kernels, the masks
and the refusals are ``test_evabyte_attention.py``'s, whose helpers and
fixtures this file shares."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_evabyte_attention import DOCS, KIND, S, batch, load, packed, program_outputs, seeded, tiny  # noqa: F401  (fixtures by name)

from benchmark import counts_evabyte
from benchmark.references import eva_dense as reference
from benchmark.references.decoder import adamw_apply
from maggy_tpu.models import sown, transformer
from maggy_tpu.ops import eva
from maggy_tpu.train import trainer as trainer_mod


# --------------------------------------------------------------- the layer


@pytest.mark.parametrize("layer", [0, 1])
def test_attention_layer_against_the_reference(tiny, batch, seeded, layer):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    attn = params[f"layers_{layer}"]["layer"]["attn"]
    assert attn["wq"]["kernel"].shape == (64, 4, 16) and attn["eva_phi"].shape == (4, 16)
    x = jax.random.normal(jax.random.key(4), (2, S, sizes["d_model"]), jnp.float32)
    got, mods = transformer.Attention(pcfg, "eva_attention").apply(
        {"params": attn}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )
    w = reference.layer_params(leaves, layer)
    want = reference.attention(x, w, batch["positions"], batch["segment_ids"], sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for fault in ("no_summaries", "summaries_cross_documents"):  # each planted fault is another layer
        other = reference.attention(x, w, batch["positions"], batch["segment_ids"], sizes, {"fault": fault})
        assert float(jnp.abs(other - want).max()) > 1e-3, fault
    (remote, local), (cut, chunks) = reference.seen_entries(batch, sizes)
    np.testing.assert_array_equal(mods["intermediates"]["eva_counts"][0], [remote, remote + local, cut, chunks])


def test_attention_layer_on_the_interpreted_kernels_against_the_reference(monkeypatch):
    """Heads of 64, which the kernels tile: the layer's output and the gradient
    of every leaf, ``phi`` and ``mu`` among them, with the dispatch steered onto
    ``ops.eva.eva_attention`` in the interpreter."""
    sizes = dict(d_model=128, n_heads=2, head_dim=64, window=64, chunk=8, rope_theta=1e5, norm_eps=1e-5)
    pcfg = transformer.DecoderConfig(
        d_model=128, n_heads=2, n_kv_heads=2, n_layers=1, layer_types=("eva_attention",), eva_window=64, eva_chunk=8,
        rope_theta=1e5, dtype=jnp.float32, max_seq_len=256,
    )
    taken = []

    def on_kernels(q, k, v, ks, vs, *, segment_ids, window, chunk):
        taken.append((window, chunk))
        return eva.eva_attention(q, k, v, ks, vs, segment_ids, window=window, chunk=chunk, interpret=True)

    monkeypatch.setattr(transformer, "auto_eva_attention", on_kernels)
    rows = packed([[37, 113, 51], [100, 156]], np.random.default_rng(1), s=256)
    keys = jax.random.split(jax.random.key(7), 8)
    w = {n: 0.05 * jax.random.normal(k, (128, 128)) for n, k in zip(("wq", "wk", "wv", "wo"), keys)}
    w.update(phi=0.5 * jax.random.normal(keys[4], (2, 64)), mu=0.5 * jax.random.normal(keys[5], (2, 64)))
    x, g = (jax.random.normal(k, (2, 256, 128), jnp.float32) for k in keys[6:])

    def program(w):
        params = {n: {"kernel": w[n].reshape(128, 2, 64)} for n in ("wq", "wk", "wv")}
        params.update(wo={"kernel": w["wo"].reshape(2, 64, 128)}, eva_phi=w["phi"], eva_mu=w["mu"])
        out, _ = transformer.Attention(pcfg, "eva_attention").apply(
            {"params": params}, x, rows["positions"], rows["segment_ids"], mutable=["intermediates"]
        )
        return out

    plain = lambda w: reference.attention(x, w, rows["positions"], rows["segment_ids"], sizes)
    np.testing.assert_allclose(program(w), plain(w), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda w: (program(w) * g).sum())(w)
    want = jax.grad(lambda w: (plain(w) * g).sum())(w)
    assert taken and set(taken) == {(64, 8)}
    for n in w:
        assert float(jnp.abs(want[n]).max()) > 1e-2, n
        np.testing.assert_allclose(got[n], want[n], rtol=5e-4, atol=5e-5 * float(jnp.abs(want[n]).max()), err_msg=n)


# ------------------------------------------------- the whole model and its step


def test_eight_heads_logits_both_losses_and_the_counters(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    logits, mods = jax.jit(lambda p: program_outputs(model, p, batch))(params)
    want = jax.jit(lambda p: reference.logits_of(p, batch, sizes))(leaves)
    assert want.shape == (2, S, 8, 320) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want[:, :, 0], rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(mods["intermediates"]["mtp_logits"][0], want[:, :, 1:], rtol=2e-4, atol=5e-5)
    total, parts = jax.jit(lambda p: reference.losses(p, batch, sizes))(leaves)
    main, mtp = trainer_mod.lm_loss_fn(logits, batch), trainer_mod.mtp_loss(mods, batch)
    np.testing.assert_allclose(main, parts["main"], rtol=1e-5)
    np.testing.assert_allclose(mtp, parts["mtp"], rtol=1e-5)
    np.testing.assert_allclose(main + pcfg.mtp_weight * mtp, total, rtol=1e-5)  # the sum of the eight
    counters = sown.step_counters(mods)
    (remote, local), (cut, chunks) = reference.seen_entries(batch, sizes)
    assert remote > 0 and cut == 4 and chunks == 2 * S // 4  # at 45 and 115 (the padding's start), at 19 and 109: all inside chunks
    np.testing.assert_allclose(counters["eva_remote_share"], remote / (remote + local), rtol=1e-6)
    np.testing.assert_allclose(counters["eva_chunks_cut_share"], cut / chunks, rtol=1e-6)
    docs = [n for row in DOCS for n in row]
    assert counts_evabyte.entries(docs, sizes) == (remote, local)
    assert counts_evabyte.rows_of(docs, S) == DOCS
    assert sown.step_counters({}) == {}


def test_a_later_byte_or_another_document_changes_no_logit_bit(tiny, batch, seeded):
    _cfg, _ref, _sizes, _pcfg = tiny
    _leaves, model, params = seeded
    run = jax.jit(lambda tokens: program_outputs(model, params, dict(batch, tokens=tokens)))
    base, base_mods = run(batch["tokens"])
    tokens = np.asarray(batch["tokens"]).copy()
    tokens[1, :19] = (tokens[1, :19] + 7) % 319 + 1  # row 1: the document before the one of 90 (19..108)
    tokens[1, 80:] = (tokens[1, 80:] + 11) % 319 + 1  # and everything from its position 61 on
    moved, moved_mods = run(jnp.asarray(tokens))
    np.testing.assert_array_equal(moved[1, 19:80], base[1, 19:80])
    np.testing.assert_array_equal(moved[0], base[0])
    further = lambda m: m["intermediates"]["mtp_logits"][0]
    np.testing.assert_array_equal(further(moved_mods)[1, 19:80], further(base_mods)[1, 19:80])
    assert float(jnp.abs(moved[1, 80:109] - base[1, 80:109]).max()) > 1e-3


def test_gradient_of_every_leaf_and_the_change_after_two_steps(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's; then two AdamW steps on both sides from those gradients."""
    cfg, ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    hp = cfg[KIND]["optimizer"]

    def worst_gap(got, want):
        got = {ref.to_reference(n): a for n, a in ref.named_leaves(got).items()}
        got = {n: np.asarray(a).reshape(want[n].shape) for n, a in got.items()}
        assert set(got) == set(want)
        norms = {n: float(np.linalg.norm(a)) for n, a in want.items()}
        assert all(v > 0 for v in norms.values())
        floor = float(np.median(list(norms.values())))
        return max((float(np.linalg.norm(got[n] - want[n])) / max(norms[n], floor), n) for n in want)

    def program_loss(q):
        logits, mods = program_outputs(model, q, batch)
        return trainer_mod.lm_loss_fn(logits, batch) + pcfg.mtp_weight * trainer_mod.mtp_loss(mods, batch)

    program_grad = jax.jit(jax.grad(program_loss))
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
    one_head = jax.jit(jax.grad(lambda q: reference.losses(q, batch, sizes, {"fault": "one_head"})[0]))(leaves)
    assert float(jnp.abs(one_head["lm_head"][:, 320:]).max()) == 0.0 and float(jnp.abs(gr[0]["lm_head"][:, 320:]).max()) > 0
    for n in ("l0.phi", "l1.mu"):
        assert float(jnp.abs(gr[0][n]).max()) > 0, n


def test_the_scanned_stack_is_the_unrolled_one(tiny, batch, seeded):
    """The cell unrolls its layers (``scan_layers`` false: PERF.md section 4 says
    what the scan cost in memory); under the scan the same weights, stacked,
    give the same logits."""
    _cfg, _ref, _sizes, pcfg = tiny
    _leaves, model, params = seeded
    layers = [params[f"layers_{i}"] for i in range(pcfg.n_layers)]
    stacked = {k: v for k, v in params.items() if not k.startswith("layers_")}
    stacked["layers"] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    scanned = transformer.Decoder(dataclasses.replace(pcfg, scan_layers=True))
    got, mods = program_outputs(scanned, stacked, batch)
    want, _ = program_outputs(model, params, batch)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert mods["intermediates"]["layers"]["layer"]["attn"]["eva_counts"][0].shape == (pcfg.n_layers, 4)


def test_two_fit_steps_against_the_references_and_the_gauges(tiny, batch, seeded):
    """``Trainer.fit`` from the seeded weights, recomputing every layer, beside
    ``eva_dense.train_steps``: both losses of both steps, and the step's
    counters as gauges."""
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    cfg, ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    hp = cfg[KIND]["optimizer"]
    model = transformer.Decoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name.startswith("attention."):
                seen[name] = value
            super().gauge(name, value)

    host = {k: np.asarray(v) for k, v in batch.items()}
    want = reference.train_steps(lambda n: leaves[n], list(leaves), [batch, batch], sizes, hp)
    with telemetry.current(Recorder(worker="t")):
        opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], weight_decay=hp["weight_decay"])
        tr = trainer_mod.Trainer(model, opt, make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        boxed, treedef = jax.tree_util.tree_flatten(state.params)  # the state's leaves carry their logical axes
        seeded_leaves = [jnp.asarray(b, a.dtype) for a, b in zip(boxed, jax.tree_util.tree_leaves(params))]
        state = state.replace(params=jax.tree_util.tree_unflatten(treedef, seeded_leaves))
        outs = []
        for _ in range(2):
            state, out = tr.fit(state, iter([host]), num_steps=1)
            outs.append(out)
    for out, loss, mtp in zip(outs, want["loss"], want["mtp_loss"]):
        assert out["loss"] == pytest.approx(loss, rel=2e-5) and out["mtp_loss"] == pytest.approx(mtp, rel=2e-5)
        assert out["total_loss"] == pytest.approx(out["loss"] + 7 * out["mtp_loss"], rel=1e-6)
    assert want["loss"][1] < want["loss"][0] and want["slots"] == [0, 0]
    (remote, local), (cut, chunks) = reference.seen_entries(batch, sizes)
    assert outs[-1]["eva_remote_share"] == pytest.approx(remote / (remote + local), rel=1e-6)
    assert outs[-1]["eva_chunks_cut_share"] == pytest.approx(cut / chunks, rel=1e-6)
    assert seen["attention.eva_remote_share"] == outs[-1]["eva_remote_share"]
    assert seen["attention.eva_chunks_cut_share"] == outs[-1]["eva_chunks_cut_share"]
    visited = eva.tiles_visited_share(host["segment_ids"], window=32, chunk=4, head_dim=16)
    assert seen["attention.tiles_visited_share"] == pytest.approx(visited) and 0 < visited <= 1
