"""The head's product and the loss in blocks of the sequence
(``maggy_tpu/models/head.py``) against the losses over whole logits in
``train/trainer.py``: value and gradients with respect to hidden states and
kernel for each form of the objective, the rule that chooses the block, and
a dense train step of a model of each decoder class with the constants forced
small against the same step over whole logits.

Everything is float32 here, so what differs between the two sides is the order
of the sums (a block's partial sums, the weights divided by their count before
the sum and not after): a few float32 roundings of the compared quantity's
scale."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, run as bench_run  # noqa: E402
from maggy_tpu import models, telemetry  # noqa: E402
from maggy_tpu.models import head, sown, transformer  # noqa: E402
from maggy_tpu.parallel.mesh import make_mesh  # noqa: E402
from maggy_tpu.parallel.spec import ShardingSpec  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

TOL = dict(rtol=2e-5, atol=2e-7)
B, S, D, V = 2, 48, 16, 40


def batch_of(seq=S, vocab=V, *, mask=False, segments=False, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, vocab, (B, seq)), jnp.int32)}
    if mask:
        batch["loss_mask"] = jnp.asarray(rng.random((B, seq)) > 0.3, jnp.int32)
    if segments:  # two documents and a padded tail
        cuts = (seq // 2, seq - seq // 8)
        batch["segment_ids"] = jnp.asarray(
            np.repeat(np.select([np.arange(seq) < cuts[0], np.arange(seq) < cuts[1]], [1, 2], 0)[None], B, 0), jnp.int32
        )
    return batch


def operands(seq=S, vocab=V, heads=1, tied=False, seed=1):
    k_h, k_w = jax.random.split(jax.random.key(seed))
    hidden = jax.random.normal(k_h, (B, seq, D), jnp.float32)
    kernel = jax.random.normal(k_w, (vocab, D) if tied else (D, vocab * heads), jnp.float32) * 0.3
    return hidden, kernel


def logits_of(hidden, kernel, tied=False, softcap=0.0):
    logits = jnp.einsum("bsd,vd->bsv", hidden, kernel) if tied else hidden @ kernel
    return jnp.tanh(logits / softcap) * softcap if softcap else logits


# name: (batch's keys, ahead, rows a block, sequence, vocabulary, tied, softcap)
NEXT_TOKEN = {
    "plain_rows": ({}, 1, 16, S, V, False, 0.0),
    "loss_mask": ({"mask": True}, 1, 16, S, V, False, 0.0),
    "segment_ids": ({"segments": True}, 1, 16, S, V, False, 0.0),
    "mask_and_segments": ({"mask": True, "segments": True}, 1, 8, S, V, False, 0.0),
    "ahead_2": ({"segments": True}, 2, 16, S, V, False, 0.0),
    "tied_head": ({"mask": True}, 1, 16, S, V, True, 0.0),
    "logits_softcap": ({}, 1, 16, S, V, False, 5.0),
    "length_no_block_divides": ({"segments": True}, 1, 16, 41, V, False, 0.0),
    "vocabulary_37984": ({}, 1, 4, 12, 37984, False, 0.0),
}


@pytest.mark.parametrize("name", list(NEXT_TOKEN))
def test_blocks_give_lm_loss_fn_and_its_gradients(name):
    keys, ahead, rows, seq, vocab, tied, softcap = NEXT_TOKEN[name]
    batch, (hidden, kernel) = batch_of(seq, vocab, **keys), operands(seq, vocab, tied=tied)
    ids, weights = head.next_token(batch, ahead)

    def whole(h, w):
        return trainer_mod.lm_loss_fn(logits_of(h, w, tied, softcap), batch, ahead)

    def blocks(h, w):
        return head.loss(h, w, ids, weights, rows=rows, dtype=jnp.float32, tied=tied, softcap=softcap)

    assert "scan[" in str(jax.make_jaxpr(blocks)(hidden, kernel))
    (want, want_grads), (got, got_grads) = (jax.value_and_grad(f, (0, 1))(hidden, kernel) for f in (whole, blocks))
    np.testing.assert_allclose(got, want, **TOL)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-7 * float(jnp.abs(w).max()) + 1e-9)
    np.testing.assert_allclose(jax.jit(blocks)(hidden, kernel), want, **TOL)  # the pass that makes no gradient


def test_blocks_give_mtp_loss_of_eight_heads_and_its_gradients():
    """``Decoder``'s ``pred_heads`` columns: head ``i`` is a call on its slice of
    the kernel with the targets ``i + 1`` ahead."""
    heads, batch = 8, batch_of(segments=True)
    hidden, kernel = operands(heads=heads)

    def whole(h, w):
        logits = (h @ w).reshape(B, S, heads, V)
        mods = {"intermediates": {"mtp_logits": (logits[:, :, 1:],)}}
        return trainer_mod.lm_loss_fn(logits[:, :, 0], batch), trainer_mod.mtp_loss(mods, batch)

    def blocks(h, w):
        losses = jnp.stack([
            head.loss(h, w[:, i * V:(i + 1) * V], *head.next_token(batch, i + 1), rows=16, dtype=jnp.float32)
            for i in range(heads)
        ])
        return trainer_mod.data_losses(trainer_mod.lm_loss_fn, losses, {}, batch, in_head=True)

    for part in (0, 1):
        want, got = (jax.value_and_grad(lambda h, w: f(h, w)[part], (0, 1))(hidden, kernel) for f in (whole, blocks))
        np.testing.assert_allclose(got[0], want[0], **TOL)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-8)


@pytest.mark.parametrize("keys", [{}, {"mask": True}, {"segments": True}], ids=["every_position", "loss_mask", "segment_ids"])
def test_blocks_give_target_weighted_loss_without_shift(keys):
    batch, (hidden, kernel) = batch_of(**keys), operands()
    own = jax.random.uniform(jax.random.key(5), (B, S), jnp.float32) * 3.0
    ids, weights = head.own_token(batch)

    def whole(h, w, own):
        return trainer_mod.target_weighted_loss(h @ w, batch, own)

    def blocks(h, w, own):
        return head.loss(h, w, ids, weights * own, rows=16, dtype=jnp.float32)

    want, got = (jax.value_and_grad(f, (0, 1, 2))(hidden, kernel, own) for f in (whole, blocks))
    np.testing.assert_allclose(got[0], want[0], **TOL)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-8)


def test_one_block_is_whole_logits_and_autodiffs_backward():
    """Rows of the sequence's length or more: no loop and no gradient made in
    the forward pass, the float32 logits whole."""
    batch, (hidden, kernel) = batch_of(mask=True), operands()
    ids, weights = head.next_token(batch)

    def one(h, w):
        return head.loss(h, w, ids, weights, rows=S, dtype=jnp.float32)

    text = str(jax.make_jaxpr(jax.value_and_grad(one, (0, 1)))(hidden, kernel))
    assert "scan[" not in text and "while[" not in text and "custom_vjp" not in text and f"f32[{B},{S},{V}]" in text
    want = jax.value_and_grad(lambda h, w: trainer_mod.lm_loss_fn(h @ w, batch), (0, 1))(hidden, kernel)
    got = jax.value_and_grad(one, (0, 1))(hidden, kernel)
    np.testing.assert_allclose(got[0], want[0], **TOL)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-8)


# (batch, sequence, vocabulary, heads, mesh axes) -> rows: the eight cells' shapes on one chip, then meshes
RULE = {
    "mistral_8192x32768_whole": ((2, 4096, 32768, 1, {}), 4096),
    "lfm2_32768x8192_whole": ((4, 8192, 8192, 1, {}), 8192),
    "sdar_16384x18992_whole": ((2, 8192, 18992, 1, {}), 8192),
    "laguna_8192x12544_whole": ((1, 8192, 12544, 1, {}), 8192),
    "evabyte_16384x320_eight_heads_whole": ((1, 16384, 320, 8, {}), 16384),
    "glm_16384x19360_two_heads_blocks": ((2, 8192, 19360, 2, {}), 4096),
    "keye_32768x18992_blocks": ((1, 32768, 18992, 1, {}), 8192),
    "smallthinker_16384x37984_blocks": ((1, 16384, 37984, 1, {}), 4096),
    "published_vocabulary_151936": ((1, 16384, 151936, 1, {}), 1024),
    "sequence_sharded_mesh_one_block": ((1, 16384, 37984, 1, {"sp": 2}), 16384),
    "vocabulary_sharded_mesh_one_block": ((1, 16384, 37984, 1, {"tp": 2}), 16384),
    "batch_sharded_mesh_counts_a_device": ((8, 16384, 37984, 1, {"dp": 4}), 2048),
    "batch_sharded_mesh_whole_a_device": ((4, 4096, 37984, 1, {"dp": 2, "fsdp": 2}), 4096),
}


@pytest.mark.parametrize("name", list(RULE))
def test_block_rows_follow_the_float32_logits_bytes_a_device(name):
    (batch, seq, vocab, heads, axes), rows = RULE[name]
    mesh = make_mesh(ShardingSpec(**axes), devices=jax.devices()[: int(np.prod(list(axes.values()) or [1]))])
    assert head.block_rows(batch, seq, vocab, heads, mesh) == rows
    if not axes:
        assert head.block_rows(batch, seq, vocab, heads) == rows
    if rows < seq:
        assert -(-batch // mesh.size) * rows * vocab * 4 <= head.BLOCK_LOGITS_BYTES


def tiny_moe_with_mtp():
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.glm-4.7-flash.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load("benchmark/configs/glm-4.7-flash.json"), small)
    fields = configs.load_reference(cfg).program_fields(cfg, "train_packed_ref")
    return models.MoEDecoder(models.MoEConfig(**dict(fields, dtype=jnp.float32, remat=False, max_seq_len=S)))


def tiny_decoder():
    return models.Decoder(transformer.DecoderConfig(
        vocab_size=V, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=S, dtype=jnp.float32,
        pred_heads=3, logits_softcap=30.0,
    ))


@pytest.mark.parametrize("make", [tiny_moe_with_mtp, tiny_decoder], ids=["moe_decoder_with_mtp", "decoder_three_heads_softcap"])
def test_dense_train_step_in_blocks_gives_the_step_over_whole_logits(make, monkeypatch):
    model = make()
    mesh = make_mesh(ShardingSpec(), devices=jax.devices()[:1])
    batch = batch_of(vocab=model.cfg.vocab_size, mask=True, segments=True)
    batch["positions"] = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    steps = {}
    for form in ("whole", "blocks"):
        if form == "blocks":  # four blocks of 16 rows
            monkeypatch.setattr(head, "WHOLE_LOGITS_BYTES", 1)
            monkeypatch.setattr(head, "BLOCK_LOGITS_BYTES", B * 16 * model.cfg.vocab_size * 4)
        trainer = trainer_mod.Trainer(model, optax.adamw(1e-3), mesh)
        state = trainer.make_state(jax.random.key(0), batch)
        tel = telemetry.Telemetry(worker="t")
        with telemetry.current(tel), mesh:
            state, metrics = trainer.step(state, trainer.shard_batch(batch))
            evaluated = trainer.evaluate(state, iter([batch]), 1)["loss"]
        events = [e["attrs"] for e in tel.drain_events() if e["name"] == "loss.blocks"]
        steps[form] = dict({k: float(v) for k, v in metrics.items()}, evaluated=evaluated), state.params, events
    (whole, whole_params, whole_events), (blocks, blocks_params, blocks_events) = steps["whole"], steps["blocks"]
    assert {e["blocks"] for e in whole_events} == {1} and {e["backward"] for e in whole_events} == {"autodiff"}
    assert {(e["blocks"], e["rows"], e["backward"]) for e in blocks_events} == {(3, 16, "in_forward")}
    assert all(e["vocab"] == model.cfg.vocab_size and e["heads"] == len(model.cfg.head_aheads()) for e in blocks_events)
    assert set(whole) == set(blocks) and "mtp_loss" in whole
    for key in whole:
        np.testing.assert_allclose(blocks[key], whole[key], rtol=2e-5, err_msg=key)
    for got, want in zip(jax.tree_util.tree_leaves(blocks_params), jax.tree_util.tree_leaves(whole_params)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)  # one AdamW step of 1e-3 from equal weights


def test_a_loss_fn_of_the_users_and_a_bare_apply_still_get_logits(monkeypatch):
    monkeypatch.setattr(head, "WHOLE_LOGITS_BYTES", 1)
    model, mesh = tiny_decoder(), make_mesh(ShardingSpec(), devices=jax.devices()[:1])
    batch = batch_of(mask=True)
    seen = {}

    def own_loss(logits, batch):
        seen["logits"] = logits.shape
        return trainer_mod.lm_loss_fn(logits, batch)

    trainer = trainer_mod.Trainer(model, optax.adamw(1e-3), mesh, loss_fn=own_loss)
    state = trainer.make_state(jax.random.key(0), batch)
    with mesh:
        trainer.step(state, trainer.shard_batch(batch))
    assert seen["logits"] == (B, S, V)
    params = model.init(jax.random.key(0), batch["tokens"])["params"]
    logits, mods = model.apply({"params": params}, batch["tokens"], mutable=["intermediates"])
    assert logits.shape == (B, S, V) and sown.mtp_logits(mods).shape == (B, S, 2, V)
