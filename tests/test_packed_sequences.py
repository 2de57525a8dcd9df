"""Packed sequences (segment_ids) through every attention path
(SURVEY §5.7): blockwise, the XLA ring and Ulysses on the sp=4 mesh,
the Pallas flash kernel (interpret machine), and the Decoder/Trainer
end-to-end. The ground truth everywhere: packed attention over segments ==
dense attention run on each segment separately."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from maggy_tpu.models.transformer import default_attention
from maggy_tpu.ops.attention import blockwise_attention
from maggy_tpu.ops.flash import flash_attention
from maggy_tpu.parallel.ringattention import ring_attention
from maggy_tpu.parallel.ulysses import ulysses_attention

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the 8-device CPU mesh"
)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _packed(B=2, S=128, H=4, KH=2, D=16, n_segs=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    # contiguous segments with uneven boundaries
    bounds = np.sort(
        np.random.default_rng(seed).choice(
            np.arange(8, S - 8), size=n_segs - 1, replace=False
        )
    )
    seg_row = np.zeros(S, np.int32)
    for b in bounds:
        seg_row[b:] += 1
    seg = jnp.asarray(np.stack([seg_row, (seg_row + 1) % n_segs + 10]))[:B]
    return q, k, v, seg


def _segwise_dense(q, k, v, seg, causal=True):
    """Ground truth: dense attention run on each segment independently."""
    out = np.zeros(q.shape, np.float32)
    for b in range(q.shape[0]):
        for s in np.unique(np.asarray(seg[b])):
            idx = np.where(np.asarray(seg[b]) == s)[0]
            o = default_attention(
                q[b : b + 1, idx], k[b : b + 1, idx], v[b : b + 1, idx],
                causal=causal,
            )
            out[b, idx] = np.asarray(o)[0]
    return out


def test_blockwise_segment_parity():
    q, k, v, seg = _packed()
    ref = _segwise_dense(q, k, v, seg)
    out = blockwise_attention(q, k, v, causal=True, segment_ids=seg, block_k=32)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)
    # and default_attention's own segment mask agrees
    out2 = default_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out2), ref, atol=2e-5)


def test_xla_ring_segment_parity_sp4():
    q, k, v, seg = _packed()
    ref = _segwise_dense(q, k, v, seg)
    mesh = _mesh(4)
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, mesh=mesh, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def test_xla_ring_segment_grads_flow():
    """Cross-segment grads must be exactly zero; within-segment nonzero."""
    mesh = _mesh(2)
    q, k, v, seg = _packed(B=1, S=32, H=2, KH=2, D=8, n_segs=2, seed=1)

    def loss(q, k, v):
        out = ring_attention(q, k, v, mesh=mesh, causal=True, segment_ids=seg)
        # loss reads only segment-0 outputs
        m = (seg[0] == np.asarray(seg[0])[0]).astype(np.float32)
        return (out[0] * m[:, None, None] ** 1).sum()

    with jax.set_mesh(mesh):
        gk = jax.grad(loss, argnums=1)(q, k, v)
    seg0 = np.asarray(seg[0]) == np.asarray(seg[0])[0]
    assert float(jnp.abs(gk[0, ~seg0]).max()) == 0.0
    assert float(jnp.abs(gk[0, seg0]).max()) > 0.0


def test_ulysses_segment_parity_sp4():
    q, k, v, seg = _packed(H=4, KH=4)  # ulysses: n | H
    ref = _segwise_dense(q, k, v, seg)
    mesh = _mesh(4)
    with jax.set_mesh(mesh):
        out = ulysses_attention(
            q, k, v, mesh=mesh, causal=True, segment_ids=seg
        )
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def test_flash_kernel_segment_parity_and_grads():
    """The Pallas kernel path (interpret machine) with in-kernel segment
    masking: forward parity AND gradient parity vs the dense reference."""
    q, k, v, seg = _packed(B=2, S=64, H=4, KH=2, D=128, n_segs=2, seed=2)
    ref = _segwise_dense(q, k, v, seg)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=16, block_k=16,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, segment_ids=seg, block_q=16, block_k=16,
            interpret=True,
        )
        return (o * jnp.cos(o)).sum()

    def loss_dense(q, k, v):
        o = default_attention(q, k, v, causal=True, segment_ids=seg)
        return (o * jnp.cos(o)).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_moe_decoder_accepts_segment_ids():
    """The MoE family threads segment_ids to its attention like Decoder:
    output must differ from the unsegmented forward (the mask bites) and
    match a two-forward per-segment reference on the first segment."""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe()
    model = MoEDecoder(cfg)
    rng = np.random.default_rng(0)
    B, S = 2, 32
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    seg = np.zeros((B, S), np.int32)
    seg[:, S // 2 :] = 1
    params = model.init(jax.random.key(0), tokens)["params"]
    packed = model.apply({"params": params}, tokens, None, jnp.asarray(seg))
    plain = model.apply({"params": params}, tokens)
    assert not np.allclose(np.asarray(packed), np.asarray(plain), atol=1e-4)
    # first segment sees only itself: equals a forward on just that slice
    ref = model.apply({"params": params}, tokens[:, : S // 2])
    np.testing.assert_allclose(
        np.asarray(packed[:, : S // 2]), np.asarray(ref), atol=2e-2
    )


def test_decoder_trainer_packed_end_to_end():
    """Packed batch {tokens, positions, segment_ids} through the Trainer on
    the sp mesh: segment_ids reach ring attention, positions restart per
    segment, the LM loss skips boundary targets, and loss decreases."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.ringattention import make_ring_attention
    from maggy_tpu.parallel.spec import ShardingSpec
    from maggy_tpu.train import TrainContext

    ctx = TrainContext.create(ShardingSpec(sp=4, dp=2))
    cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
    rng = np.random.default_rng(0)
    B, S = 4, 64
    tokens = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    seg = np.zeros((B, S), np.int32)
    seg[:, S // 2 :] = 1  # two packed docs per row
    pos = np.concatenate(
        [np.arange(S // 2), np.arange(S - S // 2)]
    )[None].repeat(B, 0).astype(np.int32)
    batch = {"tokens": tokens, "positions": pos, "segment_ids": seg}

    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-2))
    state = trainer.make_state(jax.random.key(0), batch)
    losses = []
    for _ in range(5):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_fit_gauges_the_share_of_flash_tiles_a_packed_batch_needs():
    """``fit`` records ``attention.tiles_visited_share`` for every packed host
    batch, in the prefetcher's thread: 3 of the 4 tiles of a 2 x 2 grid for one
    document a row, the 2 diagonal ones for two documents that fill a tile each.
    A batch with no segment ids records nothing."""
    import threading

    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.spec import ShardingSpec
    from maggy_tpu.train import TrainContext

    ctx = TrainContext.create(ShardingSpec(dp=2), devices=jax.devices()[:2])
    cfg = DecoderConfig.tiny()
    B, S = 2, 1024  # the automatic tiles at 1,024 are 512 x 512
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)

    def batch(seg):
        return {
            "tokens": tokens, "segment_ids": seg,
            "positions": np.arange(S, dtype=np.int32)[None].repeat(B, 0) % 512,
        }

    one = np.ones((B, S), np.int32)
    two = np.repeat(np.array([1, 2], np.int32), S // 2)[None].repeat(B, 0)
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
    state = trainer.make_state(jax.random.key(0), batch(one))

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name == "attention.tiles_visited_share":
                shares.append((value, threading.current_thread().name))
            super().gauge(name, value)

    shares = []
    with telemetry.current(Recorder(worker="t")):
        state, _ = trainer.fit(state, iter([batch(one), batch(two)]), num_steps=2)
        assert [v for v, _ in shares] == [0.75, 0.5]
        assert {t for _, t in shares} == {"maggy-device-prefetch"}
        del shares[:]
        trainer2 = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
        plain = {"tokens": tokens}
        trainer2.fit(trainer2.make_state(jax.random.key(0), plain), iter([plain]), num_steps=1)
        assert shares == []


def test_packed_side_inputs_seq_sharded_no_remat(capfd):
    """On an sp mesh the packed side inputs must be
    PLACED (batch, seq) by shard_batch, so XLA never has to involuntarily
    rematerialize them per step. Oracle: XLA's own 'Involuntary full
    rematerialization' SPMD warning — absent with the trainer's placement,
    present (positive control) when the same inputs are forced batch-only."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.ringattention import make_ring_attention
    from maggy_tpu.parallel.spec import ShardingSpec
    from maggy_tpu.train import TrainContext

    # the warning fires at partition time only — a persistent-cache hit
    # would silently skip it and blind both arms of the test
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        B, S = 4, 128
        ctx = TrainContext.create(ShardingSpec(fsdp=2, sp=4))
        cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
        trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
        rng = np.random.default_rng(0)
        seg = np.zeros((B, S), np.int32)
        seg[:, S // 2:] = 1
        pos = (
            np.concatenate([np.arange(S // 2), np.arange(S - S // 2)])[None]
            .repeat(B, 0)
            .astype(np.int32)
        )
        batch = {
            "tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
            "positions": pos,
            "segment_ids": seg,
        }
        state = trainer.make_state(jax.random.key(0), batch)
        step = trainer._build_train_step()

        sb = trainer.shard_batch(batch)
        assert sb["segment_ids"].sharding.spec == P(("data", "fsdp"), "seq")
        assert sb["positions"].sharding.spec == P(("data", "fsdp"), "seq")

        capfd.readouterr()  # drain
        with trainer.mesh:
            step.lower(state, sb).compile()
        err = capfd.readouterr().err
        assert "Involuntary full rematerialization" not in err, err[-1500:]

        # positive control: the batch-only placement this replaced DOES trip
        # the warning — proving the oracle detects the regression
        bo = NamedSharding(trainer.mesh, P(("data", "fsdp")))
        sb_old = dict(sb)
        sb_old["segment_ids"] = jax.device_put(seg, bo)
        sb_old["positions"] = jax.device_put(pos, bo)
        with trainer.mesh:
            step.lower(state, sb_old).compile()
        err = capfd.readouterr().err
        assert "Involuntary full rematerialization" in err

        # numerics are placement-independent (fresh states: step donates)
        _, m_new = trainer.step(state, sb)
        state2 = trainer.make_state(jax.random.key(0), batch)
        _, m_old = trainer.step(state2, sb_old)
        assert abs(float(m_new["loss"]) - float(m_old["loss"])) < 1e-5
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_padded_packed_row_needs_loss_mask():
    """ADVICE r4 / docs 'Padding convention': a trailing pad region that
    shares a segment id still attends within itself and contributes
    next-token loss — `loss_mask` is what removes it. Locks both facts: the
    unmasked padded loss differs from the true loss; the masked one matches
    the unpadded row exactly."""
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train.trainer import lm_loss_fn

    cfg = DecoderConfig.tiny()
    rng = np.random.default_rng(2)
    S, PAD = 24, 8
    doc = rng.integers(1, cfg.vocab_size, S).astype(np.int32)
    model = Decoder(cfg)
    variables = model.init(jax.random.key(1), jnp.asarray(doc[None]))

    # unpadded reference
    jb_ref = {"tokens": jnp.asarray(doc[None])}
    ref = float(lm_loss_fn(model.apply(variables, jb_ref["tokens"]), jb_ref))

    # padded row: pad gets its OWN segment id (so it cannot attend into the
    # document), but without a loss_mask its intra-pad targets still count
    padded = np.concatenate([doc, np.zeros(PAD, np.int32)])
    seg = np.concatenate([np.zeros(S), np.ones(PAD)]).astype(np.int32)
    pos = np.concatenate([np.arange(S), np.arange(PAD)]).astype(np.int32)
    jb = {
        "tokens": jnp.asarray(padded[None]),
        "segment_ids": jnp.asarray(seg[None]),
        "positions": jnp.asarray(pos[None]),
    }
    logits = model.apply(variables, jb["tokens"], jb["positions"], jb["segment_ids"])
    unmasked = float(lm_loss_fn(logits, jb))
    assert abs(unmasked - ref) > 1e-3  # pad leaks into the objective

    mask = np.concatenate([np.ones(S), np.zeros(PAD)]).astype(np.float32)
    masked = float(lm_loss_fn(logits, {**jb, "loss_mask": jnp.asarray(mask[None])}))
    np.testing.assert_allclose(masked, ref, atol=2e-3)  # mask restores truth
