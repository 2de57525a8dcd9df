"""Trainer-integrated pipeline parallelism: a mesh with
stage>1 must actually train the real Decoder under 1F1B — same numbers as the
dense path — or raise loudly, never silently replicate the stage axis."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.train import TrainContext
from maggy_tpu.train.trainer import lm_loss_fn

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


def _batch(cfg, bsz=8, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, cfg.vocab_size, (bsz, seq)).astype(np.int32)}


def test_pp_trainer_matches_dense_loss_and_grads():
    """pp=2 1F1B step == dense jax.grad on the same params (loss and grads,
    compared through unstack)."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)

    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-2))
    trainer.n_microbatches = 2
    state = trainer.make_state(jax.random.key(0), batch)

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))

    model = Decoder(cfg)

    def dense_loss(params):
        return lm_loss_fn(model.apply({"params": params}, batch["tokens"]), batch)

    ref_loss, ref_grads = jax.value_and_grad(dense_loss)(dense_params)

    new_state, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-3

    # grads: recover from the sgd update (p_new = p - lr * g)
    got = jax.jit(parts.unstack)(new_state.params)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_grads)
    flat_old = dict(jax.tree_util.tree_leaves_with_path(dense_params))
    flat_new = dict(jax.tree_util.tree_leaves_with_path(jax.device_get(got)))
    for path, g_ref in flat_ref:
        g_got = (flat_old[path] - flat_new[path]) / 1e-2
        np.testing.assert_allclose(
            np.asarray(g_got), np.asarray(g_ref), atol=5e-2,
            err_msg=jax.tree_util.keystr(path),
        )


def test_pp_trainer_loss_decreases_and_eval_matches():
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)
    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-2))
    trainer.n_microbatches = 2
    state = trainer.make_state(jax.random.key(0), batch)
    losses = []
    for _ in range(5):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]

    # eval path under pp: unstacked apply equals the stage-stacked state
    parts = trainer._pipeline_parts()
    dense_params = jax.jit(parts.unstack)(state.params)
    ref = Decoder(cfg).apply({"params": dense_params}, jnp.asarray(batch["tokens"]))
    got = trainer.eval_logits(state, trainer.shard_batch(batch))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.device_get(ref)), atol=1e-4
    )


def test_pp_four_stages():
    """4 stages x 2 dp on the deeper tiny config; restack round-trips."""
    cfg = DecoderConfig.tiny(n_layers=4)
    batch = _batch(cfg, bsz=8)
    ctx = TrainContext.create(ShardingSpec(pp=4, dp=2))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-2))
    trainer.n_microbatches = 4
    state = trainer.make_state(jax.random.key(1), batch)
    state, m = trainer.step(state, trainer.shard_batch(batch))
    assert np.isfinite(float(m["loss"]))

    parts = trainer._pipeline_parts()
    stacked = jax.jit(parts.restack)(jax.jit(parts.unstack)(state.params))
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(jax.device_get(stacked)),
        jax.tree_util.tree_leaves_with_path(jax.device_get(state.params)),
    ):
        assert pa == pb
        if "embedding" in jax.tree_util.keystr(pa) or "final_norm" in jax.tree_util.keystr(pa) or "lm_head" in jax.tree_util.keystr(pa):
            continue  # broadcast leaves only round-trip their owning stage
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


def test_pp_loss_mask_matches_dense_weighting():
    """Uneven loss_mask density across microbatches: the pp step must report
    the dense path's global mask-weighted mean, not an average of
    per-microbatch masked means (which would up-weight sparse microbatches)."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)
    mask = np.zeros_like(batch["tokens"])
    mask[:2] = 1          # dense rows in microbatch 0
    mask[2:, :3] = 1      # sparse rows elsewhere
    batch["loss_mask"] = mask

    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    ref = lm_loss_fn(
        Decoder(cfg).apply({"params": dense_params}, jnp.asarray(batch["tokens"])),
        {k: jnp.asarray(v) for k, v in batch.items()},
    )
    _, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - float(ref)) < 2e-3


def test_pp_packed_sequences_match_dense():
    """Packed batch (segment_ids + per-segment positions) under pp=2: the
    1F1B loss must equal dense jax.grad's on the same params — side inputs
    reach every stage through the raw channel stream. Segmentation is
    UNEVEN across microbatches (rows 0-3: four segments; rows 4-7: one) so
    a per-microbatch masked-mean average — different denominators — would
    diverge from the dense global masked mean."""
    cfg = DecoderConfig.tiny()
    B, S = 8, 32
    rng = np.random.default_rng(0)
    seg = np.zeros((B, S), np.int32)
    for i, b in enumerate((8, 16, 24)):  # rows 0-3: 4 segments
        seg[:4, b:] = i + 1
    pos4 = np.concatenate([np.arange(8)] * 4)
    pos1 = np.arange(S)
    pos = np.stack([pos4] * 4 + [pos1] * 4).astype(np.int32)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "positions": pos,
        "segment_ids": seg,
    }

    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-1), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    # train a few packed steps first: at init every loss is ~ln(V), so a
    # broken segment path would be indistinguishable — trained params are
    # segment-sensitive
    for _ in range(5):
        state, _ = trainer.step(state, trainer.shard_batch(batch))

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    ref_packed = lm_loss_fn(
        Decoder(cfg).apply(
            {"params": dense_params}, jb["tokens"], jb["positions"], jb["segment_ids"]
        ),
        jb,
    )
    ref_plain = lm_loss_fn(
        Decoder(cfg).apply({"params": dense_params}, jb["tokens"]),
        {"tokens": jb["tokens"]},
    )
    # the mask demonstrably matters at these params...
    assert abs(float(ref_packed) - float(ref_plain)) > 1e-3
    # ...and the pp step's loss matches the dense PACKED reference
    _, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - float(ref_packed)) < 2e-3


def test_pp_moe_decoder_trains_with_router_aux():
    """MoEDecoder under pp=2: per-stage router aux losses join the
    objective at each stage's backward tick — total loss matches the dense
    trainer's (loss + aux) on the same params, and training decreases it."""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe()
    batch = _batch(cfg, bsz=8, seq=16)

    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(MoEDecoder(cfg), optax.sgd(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    parts = trainer._pipeline_parts()
    assert parts.stage_has_aux
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))

    # dense reference: loss + summed router aux (the Trainer's dense path)
    from maggy_tpu.models.sown import collect_aux_losses

    model = MoEDecoder(cfg)
    logits, mods = model.apply(
        {"params": dense_params}, jnp.asarray(batch["tokens"]),
        mutable=["intermediates"],
    )
    ref_loss = float(lm_loss_fn(logits, batch))
    ref_aux = float(collect_aux_losses(mods))
    assert ref_aux > 0

    state, metrics = trainer.step(state, trainer.shard_batch(batch))
    # pp reports the SAME metric semantics as the dense path. aux matches
    # approximately: balancing statistics are means over each microbatch's
    # routing groups, the dense pass computes them over the full batch
    assert abs(float(metrics["loss"]) - ref_loss) < 2e-3
    assert abs(float(metrics["aux_loss"]) - ref_aux) < 1e-3
    assert float(metrics["aux_loss"]) > 0
    assert abs(
        float(metrics["total_loss"]) - (ref_loss + ref_aux)
    ) < 3e-3
    losses = [float(metrics["total_loss"])]
    for _ in range(4):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        losses.append(float(m["total_loss"]))
    assert losses[-1] < losses[0]


def test_convert_pipeline_state_across_pp_degrees():
    """A pp=2 TrainState (params + adam mu/nu) re-staged to pp=4 must train
    identically: step the converted state and compare the loss with a fresh
    pp=4 state built from the same canonical params (checkpoint portability,
    SURVEY §5.4)."""
    from maggy_tpu.train.pipeline_adapter import convert_pipeline_state

    cfg = DecoderConfig.tiny(n_layers=4)
    batch = _batch(cfg, bsz=8)

    ctx2 = TrainContext.create(ShardingSpec(pp=2, dp=4))
    tr2 = ctx2.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state2 = tr2.make_state(jax.random.key(5), batch)
    state2, m2 = tr2.step(state2, tr2.shard_batch(batch))  # warm adam state

    ctx4 = TrainContext.create(ShardingSpec(pp=4, dp=2))
    tr4 = ctx4.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=4)
    parts2, parts4 = tr2._pipeline_parts(), tr4._pipeline_parts()
    converted = convert_pipeline_state(jax.device_get(state2), parts2, parts4)
    # params round-trip exactly through the re-staging
    np.testing.assert_allclose(
        np.asarray(parts4.unstack(converted.params)["embedding"]),
        np.asarray(jax.device_get(jax.jit(parts2.unstack)(state2.params))["embedding"]),
        atol=0,
    )
    # adopt_state computes shardings from shapes alone (no throwaway init),
    # rebinds the static fields, and places every leaf
    state4 = tr4.adopt_state(converted, batch)
    state4, m4 = tr4.step(state4, tr4.shard_batch(batch))
    # same params + same batch -> same loss on the next step, any pp degree
    state2b, m2b = tr2.step(state2, tr2.shard_batch(batch))
    assert abs(float(m4["loss"]) - float(m2b["loss"])) < 2e-3


def test_pp_raises_loudly_for_unsupported():
    import flax.linen as nn

    class NotADecoder(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)

    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(NotADecoder(), optax.sgd(1e-2))
    with pytest.raises(ValueError, match="Decoder"):
        trainer.make_state(jax.random.key(0), {"inputs": np.zeros((8, 4), np.float32)})

    # pp x sp: a seq-ring collective inside the 1F1B schedule's per-stage
    # lax.cond deadlocks (non-uniform predicate) — refuse loudly
    # (pp x tp and pp x ep ARE supported — see test_pp_tp_* / test_pp_ep_*)
    ctx2 = TrainContext.create(ShardingSpec(pp=2, dp=2, sp=2))
    tr2 = ctx2.trainer(Decoder(cfg), optax.sgd(1e-2))
    with pytest.raises(ValueError, match="does not compose with sp"):
        tr2.make_state(jax.random.key(0), batch)

    # layer count must split evenly into stages
    ctx3 = TrainContext.create(ShardingSpec(pp=4, dp=2))
    tr3 = ctx3.trainer(Decoder(DecoderConfig.tiny(n_layers=2)), optax.sgd(1e-2))
    with pytest.raises(ValueError, match="divisible"):
        tr3.make_state(jax.random.key(0), batch)

    # tied embeddings would silently untie across stages
    ctx4 = TrainContext.create(ShardingSpec(pp=2, dp=4))
    tr4 = ctx4.trainer(
        Decoder(DecoderConfig.tiny(tie_embeddings=True)), optax.sgd(1e-2)
    )
    with pytest.raises(ValueError, match="tie_embeddings"):
        tr4.make_state(jax.random.key(0), batch)

    # microbatch rows must shard over data x fsdp: clear error, not shard_map's
    ctx5 = TrainContext.create(ShardingSpec(pp=2, dp=4))
    tr5 = ctx5.trainer(Decoder(cfg), optax.sgd(1e-2), n_microbatches=4)
    state5 = tr5.make_state(jax.random.key(0), batch)  # bsz=8 -> mb=2 < dpf=4
    with pytest.raises(ValueError, match="microbatches"):
        tr5.step(state5, tr5.shard_batch(batch))


def test_pp_tp_matches_dense_loss_and_grads():
    """pp=2 x tp=2 x dp=2: stage params carry
    tensor-sharded dims (attn heads / mlp hidden / vocab — the model's own
    logical axes resolved through the Trainer rules), the pipeline shard_map
    stays manual over stage/data/fsdp with `tensor` in GSPMD-auto mode, and
    the 1F1B step matches dense jax.grad on the same params."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)

    ctx = TrainContext.create(ShardingSpec(pp=2, tp=2, dp=2))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)

    # placement: heads/mlp/vocab dims really sit on the tensor axis
    specs = {
        jax.tree_util.keystr(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    assert specs["['embedding']"] == jax.sharding.PartitionSpec(
        "stage", "tensor", None
    )
    assert "tensor" in specs["['layers']['layer']['attn']['wq']['kernel']"]
    assert "tensor" in specs["['layers']['layer']['mlp']['w_gate']['kernel']"]
    assert "tensor" in specs["['lm_head']['kernel']"]

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    model = Decoder(cfg)

    def dense_loss(params):
        return lm_loss_fn(model.apply({"params": params}, batch["tokens"]), batch)

    ref_loss, ref_grads = jax.value_and_grad(dense_loss)(dense_params)

    new_state, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-3

    got = jax.device_get(jax.jit(parts.unstack)(new_state.params))
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_grads)
    flat_old = dict(jax.tree_util.tree_leaves_with_path(dense_params))
    flat_new = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, g_ref in flat_ref:
        g_got = (flat_old[path] - flat_new[path]) / 1e-2
        np.testing.assert_allclose(
            np.asarray(g_got), np.asarray(g_ref), atol=5e-2,
            err_msg=jax.tree_util.keystr(path),
        )


def test_pp_tp_trains_and_eval_matches():
    """pp x tp under adamw decreases the loss; eval_logits through the
    unstacked model matches a host-side dense apply (bf16 reduction-order
    tolerance: tensor-partitioned einsums reduce in a different order)."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)
    ctx = TrainContext.create(ShardingSpec(pp=2, tp=2, dp=2))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    losses = []
    for _ in range(4):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    ref = Decoder(cfg).apply({"params": dense_params}, jnp.asarray(batch["tokens"]))
    got = trainer.eval_logits(state, trainer.shard_batch(batch))
    np.testing.assert_allclose(
        np.asarray(jax.device_get(got)), np.asarray(jax.device_get(ref)), atol=3e-2
    )


def test_pp_tp_moe_trains():
    """MoEDecoder under pp x tp: expert FFN hidden dims tensor-shard inside
    each stage; router aux still joins per stage."""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe()
    batch = _batch(cfg, bsz=8, seq=16)
    ctx = TrainContext.create(ShardingSpec(pp=2, tp=2, dp=2))
    trainer = ctx.trainer(MoEDecoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(1), batch)
    losses = []
    for _ in range(3):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        losses.append(float(m["total_loss"]))
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]
    assert float(m["aux_loss"]) > 0


def test_pp_pipelined_eval_loss_bounded_memory():
    """evaluate() under pp computes the loss THROUGH the
    pipeline stages (forward-only sweep) — matching the dense loss, with
    compiled temp memory well under the unstack-everything eval it
    replaced (at scale the dominant win is never materializing the full
    replicated param set)."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)
    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    for _ in range(3):
        state, _ = trainer.step(state, trainer.shard_batch(batch))

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    ref = float(
        lm_loss_fn(Decoder(cfg).apply({"params": dense_params}, jb["tokens"]), jb)
    )
    res = trainer.evaluate(state, iter([batch] * 2), 2)
    assert abs(res["loss"] - ref) < 2e-3

    # live-bytes bound: the pipelined eval's compiled temp allocation must be
    # well under the replicated-unstack eval it replaced
    def replicated_eval(state, b):
        params = parts.unstack(state.params)
        return lm_loss_fn(Decoder(cfg).apply({"params": params}, b["tokens"]), b)

    sb = trainer.shard_batch(batch)
    with trainer.mesh:
        pip = trainer._eval_loss_step.lower(state, sb).compile()
        rep = jax.jit(replicated_eval).lower(state, sb).compile()
    pip_temp = pip.memory_analysis().temp_size_in_bytes
    rep_temp = rep.memory_analysis().temp_size_in_bytes
    assert pip_temp < rep_temp * 0.6, (pip_temp, rep_temp)


def test_pp_pipelined_eval_packed_matches_dense():
    """Packed batches evaluate through the pipeline too: side inputs ride
    the raw channel stream, and the masked global-mean rescale keeps the
    reported loss equal to the dense packed loss."""
    cfg = DecoderConfig.tiny()
    B, S = 8, 32
    rng = np.random.default_rng(1)
    seg = np.zeros((B, S), np.int32)
    seg[:4, S // 2:] = 1  # rows 0-3 packed, rows 4-7 single-doc
    pos = np.stack(
        [np.concatenate([np.arange(S // 2), np.arange(S - S // 2)])] * 4
        + [np.arange(S)] * 4
    ).astype(np.int32)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "positions": pos,
        "segment_ids": seg,
    }
    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-1), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    for _ in range(4):
        state, _ = trainer.step(state, trainer.shard_batch(batch))

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    ref = float(lm_loss_fn(
        Decoder(cfg).apply(
            {"params": dense_params}, jb["tokens"], jb["positions"], jb["segment_ids"]
        ),
        jb,
    ))
    res = trainer.evaluate(state, iter([batch] * 2), 2)
    assert abs(res["loss"] - ref) < 2e-3


def test_pp_tp_packed_matches_dense():
    """Packed batch under pp x tp: segment ids reach the nested
    tensor-manual stage attention (replicated across head shards) and the
    loss matches dense packed ground truth on trained params."""
    cfg = DecoderConfig.tiny()
    B, S = 8, 32
    rng = np.random.default_rng(0)
    seg = np.zeros((B, S), np.int32)
    seg[:, S // 2:] = 1
    pos = np.concatenate(
        [np.arange(S // 2), np.arange(S - S // 2)]
    )[None].repeat(B, 0).astype(np.int32)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "positions": pos,
        "segment_ids": seg,
    }
    ctx = TrainContext.create(ShardingSpec(pp=2, tp=2, dp=2))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-1), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)
    for _ in range(4):
        state, _ = trainer.step(state, trainer.shard_batch(batch))

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    ref = lm_loss_fn(
        Decoder(cfg).apply(
            {"params": dense_params}, jb["tokens"], jb["positions"], jb["segment_ids"]
        ),
        jb,
    )
    _, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - float(ref)) < 2e-3


def test_pp_ep_moe_matches_dense():
    """pp x ep: expert FFN weights shard over the expert axis INSIDE each
    stage (GSPMD-auto in the pipeline's partial-manual region), and the
    step matches the dense trainer's loss + router aux on the same params."""
    from maggy_tpu.models import MoEConfig, MoEDecoder
    from maggy_tpu.models.sown import collect_aux_losses

    cfg = MoEConfig.tiny_moe()
    batch = _batch(cfg, bsz=8, seq=16)
    ctx = TrainContext.create(ShardingSpec(pp=2, ep=2, dp=2))
    trainer = ctx.trainer(MoEDecoder(cfg), optax.sgd(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(0), batch)

    # placement: expert dims really sit on the expert axis
    specs = {
        jax.tree_util.keystr(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    assert any("expert" in str(s) for s in specs.values()), specs

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    model = MoEDecoder(cfg)
    logits, mods = model.apply(
        {"params": dense_params}, jnp.asarray(batch["tokens"]),
        mutable=["intermediates"],
    )
    ref_loss = float(lm_loss_fn(logits, batch))
    ref_aux = float(collect_aux_losses(mods))

    state, metrics = trainer.step(state, trainer.shard_batch(batch))
    assert abs(float(metrics["loss"]) - ref_loss) < 2e-3
    assert abs(float(metrics["aux_loss"]) - ref_aux) < 1e-3
    assert float(metrics["aux_loss"]) > 0


def test_pp_ep_dense_model_refused():
    """ep>1 under pp with a NON-MoE model has no expert dims to shard — the
    axis would silently replicate every stage param; refuse loudly."""
    cfg = DecoderConfig.tiny()
    ctx = TrainContext.create(ShardingSpec(pp=2, ep=2, dp=2))
    trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-2), n_microbatches=2)
    with pytest.raises(ValueError, match="needs an MoE model"):
        trainer.make_state(jax.random.key(0), _batch(cfg))


def test_pp_tp_ep_three_way_composition():
    """pp x tp x ep on one mesh: attention heads tensor-sharded AND expert
    FFNs expert-sharded inside each pipeline stage, training end-to-end."""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe()
    batch = _batch(cfg, bsz=8, seq=16)
    ctx = TrainContext.create(ShardingSpec(pp=2, tp=2, ep=2))
    trainer = ctx.trainer(MoEDecoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state = trainer.make_state(jax.random.key(1), batch)

    specs = [
        str(leaf.sharding.spec)
        for _, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    ]
    assert any("expert" in s for s in specs)
    assert any("tensor" in s for s in specs)

    # dense-reference parity, same bar as the 2-way composition tests: a
    # subtly wrong 3-way layout that still "trains" must not pass
    from maggy_tpu.models.sown import collect_aux_losses

    parts = trainer._pipeline_parts()
    dense_params = jax.device_get(jax.jit(parts.unstack)(state.params))
    logits, mods = MoEDecoder(cfg).apply(
        {"params": dense_params}, jnp.asarray(batch["tokens"]),
        mutable=["intermediates"],
    )
    ref_loss = float(lm_loss_fn(logits, batch))
    ref_aux = float(collect_aux_losses(mods))

    losses = []
    for i in range(3):
        state, m = trainer.step(state, trainer.shard_batch(batch))
        if i == 0:
            assert abs(float(m["loss"]) - ref_loss) < 2e-3
            assert abs(float(m["aux_loss"]) - ref_aux) < 1e-3
        losses.append(float(m["total_loss"]))
    assert losses[-1] < losses[0]
    assert float(m["aux_loss"]) > 0


def test_restore_pp_checkpoint_onto_pp_tp_mesh():
    """Checkpoint portability across LAYOUTS, not just degrees: a state
    trained on a plain pp=2 x dp mesh adopts onto a pp=2 x tp=2 mesh —
    adopt_state recomputes the tensor-sharded placements from shapes alone
    — and the next step's loss matches continuing on the original mesh."""
    cfg = DecoderConfig.tiny()
    batch = _batch(cfg)

    ctx_pp = TrainContext.create(ShardingSpec(pp=2, dp=4))
    tr_pp = ctx_pp.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    state = tr_pp.make_state(jax.random.key(3), batch)
    state, _ = tr_pp.step(state, tr_pp.shard_batch(batch))  # warm adam

    ctx_tp = TrainContext.create(ShardingSpec(pp=2, tp=2, dp=2))
    tr_tp = ctx_tp.trainer(Decoder(cfg), optax.adamw(1e-2), n_microbatches=2)
    adopted = tr_tp.adopt_state(jax.device_get(state), batch)

    # placements really are the pp x tp layout now
    specs = {
        jax.tree_util.keystr(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_leaves_with_path(adopted.params)
    }
    assert "tensor" in str(specs["['layers']['layer']['attn']['wq']['kernel']"])

    _, m_tp = tr_tp.step(adopted, tr_tp.shard_batch(batch))
    _, m_pp = tr_pp.step(state, tr_pp.shard_batch(batch))
    assert abs(float(m_tp["loss"]) - float(m_pp["loss"])) < 2e-3
