"""Bucketed gradient overlap + ZeRO-1 optimizer-state sharding
(maggy_tpu/parallel/overlap.py and its Trainer/checkpoint integration).

Covers the tentpole contracts: bucket-plan geometry, flatten/unflatten and
optax-state conversions round-trip exactly, zero_stage=0/bucket_mb=inf is
bit-identical to the dense path, bucketed and ZeRO-1 steps track the dense
loss, ZeRO-1 shrinks optimizer bytes per device by ~1/data_width, checkpoint
round-trips across zero_stage and world-size transitions, and pp-composed
meshes fall back to the unbucketed path with a one-time warning.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.parallel import overlap as ovl
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.train import pipeline_adapter
from maggy_tpu.train.checkpoint import Checkpointer, restore_zero_compat
from maggy_tpu.train.data import synthetic_lm_batches
from maggy_tpu.train.trainer import TrainContext


def _tree(seed=0):
    """Small mixed-shape/dtype param tree for plan/flatten unit tests."""
    rng = np.random.default_rng(seed)
    return {
        "emb": {"w": rng.normal(size=(7, 5)).astype(np.float32)},
        "layers": [
            {"k": rng.normal(size=(5, 5)).astype(np.float32),
             "b": rng.normal(size=(5,)).astype(np.float32)}
            for _ in range(3)
        ],
        "head": {"w": rng.normal(size=(5, 7)).astype(np.float32)},
    }


# ------------------------------------------------------------ plan geometry


def test_plan_buckets_reverse_order_and_cap():
    tree = _tree()
    leaves = jax.tree.leaves(tree)
    plan = ovl.plan_buckets(tree, bucket_mb=100 / 2**20)  # 100-byte cap
    assert plan.n_leaves == len(leaves)
    # bucket 0 holds the LAST flatten-order leaves (backward produces their
    # grads first), and indices across buckets walk strictly backwards
    flat_order = [i for b in plan.buckets for i in b.indices]
    assert flat_order[0] == len(leaves) - 1
    assert sorted(flat_order) == list(range(len(leaves)))
    for b in plan.buckets:
        assert list(b.indices) == sorted(b.indices, reverse=True)
        # the 100-byte cap is honored unless a single leaf exceeds it
        assert b.size * 4 <= 100 or len(b.indices) == 1
        assert b.size == sum(b.sizes)
    # names zero-padded so dict key-sort order == plan order
    names = [b.name for b in plan.buckets]
    assert names == sorted(names)


def test_plan_buckets_unbounded_padding_and_errors():
    tree = _tree()
    # None/inf cap -> one bucket for the whole (single-dtype) tree
    for cap in (None, float("inf")):
        plan = ovl.plan_buckets(tree, cap)
        assert len(plan.buckets) == 1
    # pad_to rounds every bucket to a shardable multiple
    plan = ovl.plan_buckets(tree, 100 / 2**20, pad_to=8)
    for b in plan.buckets:
        assert b.padded_size % 8 == 0 and b.padded_size >= b.size
    with pytest.raises(ValueError):
        ovl.plan_buckets({}, 1.0)
    with pytest.raises(ValueError):
        ovl.plan_buckets(tree, 1.0, pad_to=0)


def test_plan_buckets_splits_dtypes():
    tree = {
        "a": jnp.zeros((4,), jnp.float32),
        "b": jnp.zeros((4,), jnp.bfloat16),
        "c": jnp.zeros((4,), jnp.float32),
    }
    plan = ovl.plan_buckets(tree, None)
    # consecutive leaves of different dtype never share a flat vector
    assert len(plan.buckets) == 3
    assert [b.dtype for b in plan.buckets] == ["float32", "bfloat16", "float32"]


def test_flatten_unflatten_roundtrip():
    tree = _tree(1)
    plan = ovl.plan_buckets(tree, 120 / 2**20, pad_to=4)
    flats = ovl.flatten_buckets(tree, plan)
    assert set(flats) == {b.name for b in plan.buckets}
    for b in plan.buckets:
        assert flats[b.name].shape == (b.padded_size,)
    back = ovl.unflatten_buckets(flats, plan, tree)
    assert jax.tree.all(
        jax.tree.map(lambda a, b: bool(np.array_equal(a, b)), tree, back)
    )
    with pytest.raises(ValueError):
        ovl.flatten_buckets({"just": np.zeros(3)}, plan)


def test_opt_state_flatten_and_reflatten_roundtrip():
    tree = jax.tree.map(jnp.asarray, _tree(2))
    tx = optax.adamw(1e-3)
    opt = tx.update(jax.tree.map(jnp.ones_like, tree), tx.init(tree), tree)[1]
    plan = ovl.plan_buckets(tree, 100 / 2**20, pad_to=4)
    flat = ovl.flatten_opt_state(opt, plan, tree)
    # adam mu/nu became {bucket: vector} dicts; the count leaf passed through
    mu_flat = flat[0].mu
    assert set(mu_flat) == {b.name for b in plan.buckets}
    assert flat[0].count.shape == ()
    back = ovl.unflatten_opt_state(flat, plan, tree)
    assert jax.tree.all(
        jax.tree.map(lambda a, b: bool(np.array_equal(a, b)), opt, back)
    )
    # re-bucketing across plans (width/bucket_mb change) round-trips exactly
    plan2 = ovl.plan_buckets(tree, None, pad_to=2)
    re2 = ovl.reflatten_opt_state(flat, plan, plan2, tree)
    assert set(re2[0].mu) == {b.name for b in plan2.buckets}
    back2 = ovl.unflatten_opt_state(re2, plan2, tree)
    assert jax.tree.all(
        jax.tree.map(lambda a, b: bool(np.array_equal(a, b)), opt, back2)
    )


# ------------------------------------------------------------------ config


def test_sharding_spec_zero_fields():
    spec = ShardingSpec(dp=8, zero_stage=1, bucket_mb=4.0)
    assert spec.zero_stage == 1 and spec.bucket_mb == 4.0
    # scaled_to preserves the zero fields (dataclasses.replace path)
    scaled = spec.scaled_to(4)
    assert scaled.dp == 4 and scaled.zero_stage == 1 and scaled.bucket_mb == 4.0
    with pytest.raises(ValueError):
        ShardingSpec(dp=8, zero_stage=2)
    with pytest.raises(ValueError):
        ShardingSpec(dp=8, bucket_mb=0)


def test_distributed_config_zero_mapping():
    from maggy_tpu.config.distributed import DistributedConfig

    cfg = DistributedConfig(zero_lvl=1)
    assert cfg.sharding == "dp" and cfg.zero_stage == 1
    spec = cfg.resolve_sharding(8)
    assert spec.dp == 8 and spec.zero_stage == 1
    # explicit zero_stage wins over the zero_lvl mapping
    cfg0 = DistributedConfig(zero_lvl=1, zero_stage=0)
    assert cfg0.resolve_sharding(8).zero_stage == 0
    cfgb = DistributedConfig(sharding="dp", bucket_mb=16)
    assert cfgb.resolve_sharding(8).bucket_mb == 16.0
    with pytest.raises(ValueError):
        DistributedConfig(zero_stage=3)


def test_planner_memory_bound_raises_zero_before_batch():
    from maggy_tpu.autopilot.diagnose import Diagnosis
    from maggy_tpu.autopilot.plan import Planner

    diag = Diagnosis(
        bottleneck="memory_bound", scope="train",
        evidence={}, shares={}, reason="hbm pressure",
    )
    moves = Planner().plan_all(
        diag, {"train.zero_stage": 0, "train.batch_size": 32}
    )
    assert moves[0].knob == "train.zero_stage" and moves[0].value == 1
    assert moves[1].knob == "train.batch_size" and moves[1].value == 16
    # already sharded -> no zero move, batch shrink leads
    moves1 = Planner().plan_all(
        diag, {"train.zero_stage": 1, "train.batch_size": 32}
    )
    assert [m.knob for m in moves1][0] == "train.batch_size"


# ------------------------------------------------------ eligibility / modes


def _batch(cfg, seed=3, batch=8, seq=16):
    return next(synthetic_lm_batches(cfg.vocab_size, batch, seq, seed=seed))


def test_overlap_fallback_warns_once_on_pp_and_fsdp(monkeypatch):
    monkeypatch.setattr(pipeline_adapter, "_overlap_fallback_warned", False)
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create(ShardingSpec(pp=2, dp=4))
    tr = ctx.trainer(model, optax.adamw(1e-3), bucket_mb=4)
    with pytest.warns(UserWarning, match="unbucketed"):
        mode, _, _ = tr._overlap_mode()
    assert mode == "off"
    # one-time: a second ineligible trainer stays silent
    ctx2 = TrainContext.create("fsdp")
    tr2 = ctx2.trainer(model, optax.adamw(1e-3), zero_stage=1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert tr2._overlap_mode()[0] == "off"
    assert not [w for w in rec if "unbucketed" in str(w.message)]
    # and after a reset the fsdp blocker warns with its own reason
    monkeypatch.setattr(pipeline_adapter, "_overlap_fallback_warned", False)
    tr3 = ctx2.trainer(model, optax.adamw(1e-3), zero_stage=1)
    with pytest.warns(UserWarning, match="fsdp"):
        assert tr3._overlap_mode()[0] == "off"


def test_overlap_mode_resolution():
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create("dp")
    # nothing requested -> off, silently
    assert ctx.trainer(model, optax.adamw(1e-3))._overlap_mode()[0] == "off"
    # inf bucket_mb normalizes to unbucketed -> off (the bit-identity gate)
    tr_inf = ctx.trainer(model, optax.adamw(1e-3), bucket_mb=float("inf"))
    assert tr_inf._overlap_mode()[0] == "off"
    mode, manual, dz = ctx.trainer(
        model, optax.adamw(1e-3), bucket_mb=1
    )._overlap_mode()
    # dz is the ZeRO shard count: 1 when only bucketing is requested
    assert (mode, manual, dz) == ("bucket", ("data",), 1)
    mode, manual, dz = ctx.trainer(
        model, optax.adamw(1e-3), zero_stage=1
    )._overlap_mode()
    assert (mode, dz) == ("zero", 8)


# ------------------------------------------------------------ numerics


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_loss_parity_dense_bucketed_zero_20_steps():
    """The tentpole acceptance: on a 2-axis slice(DCN)xdata(ICI) mesh the
    bucketed and ZeRO-1 steps track the dense GSPMD loss over 20 steps, and
    each other as closely."""
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create_sliced("dp", total_slices=2)
    batch0 = _batch(cfg)

    def run(**kw):
        tr = ctx.trainer(model, optax.adamw(3e-3), **kw)
        state = tr.make_state(jax.random.key(0), batch0)
        stream = synthetic_lm_batches(cfg.vocab_size, 8, 16, seed=7)
        losses, gnorms = [], []
        for _ in range(20):
            state, m = tr.step(state, tr.shard_batch(next(stream)))
            losses.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
        return tr, state, np.array(losses), np.array(gnorms)

    dense_tr, dense_state, dense_l, dense_g = run()
    bucket_tr, _, bucket_l, bucket_g = run(bucket_mb=0.25)
    zero_tr, zero_state, zero_l, zero_g = run(zero_stage=1, bucket_mb=0.25)
    assert dense_tr._overlap_mode()[0] == "off"
    assert bucket_tr._overlap_mode()[0] == "bucket"
    assert zero_tr._overlap_mode()[0] == "zero"
    # vs dense: identical math, different reduction order -> tiny drift that
    # compounds across steps (measured ~1e-4 at step 20 on this model)
    np.testing.assert_allclose(bucket_l, dense_l, rtol=0, atol=2e-3)
    np.testing.assert_allclose(zero_l, dense_l, rtol=0, atol=2e-3)
    np.testing.assert_allclose(bucket_g, dense_g, rtol=2e-3, atol=2e-3)
    # bucket vs zero: the same math again. ZeRO-1 reduces with psum_scatter
    # where the bucketed step uses psum, and the two need not sum in one
    # order, so over 20 steps they are held to what both are granted against
    # dense. The first loss comes from the initial weights and one reduction
    # with nothing compounded on it: there they must agree
    np.testing.assert_allclose(zero_l, bucket_l, rtol=0, atol=2e-3)
    np.testing.assert_allclose(zero_l[0], bucket_l[0], rtol=0, atol=1e-6)
    # ZeRO-1 state: flat bucket vectors sharded over the data axis
    from maggy_tpu.parallel.spec import AXIS_DATA

    plan = ovl.plan_buckets(zero_state.params, 0.25, pad_to=4)
    flat_leaves = [
        leaf
        for leaf in jax.tree.leaves(zero_state.opt_state)
        if getattr(leaf, "ndim", None) == 1
        and leaf.shape[0] in plan.padded_sizes
    ]
    assert flat_leaves, "zero opt state holds no flat bucket vectors"
    for leaf in flat_leaves:
        assert leaf.sharding.spec == jax.sharding.PartitionSpec(AXIS_DATA)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_zero0_inf_bucket_bit_identical_to_dense():
    """zero_stage=0 + bucket_mb=inf resolves to the dense path itself, so
    the numerics are bit-compatible by construction — asserted by running
    both and comparing exactly."""
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create("dp")
    batch0 = _batch(cfg)
    results = []
    for kw in ({}, {"zero_stage": 0, "bucket_mb": float("inf")}):
        tr = ctx.trainer(model, optax.adamw(3e-3), **kw)
        assert tr._overlap_mode()[0] == "off"
        state = tr.make_state(jax.random.key(0), batch0)
        stream = synthetic_lm_batches(cfg.vocab_size, 8, 16, seed=5)
        losses = []
        for _ in range(3):
            state, m = tr.step(state, tr.shard_batch(next(stream)))
            losses.append(float(m["loss"]))
        results.append((losses, jax.tree.map(np.asarray, state.params)))
    assert results[0][0] == results[1][0]  # bitwise-equal losses
    assert jax.tree.all(
        jax.tree.map(
            lambda a, b: bool(np.array_equal(a, b)),
            results[0][1], results[1][1],
        )
    )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_zero1_shrinks_opt_bytes_per_device():
    """AOT accounting from shapes+shardings alone (no compile): ZeRO-1 cuts
    optimizer bytes per device by ~1/data_width (exactly 1/8 up to padding
    and the unsharded count scalar)."""
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create("dp")
    batch = _batch(cfg)

    def opt_bytes(tr):
        shardings = tr.state_shardings_for(batch)
        abstract = jax.eval_shape(
            tr._init_fn(), jax.random.key(0), batch["tokens"]
        )
        return ovl.opt_state_bytes_per_device(abstract, shardings)

    dense = opt_bytes(ctx.trainer(model, optax.adamw(1e-3)))
    zero = opt_bytes(
        ctx.trainer(model, optax.adamw(1e-3), zero_stage=1, bucket_mb=0.25)
    )
    assert zero < dense
    assert zero / dense <= 1 / 8 + 0.10


# ----------------------------------------------------------- checkpoints


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_checkpoint_zero1_restores_into_dense(tmp_path):
    """Save under ZeRO-1 (flat sharded state), restore into a zero_stage=0
    trainer: warn-and-reshard converts the layout and the optimizer state is
    equal element-for-element (padding dropped)."""
    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    ctx = TrainContext.create("dp")
    batch = _batch(cfg)
    zt = ctx.trainer(model, optax.adamw(3e-3), zero_stage=1, bucket_mb=0.25)
    state = zt.make_state(jax.random.key(0), batch)
    state, _ = zt.step(state, zt.shard_batch(batch))
    ck = Checkpointer(str(tmp_path), async_save=False)
    try:
        ck.save(int(state.step), state, meta=zt.checkpoint_meta())
        ck.wait()
        assert ck.saved_meta()["zero"] == {
            "stage": 1, "bucket_mb": 0.25, "shards": 8,
        }
        dt = ctx.trainer(model, optax.adamw(3e-3))
        tmpl = dt.make_state(jax.random.key(1), batch)
        with pytest.warns(UserWarning, match="ZeRO-1"):
            restored = restore_zero_compat(
                ck, tmpl, live_meta=dt.checkpoint_meta()
            )
        plan = ovl.plan_buckets(state.params, 0.25, pad_to=8)
        dense_as_flat = ovl.flatten_opt_state(
            jax.tree.map(np.asarray, restored.opt_state), plan,
            restored.params,
        )
        assert jax.tree.all(
            jax.tree.map(
                lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
                jax.tree.map(np.asarray, state.opt_state), dense_as_flat,
            )
        )
        assert jax.tree.all(
            jax.tree.map(
                lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
                state.params, restored.params,
            )
        )
        assert int(restored.step) == int(state.step)
    finally:
        ck.close()


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_checkpoint_zero1_restores_at_different_width(tmp_path):
    """Save ZeRO-1 over 8 shards, restore ZeRO-1 over 2 (simulated world-size
    change): the re-bucketing path rebuilds padding for the new width, state
    matches reflatten_opt_state exactly, and training continues."""
    from maggy_tpu import telemetry

    cfg = DecoderConfig.tiny()
    model = Decoder(cfg)
    batch = _batch(cfg)
    ctx = TrainContext.create("dp")
    zt = ctx.trainer(model, optax.adamw(3e-3), zero_stage=1, bucket_mb=0.25)
    state = zt.make_state(jax.random.key(0), batch)
    state, _ = zt.step(state, zt.shard_batch(batch))
    ck = Checkpointer(str(tmp_path), async_save=False)
    try:
        ck.save(int(state.step), state, meta=zt.checkpoint_meta())
        ck.wait()
        ctx2 = TrainContext.create(
            ShardingSpec(dp=2), devices=jax.devices()[:2]
        )
        zt2 = ctx2.trainer(
            model, optax.adamw(3e-3), zero_stage=1, bucket_mb=0.25
        )
        tmpl = zt2.make_state(jax.random.key(2), batch)
        tel = telemetry.Telemetry(worker="test-overlap")
        with telemetry.current(tel):
            with pytest.warns(UserWarning, match="shards=8"):
                restored = restore_zero_compat(
                    ck, tmpl, live_meta=zt2.checkpoint_meta()
                )
        counters = tel.snapshot().get("counters", {})
        assert counters.get("resilience.ckpt_zero_reshards", 0) == 1
        plan8 = ovl.plan_buckets(state.params, 0.25, pad_to=8)
        plan2 = ovl.plan_buckets(state.params, 0.25, pad_to=2)
        expect = ovl.reflatten_opt_state(
            jax.tree.map(np.asarray, state.opt_state), plan8, plan2,
            state.params,
        )
        assert jax.tree.all(
            jax.tree.map(
                lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
                expect, jax.tree.map(np.asarray, restored.opt_state),
            )
        )
        # the narrower trainer keeps stepping from the converted state
        restored, m = zt2.step(restored, zt2.shard_batch(batch))
        assert np.isfinite(m["loss"])
    finally:
        ck.close()
