"""Attention op correctness: blockwise == reference, ring == reference on a
seq-sharded mesh, Ulysses == reference, flash kernel (interpret mode) ==
reference, and gradients flow through blockwise/ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models.transformer import default_attention
from maggy_tpu.ops.attention import blockwise_attention
from maggy_tpu.ops.flash import flash_attention
from maggy_tpu.parallel.mesh import make_mesh
from maggy_tpu.parallel.ringattention import ring_attention
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.parallel.ulysses import ulysses_attention


def qkv(b=2, s=64, h=4, kh=None, d=16, seed=0, dtype=jnp.float32):
    kh = kh or h
    rng = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(rng, 3)
    return (
        jax.random.normal(k1, (b, s, h, d), dtype),
        jax.random.normal(k2, (b, s, kh, d), dtype),
        jax.random.normal(k3, (b, s, kh, d), dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [16, 64, 50])
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = qkv()
    ref = default_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_gqa():
    q, k, v = qkv(h=8, kh=2)
    ref = default_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_grads_match():
    q, k, v = qkv(s=32)

    def loss_ref(q, k, v):
        return default_attention(q, k, v, causal=True).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_k=8).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(causal):
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=64, h=4, d=16)
    ref = default_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=causal)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.slow
def test_ring_gqa_and_grads():
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=32, h=8, kh=4, d=8)
    ref = default_attention(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        g = jax.grad(
            lambda q: ring_attention(q, k, v, mesh=mesh, causal=True).sum()
        )(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g_ref = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(causal):
    mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    q, k, v = qkv(b=2, s=64, h=8, d=16)
    ref = default_attention(q, k, v, causal=causal)
    with mesh:
        out = jax.jit(
            lambda q, k, v: ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_head_divisibility():
    mesh = make_mesh(ShardingSpec(sp=8))
    q, k, v = qkv(h=4)  # 4 heads, 8 shards
    with pytest.raises(ValueError, match="divide the head count"):
        with mesh:
            ulysses_attention(q, k, v, mesh=mesh)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal):
    # d must be a multiple of 128 lanes for the kernel path
    q, k, v = qkv(b=1, s=256, h=2, d=128)
    ref = default_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_flash_auto_blocks():
    """Default tiles: measured-fastest MXU sizes that divide the sequence."""
    from maggy_tpu.ops.flash import _auto_blocks

    assert _auto_blocks(1024, 1024) == (512, 512)
    assert _auto_blocks(8192, 8192) == (512, 1024)  # wide k tiles at long S
    assert _auto_blocks(1280, 1280) == (256, 256)  # halved until they divide
    assert _auto_blocks(128, 128) == (128, 128)


def test_flash_default_blocks_match_reference():
    """The auto-tuned default tiling (block_q/k=None) stays correct, fwd+bwd."""
    q, k, v = qkv(b=1, s=256, h=2, d=128)
    ref = default_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)

    g_ref = jax.grad(lambda q: (default_attention(q, k, v, causal=True) ** 2).sum())(q)
    g_fl = jax.grad(lambda q: (flash_attention(q, k, v, causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal):
    """The Pallas backward kernels (dQ + dK/dV split) against jax.grad through
    the XLA dense path — the round-1 gap (forward-only kernel)."""
    q, k, v = qkv(b=1, s=256, h=2, d=128)

    def loss_ref(q, k, v):
        return (default_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=causal, block_q=128, block_k=128) ** 2
        ).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


def test_flash_independent_bwd_tiles():
    """bwd_block_q/bwd_block_k different from the forward's tiles: the LSE
    residual re-chunks and gradients stay exact (the silicon tuning knob,
    tools/tune_flash.py)."""
    q, k, v = qkv(b=1, s=256, h=2, d=128)

    def loss(bq, bk, bbq, bbk):
        def f(q, k, v):
            o = flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                bwd_block_q=bbq, bwd_block_k=bbk,
            )
            return (o ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    base = loss(128, 128, 128, 128)
    mixed = loss(128, 128, 64, 32)   # smaller bwd tiles
    wider = loss(64, 64, 128, 256)   # larger bwd tiles than fwd
    for a, b in zip(base, mixed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    for a, b in zip(base, wider):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_backward_gqa_bf16():
    """GQA grads sum back over the head group; bf16 within bf16 tolerance."""
    q, k, v = qkv(b=2, s=128, h=4, kh=2, d=128, dtype=jnp.bfloat16)

    def loss_ref(q, k, v):
        return default_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
            .astype(jnp.float32)
            .sum()
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-1, rtol=1e-1
        )


def test_flash_under_remat():
    """flash_attention composes with jax.checkpoint (the training config)."""
    q, k, v = qkv(b=1, s=128, h=2, d=128)

    def loss(q, k, v):
        f = jax.checkpoint(
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            policy=jax.checkpoint_policies.nothing_saveable,
        )
        return (f(q, k, v) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (default_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


def test_sharded_flash_matches_reference():
    """The shard_map wrap that auto_attention uses on multi-device meshes —
    a pallas_call has no GSPMD partitioning rule, so this is the only legal
    multi-chip route; exercised here on the CPU mesh in interpret mode."""
    from maggy_tpu.ops.flash import sharded_flash_attention

    mesh = make_mesh(ShardingSpec(dp=2, fsdp=2, tp=2))
    q, k, v = qkv(b=4, s=128, h=2, d=128)
    ref = default_attention(q, k, v, causal=True)
    with mesh:
        out = jax.jit(
            lambda q, k, v: sharded_flash_attention(
                q, k, v, mesh=mesh, causal=True, interpret=True
            )
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
    # gradients flow through the shard_map'd custom VJP
    with mesh:
        g = jax.jit(
            jax.grad(
                lambda q: sharded_flash_attention(
                    q, k, v, mesh=mesh, causal=True, interpret=True
                ).sum()
            )
        )(q)
    g_ref = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-2, rtol=2e-2)


def test_sharded_flash_refuses_incompatible_mesh():
    from maggy_tpu.ops.flash import sharded_flash_attention

    q, k, v = qkv(b=2, s=128, h=4, d=128)
    sp_mesh = make_mesh(ShardingSpec(sp=4, dp=2))
    assert sharded_flash_attention(q, k, v, mesh=sp_mesh) is None  # sp in use
    dp_mesh = make_mesh(ShardingSpec(dp=8))
    q3, k3, v3 = qkv(b=3, s=128, h=4, d=128)
    assert sharded_flash_attention(q3, k3, v3, mesh=dp_mesh) is None  # 3 % 8


def test_flash_fallback_on_odd_shapes():
    q, k, v = qkv(b=1, s=60, h=2, d=16)  # not tileable -> blockwise fallback
    ref = default_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_compiled_refuses_odd_shapes_by_name():
    """interpret=False (what a TPU picks by itself) never falls back: the
    caller asked for the kernel, so an untileable shape raises, naming the
    dimension — before any Mosaic lowering, so this runs off-TPU too."""
    q, k, v = qkv(b=1, s=60, h=2, d=16)
    with pytest.raises(ValueError, match="head_dim 16 is not a multiple of 128"):
        flash_attention(q, k, v, causal=True, interpret=False)
    q, k, v = qkv(b=1, s=100, h=2, d=128)
    with pytest.raises(ValueError, match=r"block_q=100 \(sequence length 100\)"):
        flash_attention(q, k, v, causal=True, interpret=False)
    # packed segments put the block in the lane dim: 8-aligned is not enough
    q, k, v = qkv(b=2, s=200, h=2, d=128)
    seg = jnp.zeros((2, 200), jnp.int32)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        flash_attention(q, k, v, segment_ids=seg, interpret=False)


def test_auto_attention_records_its_choice():
    """The automatic dispatch is never silent: one attention.kernel event
    per trace says which kernel it took and, off the flash path, why."""
    from maggy_tpu import telemetry
    from maggy_tpu.models.transformer import auto_attention

    tel = telemetry.Telemetry(worker="t")
    q, k, v = qkv(b=1, s=128, h=2, d=128)
    with telemetry.current(tel):
        auto_attention(q, k, v)
    (event,) = [e for e in tel.drain_events() if e["name"] == "attention.kernel"]
    assert event["attrs"]["kernel"] == "xla_dense"
    assert event["attrs"]["reason"] == "backend is cpu"
    assert event["attrs"]["q"] == [1, 128, 2, 128]


@pytest.mark.slow
def test_decoder_with_ring_attention_e2e():
    """Decoder runs unchanged with ring attention as its attention_fn on an
    sp mesh — the long-context config."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.ringattention import make_ring_attention
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    ctx = TrainContext.create(ShardingSpec(sp=4, dp=2))
    cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 4, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    first = last = None
    for _ in range(15):
        state, m = trainer.step(state, trainer.shard_batch(next(data)))
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert np.isfinite(last) and last < first
