"""A recomputed layer runs the flash forward once: the kernel's output and row
statistics are named residuals (``ops.flash.FLASH_RESIDUALS``) that every
recompute policy keeps, so the replay inside the backward pass rebuilds q, k
and v and not the kernel's results. Counted in the jaxpr of the loss and its
gradient (the Pallas interpreter stands in for Mosaic; ``test_tpu_compile.py``
asks the TPU's compiler the same)."""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models.transformer import REMAT_POLICIES, Decoder, DecoderConfig
from maggy_tpu.ops.flash import FLASH_RESIDUALS, flash_attention, sharded_flash_attention

S = 128
LATENT = dict(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128)
ONCE = {"flash_fwd": 1, "flash_bwd": 1}


def count(jaxpr, found=None):
    """Pallas kernels by name and ``checkpoint_name`` tags, at any depth."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[str(eqn.params["name"])] += 1
        elif eqn.primitive.name == "name":
            found["name:" + eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count(sub, found)
    return found


def kernels(counted):
    return {k: v for k, v in counted.items() if not k.startswith("name:")}


def decoder(latent, **fields):
    """Two scanned layers whose heads are 128 wide (what the kernels tile),
    attention through the flash kernels in the interpreter."""
    cfg = DecoderConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=128, max_seq_len=S,
        dtype=jnp.float32, attention_fn=functools.partial(flash_attention, interpret=True, block_q=64, block_k=64),
        **(LATENT if latent else {}), **fields,
    )
    return Decoder(cfg)


def batch(packed):
    tokens = jnp.asarray(np.arange(2 * S).reshape(2, S) % 64, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    seg = jnp.asarray(np.stack([np.repeat([1, 2, 0], [70, 50, 8]), np.repeat([1, 2], [64, 64])]), jnp.int32)
    return tokens, positions, (seg if packed else None)


def loss_and_grad(model, inputs):
    return jax.value_and_grad(lambda p: jnp.mean(jnp.square(model.apply(p, *inputs))))


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("policy", ["nothing", "dots"])
@pytest.mark.parametrize("latent", [False, True], ids=["Attention", "LatentAttention"])
def test_recomputed_layer_runs_the_forward_kernel_once(latent, policy, packed):
    model, inputs = decoder(latent, remat=True, remat_policy=policy), batch(packed)
    params = model.init(jax.random.key(0), inputs[0])
    counted = count(jax.make_jaxpr(loss_and_grad(model, inputs))(params).jaxpr)
    assert kernels(counted) == ONCE  # a replayed kernel would make flash_fwd 2
    assert all(counted["name:" + name] == 1 for name in FLASH_RESIDUALS)


@pytest.mark.parametrize("policy", ["nothing", "dots"])
@pytest.mark.parametrize("latent", [False, True], ids=["Attention", "LatentAttention"])
def test_recomputed_layer_equals_the_plain_layer(latent, policy):
    """What is kept is what the replay would have computed: the loss and every
    gradient are the unrecomputed model's, exactly."""
    inputs = batch(packed=True)
    plain = decoder(latent, remat=False)
    params = plain.init(jax.random.key(1), inputs[0])
    want = jax.jit(loss_and_grad(plain, inputs))(params)
    got = jax.jit(loss_and_grad(decoder(latent, remat=True, remat_policy=policy), inputs))(params)
    jax.tree.map(np.testing.assert_array_equal, got, want)


@pytest.mark.parametrize("latent", [False, True], ids=["Attention", "LatentAttention"])
def test_names_leave_nothing_in_a_program_that_recomputes_nothing(latent):
    model, inputs = decoder(latent, remat=False), batch(packed=True)
    params = model.init(jax.random.key(0), inputs[0])
    fn = loss_and_grad(model, inputs)
    assert kernels(count(jax.make_jaxpr(fn)(params).jaxpr)) == ONCE
    text = jax.jit(fn).lower(params).as_text()
    assert not any(name in text for name in FLASH_RESIDUALS)


def test_decode_never_reaches_the_kernels_backward_rule():
    """Serving does not differentiate: no kernel's ``core_fwd`` runs, so no
    name is in its program (decode attends over the cache without the kernel)."""
    model, (tokens, positions, _) = decoder(False, remat=True), batch(False)
    params = model.init(jax.random.key(0), tokens)["params"]
    serving = Decoder(dataclasses.replace(model.cfg, decode=True))
    cache = serving.init(jax.random.key(0), tokens, positions)["cache"]
    jaxpr = jax.make_jaxpr(
        lambda p, c: serving.apply({"params": p, "cache": c}, tokens, positions, mutable=["cache"])
    )(params, cache)
    assert not count(jaxpr.jaxpr)
    # nor does a forward pass of the training model, recomputed or not
    forward = jax.make_jaxpr(lambda p: model.apply({"params": p}, tokens, positions))(params)
    assert dict(count(forward.jaxpr)) == {"flash_fwd": 1}


@pytest.mark.parametrize("policy", ["nothing", "dots"])
def test_kernel_under_shard_map_keeps_its_residuals(policy):
    """The named values inside a ``shard_map`` body are residuals of the remat
    around it: batch over data, heads over tensor, one forward kernel."""
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    mesh = make_mesh(ShardingSpec(dp=2, tp=2), jax.devices()[:4])
    q, k, v = (jax.random.normal(jax.random.key(i), (2, S, 2, 128), jnp.float32) for i in range(3))
    seg = batch(packed=True)[2]

    def attend(q, k, v):
        return sharded_flash_attention(q, k, v, mesh=mesh, interpret=True, segment_ids=seg)

    def loss(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v))), argnums=(0, 1, 2))

    recomputed = loss(jax.checkpoint(attend, policy=REMAT_POLICIES[policy]))
    with mesh:
        assert kernels(count(jax.make_jaxpr(recomputed)(q, k, v).jaxpr)) == ONCE
        jax.tree.map(np.testing.assert_array_equal, jax.jit(recomputed)(q, k, v), jax.jit(loss(attend))(q, k, v))


def test_the_policy_that_kept_nothing_is_gone():
    assert sorted(REMAT_POLICIES) == ["dots", "everything", "nothing"]
    with pytest.raises(ValueError, match="must be one of"):
        DecoderConfig(remat_policy="dots_attn")
    assert DecoderConfig().remat_policy == "dots"
