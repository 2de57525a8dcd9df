"""KV-cache incremental decoding: numerical equivalence with the full forward
pass, cached vs recompute generation agreement, and cache shapes through the
scanned layer stack."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.models.generate import generate, generate_cached, init_cache


@pytest.fixture(scope="module")
def setup():
    cfg = DecoderConfig.tiny(max_seq_len=32)
    model = Decoder(cfg)
    tokens = jnp.asarray(np.arange(16)[None, :] % cfg.vocab_size, dtype=jnp.int32)
    # param seed deliberately != the key(0) init_cache uses internally — a
    # cache polluted by init-time params must not be coincidentally correct
    variables = model.init(jax.random.key(7), tokens)
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))
    return cfg, model, decode_model, variables, tokens


@pytest.mark.slow
def test_incremental_matches_full_forward(setup):
    cfg, model, decode_model, variables, tokens = setup
    full = np.asarray(model.apply(variables, tokens))
    cache = init_cache(decode_model, tokens)
    outs = []
    for p in range(tokens.shape[1]):
        logits, mut = decode_model.apply(
            {"params": variables["params"], "cache": cache},
            tokens[:, p : p + 1],
            jnp.full((1, 1), p, jnp.int32),
            mutable=["cache"],
        )
        cache = mut["cache"]
        outs.append(np.asarray(logits[:, 0]))
    inc = np.stack(outs, axis=1)
    np.testing.assert_allclose(inc, full, atol=2e-2)  # bf16 accumulation noise


def test_multi_chunk_cache_reads_match_full_forward():
    """Length-adaptive chunked cache reads (decode_chunk < max_seq_len): the
    cross-chunk online-softmax recurrence must reproduce the full forward —
    geometry chosen so 4 chunks are live and the prefix crosses chunk
    boundaries mid-decode (the length-adaptive read, multi-chunk case)."""
    cfg = DecoderConfig.tiny(max_seq_len=64, decode_chunk=16, dtype=jnp.float32)
    model = Decoder(cfg)
    tokens = jnp.asarray(
        np.arange(56)[None, :] % cfg.vocab_size, dtype=jnp.int32
    )
    variables = model.init(jax.random.key(3), tokens)
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))
    full = np.asarray(model.apply(variables, tokens))
    cache = init_cache(decode_model, tokens)
    outs = []
    for p in range(tokens.shape[1]):
        logits, mut = decode_model.apply(
            {"params": variables["params"], "cache": cache},
            tokens[:, p : p + 1],
            jnp.full((1, 1), p, jnp.int32),
            mutable=["cache"],
        )
        cache = mut["cache"]
        outs.append(np.asarray(logits[:, 0]))
    np.testing.assert_allclose(np.stack(outs, axis=1), full, atol=2e-4)


def test_cache_shapes_scanned(setup):
    cfg, _, decode_model, _, tokens = setup
    cache = init_cache(decode_model, tokens)
    k = cache["layers"]["layer"]["attn"]["k"]
    # [n_layers, B, max_seq_len, kv_heads, head_dim] — layer axis from nn.scan
    assert k.shape == (cfg.n_layers, 1, cfg.max_seq_len, cfg.n_kv_heads, cfg.head_dim)


def test_cached_generation_matches_recompute(setup):
    cfg, model, decode_model, variables, _ = setup
    prompt = np.zeros((2, 24), dtype=np.int32)
    prompt[0, :5] = [3, 6, 9, 12, 15]
    prompt[1, :7] = np.arange(7) * 2
    plen = jnp.asarray([5, 7])
    a = np.asarray(generate(model, variables, jnp.asarray(prompt), plen))
    b = np.asarray(
        generate_cached(decode_model, variables["params"], jnp.asarray(prompt), plen)
    )
    assert (a == b).mean() > 0.95  # bf16 ties may break differently


@pytest.mark.slow
def test_moe_decoder_cached_generation():
    """The MoE decoder shares the Attention module, so KV-cache decode works
    for it too. (Note: per-step routing never drops tokens — capacity >=
    top_k at t=1 — so under congestion decode can be *more* faithful than the
    capacity-limited training forward; uncongested they agree.)"""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe(max_seq_len=24)
    model = MoEDecoder(cfg)
    tokens = jnp.asarray(np.arange(12)[None, :] % cfg.vocab_size, dtype=jnp.int32)
    variables = model.init(jax.random.key(3), tokens)
    full = np.asarray(model.apply(variables, tokens))

    decode_model = MoEDecoder(dataclasses.replace(cfg, decode=True))
    cache = init_cache(decode_model, tokens)
    outs = []
    for p in range(12):
        logits, mut = decode_model.apply(
            {"params": variables["params"], "cache": cache},
            tokens[:, p : p + 1],
            jnp.full((1, 1), p, jnp.int32),
            mutable=["cache"],
        )
        cache = mut["cache"]
        outs.append(np.asarray(logits[:, 0]))
    np.testing.assert_allclose(np.stack(outs, 1), full, atol=3e-2)

    prompt = np.zeros((1, 16), dtype=np.int32)
    prompt[0, :4] = [1, 2, 3, 4]
    a = np.asarray(generate(model, variables, jnp.asarray(prompt), jnp.asarray([4])))
    b = np.asarray(
        generate_cached(
            decode_model, variables["params"], jnp.asarray(prompt), jnp.asarray([4])
        )
    )
    assert (a == b).mean() > 0.9


def test_cached_generation_eos(setup):
    cfg, model, decode_model, variables, _ = setup
    prompt = np.zeros((1, 16), dtype=np.int32)
    prompt[0, :4] = [1, 2, 3, 4]
    plen = jnp.asarray([4])
    free = np.asarray(
        generate_cached(decode_model, variables["params"], jnp.asarray(prompt), plen)
    )
    eos = int(free[0, 4])
    out = np.asarray(
        generate_cached(
            decode_model, variables["params"], jnp.asarray(prompt), plen, eos_id=eos
        )
    )
    hits = np.where(out[0] == eos)[0]
    assert hits.size and (out[0, hits[0]:] == eos).all()


@pytest.mark.slow
def test_tp_decode_cache_sharded():
    """On a tp mesh the KV cache shards its kv-head dim over tensor (1/tp per
    device, not a full replica) and cached generation still matches the
    recompute path."""
    from maggy_tpu.models.generate import cache_shardings
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import AXIS_TENSOR, ShardingSpec

    cfg = DecoderConfig.tiny(max_seq_len=32)  # 2 kv heads
    mesh = make_mesh(ShardingSpec(tp=2), jax.devices()[:2])
    model = Decoder(cfg)
    tokens = jnp.asarray(np.arange(16)[None, :] % cfg.vocab_size, dtype=jnp.int32)
    variables = model.init(jax.random.key(7), tokens)
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))

    cache = init_cache(decode_model, tokens, mesh=mesh)
    k = cache["layers"]["layer"]["attn"]["k"]
    spec = k.sharding.spec
    assert spec[-2] == AXIS_TENSOR, spec  # kv heads sharded, cache not replicated
    shard_shape = k.sharding.shard_shape(k.shape)
    assert shard_shape[-2] == cfg.n_kv_heads // 2

    # numerics: incremental decode on the sharded cache == full forward
    full = np.asarray(model.apply(variables, tokens))
    outs = []
    with mesh:
        for p in range(tokens.shape[1]):
            logits, mut = decode_model.apply(
                {"params": variables["params"], "cache": cache},
                tokens[:, p : p + 1],
                jnp.full((1, 1), p, jnp.int32),
                mutable=["cache"],
            )
            cache = mut["cache"]
            outs.append(np.asarray(logits[:, 0]))
    inc = np.stack(outs, axis=1)
    np.testing.assert_allclose(inc, full, atol=2e-2)


@pytest.mark.slow
def test_packed_prefill_logits_match_per_sequence(setup):
    """A packed prompt batch prefills in ONE pass, and the
    segment mask isolates each segment — every segment's prefill logits
    equal a plain forward over that sequence alone."""
    from maggy_tpu.models.generate import prefill

    cfg, model, decode_model, variables, _ = setup
    rng = np.random.default_rng(3)
    s1 = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
    s2 = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
    packed = jnp.asarray(np.concatenate([s1, s2])[None])  # [1, 16]
    positions = jnp.asarray(
        np.concatenate([np.arange(6), np.arange(10)])[None].astype(np.int32)
    )
    seg = jnp.asarray(np.concatenate([np.zeros(6), np.ones(10)])[None].astype(np.int32))

    logits, cache = prefill(
        decode_model, variables["params"], packed, positions, seg
    )
    # every scanned layer's write index advanced by the full prompt length
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if "index" in jax.tree_util.keystr(path):
            assert all(int(v) == 16 for v in np.asarray(leaf).ravel())
    ref1 = np.asarray(model.apply(variables, jnp.asarray(s1[None])))
    ref2 = np.asarray(model.apply(variables, jnp.asarray(s2[None])))
    got = np.asarray(logits)
    np.testing.assert_allclose(got[:, :6], ref1, atol=3e-2)
    np.testing.assert_allclose(got[:, 6:], ref2, atol=3e-2)


@pytest.mark.slow
def test_packed_prefill_decode_matches_unpacked_decode(setup):
    """Packed prefill + cached decode of each row's LAST segment equals the
    per-sequence unpacked cached decode — greedy tokens must match exactly."""
    from maggy_tpu.models.generate import generate_cached_packed

    cfg, model, decode_model, variables, _ = setup
    rng = np.random.default_rng(4)
    MAX_NEW = 6
    rows = []
    poss = []
    segs = []
    lasts = []
    for r in range(2):
        a = rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
        b = rng.integers(1, cfg.vocab_size, 7).astype(np.int32)
        rows.append(np.concatenate([a, b]))
        poss.append(np.concatenate([np.arange(5), np.arange(7)]))
        segs.append(np.concatenate([np.zeros(5), np.ones(7)]))
        lasts.append(b)
    packed = jnp.asarray(np.stack(rows).astype(np.int32))
    positions = jnp.asarray(np.stack(poss).astype(np.int32))
    seg = jnp.asarray(np.stack(segs).astype(np.int32))

    _, new_tokens = generate_cached_packed(
        decode_model, variables["params"], packed, positions, seg,
        max_new=MAX_NEW,
    )

    # unpacked reference: each last segment decoded alone through the
    # existing cached path
    for r, b in enumerate(lasts):
        buf = np.zeros((1, 7 + MAX_NEW), np.int32)
        buf[0, :7] = b
        ref = generate_cached(
            decode_model, variables["params"], jnp.asarray(buf),
            jnp.asarray([7], jnp.int32),
        )
        np.testing.assert_array_equal(
            np.asarray(new_tokens)[r], np.asarray(ref)[0, 7:],
            err_msg=f"row {r}: packed continuation diverges from unpacked",
        )


@pytest.mark.slow
def test_packed_prefill_cache_overflow_raises(setup):
    from maggy_tpu.models.generate import generate_cached_packed

    cfg, model, decode_model, variables, _ = setup
    packed = jnp.zeros((1, 30), jnp.int32)
    positions = jnp.zeros((1, 30), jnp.int32)
    seg = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate_cached_packed(
            decode_model, variables["params"], packed, positions, seg,
            max_new=8,
        )
