"""The pieces under ``laguna`` (full and sliding-window grouped-query attention
layers in one period, each kind with its own number of query heads and its own
rotary form, a sigmoid gate a head, softmax-routed dropless experts scaled and
beside a shared one): the flash kernels and their visit table under a window
(``ops/flash.py``) against an explicit mask, the XLA attention's mask, YaRN's
table, the rotary width and scale, the dispatch, the refusals and the lifted
ones. The model against its plain reference
``benchmark/references/window_gqa_moe.py`` is ``test_laguna_model.py``'s, which
takes this file's helpers and fixtures (seeded weights at small sizes with the
published shape, ``benchmark/checks/tiny.laguna-s-2.1.json``).

Both sides compute in float32 here, so what differs is the order of the sums.
The chip run's comparison, in bfloat16, is the cell's
(``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import json
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, run as bench_run, weights  # noqa: E402
from benchmark.references import window_gqa_moe as reference  # noqa: E402
from maggy_tpu.models import moe, transformer  # noqa: E402
from maggy_tpu.ops.flash import flash_attention, needed_tiles, tiles_visited_share, visit_bounds  # noqa: E402
from test_flash_residuals import count  # noqa: E402  (Pallas kernels in a jaxpr)

KIND = "train_packed_ref"
SEED = 17
S = 128


def load(**over):
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.laguna-s-2.1.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(bench_run.merge(configs.load("benchmark/configs/laguna-s-2.1.json"), small), over)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def tiny():
    return load()


def packed(docs, rng, s=S):
    tok = rng.integers(1, 512, size=(len(docs), s), dtype=np.int32)
    pos, seg = np.zeros((len(docs), s), np.int32), np.zeros((len(docs), s), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
        tok[r, at:] = 0
    return {k: jnp.asarray(v) for k, v in
            dict(tokens=tok, positions=pos, segment_ids=seg, loss_mask=(seg > 0).astype(np.int32)).items()}


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 128 under a window of 32: a document of 70 and one
    of 50 (both longer than the window) before padding, and one of 20 (inside
    it) before one of 108."""
    return packed([[70, 50], [20, 108]], np.random.default_rng(3))


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    _cfg, ref, sizes, pcfg = tiny
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat])
    assert sorted(ref.ref_name(p) for p, _ in flat) == sorted(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


def program_outputs(model, params, batch):
    return model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )


# ------------------------------------------------- the kernels under a window


def attention_by_hand(q, k, v, seg, window):
    """Explicit [S, S] scores and mask; ``(out [B,S,H,D], lse [B,H,S])``."""
    h, kh = q.shape[2], k.shape[2]
    kk, vv = jnp.repeat(k, h // kh, axis=2), jnp.repeat(v, h // kh, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / math.sqrt(q.shape[3])
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    mask = jnp.broadcast_to(ahead >= 0, (q.shape[0], s, s))
    if window:
        mask = mask & (ahead < window)
    if seg is not None:
        mask = mask & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None])
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv, precision="highest"), lse


def rows_of(s, packed_row):
    if not packed_row:
        return None
    a, b = s * 3 // 8 + 3, s * 7 // 16 - 5
    return jnp.asarray(np.stack([np.repeat([1, 2, 0], [a, b, s - a - b]), np.repeat([1, 2], [s // 8 - 1, s - s // 8 + 1])]), jnp.int32)


@pytest.mark.parametrize("width", [128, 64])
@pytest.mark.parametrize("packed_row", [True, False], ids=["packed", "one-document"])
@pytest.mark.parametrize("window", [48, 128, 200], ids=["under-the-tile", "the-tile", "no-multiple"])
def test_windowed_kernels_against_an_explicit_mask(window, packed_row, width):
    """Forward, log-sum-exp and the three gradients, interpreted, with tiles
    of 128 (the forward's) and 128 x 64 (the backward's, key-major): the window
    smaller than, equal to and no multiple of the tile."""
    b, s, h, kh = 2, 512, 3, 1
    keys = jax.random.split(jax.random.key(window + width), 4)
    q = jax.random.normal(keys[0], (b, s, h, width), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, kh, width), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, kh, width), jnp.float32)
    g = jax.random.normal(keys[3], (b, s, h, width), jnp.float32)
    seg = rows_of(s, packed_row)
    tiles = dict(block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=64)
    flash = lambda q, k, v: flash_attention(q, k, v, segment_ids=seg, window=window, return_lse=True, **tiles)
    (out, lse), vjp = jax.vjp(lambda q, k, v: flash(q, k, v), q, k, v)
    (want, want_lse), want_vjp = jax.vjp(lambda q, k, v: attention_by_hand(q, k, v, seg, window), q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
    for got, ref_grad in zip(vjp((g, jnp.zeros_like(lse))), want_vjp((g, jnp.zeros_like(want_lse)))):
        np.testing.assert_allclose(got, ref_grad, rtol=1e-4, atol=1e-4)
    # the table: no tile wholly outside the window is needed, in either order, and every tile with a pair is
    segs = None if seg is None else np.asarray(seg)[:, None]
    need = needed_tiles(segs, causal=True, sq=s, sk=s, block_q=128, block_k=64, window=window)
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    pair = np.broadcast_to((ahead >= 0) & (ahead < window), (need.shape[0], s, s))
    if seg is not None:
        pair = pair & (np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :]) & (np.asarray(seg) > 0)[:, :, None]
    has_pair = pair.reshape(-1, s // 128, 128, s // 64, 64).any(axis=(2, 4))
    assert not (has_pair & ~need).any()
    outside = (np.arange(s // 128)[:, None] * 128 - (np.arange(s // 64)[None, :] * 64 + 63)) >= window
    assert outside.any() and not (need & outside[None]).any()
    for outer in ("q", "k"):
        first, last = np.asarray(visit_bounds(segs, outer, causal=True, sq=s, sk=s, block_q=128, block_k=64,
                                              window=window)).reshape(-1, 2).T
        visited = np.zeros_like(need.swapaxes(1, 2) if outer == "k" else need).reshape(len(first), -1)
        for row, (a, z) in enumerate(zip(first, last)):
            visited[row, a:z + 1] = True
        wanted = (need.swapaxes(1, 2) if outer == "k" else need).reshape(len(first), -1)
        assert (visited == wanted).all()  # a window and rising ids leave a run of blocks: first..last is exact


@pytest.mark.parametrize("packed_row", [True, False], ids=["packed", "one-document"])
def test_window_zero_is_the_call_without_one(packed_row):
    b, s, h, kh, d = 2, 256, 2, 1, 64
    keys = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (b, s, n, d), jnp.float32) for kk, n in zip(keys, (h, kh, kh)))
    seg = rows_of(s, packed_row)
    tiles = dict(block_q=64, block_k=64)
    f = lambda window: jax.value_and_grad(
        lambda q, k, v: (flash_attention(q, k, v, segment_ids=seg, window=window, **tiles) ** 2).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    plain = jax.value_and_grad(
        lambda q, k, v: (flash_attention(q, k, v, segment_ids=seg, **tiles) ** 2).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, c in zip(jax.tree.leaves(f(0)), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, c)
    for a, c in zip(jax.tree.leaves(f(s)), jax.tree.leaves(plain)):  # a window of the whole row masks nothing
        np.testing.assert_array_equal(a, c)
    segs = None if seg is None else np.asarray(seg)[:, None]
    kw = dict(causal=True, sq=s, sk=s, block_q=64, block_k=64)
    np.testing.assert_array_equal(needed_tiles(segs, window=0, **kw), needed_tiles(segs, **kw))
    np.testing.assert_array_equal(visit_bounds(segs, "k", window=0, **kw), visit_bounds(segs, "k", **kw))
    if seg is not None:
        host = np.asarray(seg)
        assert tiles_visited_share(host, block_q=64, block_k=64, window=0) == tiles_visited_share(host, block_q=64, block_k=64)
        assert tiles_visited_share(host, block_q=64, block_k=64, window=32) < tiles_visited_share(host, block_q=64, block_k=64)


def test_a_window_takes_a_causal_call_and_the_kernels():
    q = jnp.zeros((1, 256, 1, 64))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=32)
    odd = jnp.zeros((1, 250, 1, 64))  # a shape that falls back to the blockwise path has no window there
    with pytest.raises(ValueError, match="cannot compile"):
        flash_attention(odd, odd, odd, window=32)


def test_xla_attention_masks_the_same_pairs():
    b, s, h, kh, d = 2, 128, 4, 2, 32
    keys = jax.random.split(jax.random.key(6), 3)
    q, k, v = (jax.random.normal(kk, (b, s, n, d), jnp.float32) for kk, n in zip(keys, (h, kh, kh)))
    seg = rows_of(s, True)
    for window in (1, 32, 128):
        got = transformer.default_attention(q, k, v, segment_ids=seg, window=window)
        np.testing.assert_allclose(got, attention_by_hand(q, k, v, seg, window)[0], rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- the rotary forms


def test_yarn_table_against_the_formula_written_out():
    theta, width, factor, original, fast, slow = 500_000.0, 64, 128.0, 8192, 32.0, 1.0
    got = np.asarray(transformer.yarn_inv_freq(theta, width, factor, original, fast, slow), np.float64)
    want = []
    dim = lambda r: width * math.log(original / (r * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = math.floor(dim(fast)), math.ceil(dim(slow))
    assert (lo, hi) == (9, 18)  # of the 32 frequencies: the first ten keep theirs, from the 18th on a 128th
    for i in range(width // 2):
        f = theta ** (-2 * i / width)
        m = 1 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append((1 - m) * f / factor + m * f)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got[:10], [theta ** (-2 * i / width) for i in range(10)], rtol=2e-6)
    np.testing.assert_allclose(got[18:], [theta ** (-2 * i / width) / factor for i in range(18, 32)], rtol=2e-6)
    form = {"theta": theta, "width": width, "yarn": dict(factor=factor, original=original, beta_fast=fast, beta_slow=slow)}
    np.testing.assert_allclose(reference.inv_freq(form), got, rtol=1e-6)


def test_rope_rotates_its_width_scales_it_and_passes_the_rest():
    x = jax.random.normal(jax.random.key(7), (2, 16, 3, 32), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    np.testing.assert_array_equal(transformer.rope(x, pos, 1e4, width=32), transformer.rope(x, pos, 1e4))
    half = transformer.rope(x, pos, 1e4, width=16, scale=1.5)
    np.testing.assert_array_equal(half[..., 16:], x[..., 16:])
    np.testing.assert_allclose(half[..., :16], 1.5 * transformer.rope(x[..., :16], pos, 1e4), rtol=1e-5, atol=1e-6)
    yarn = (8.0, 64, 32.0, 1.0)
    form = {"theta": 5e5, "width": 16, "yarn": dict(factor=8.0, original=64, beta_fast=32.0, beta_slow=1.0,
                                                     attention_factor=1.25)}
    np.testing.assert_allclose(
        transformer.rope(x, pos, 5e5, width=16, yarn=yarn, scale=1.25), reference.rotary(x, pos, form), rtol=1e-5, atol=1e-6
    )
    ramp = np.asarray(transformer.yarn_inv_freq(5e5, 16, *yarn)) * 5e5 ** (np.arange(8) / 8)
    assert ramp[0] == pytest.approx(1.0) and 1 / 8 < ramp[1] < 1.0 and ramp[2] == pytest.approx(1 / 8, rel=1e-5)


def test_attention_dispatch_hands_the_window_to_the_flash_kernels(monkeypatch):
    """Where the shape tiles (here: the interpreter told that it does), the
    dispatch gives the kernels the window and records it; a full layer of the
    same model records none."""
    from maggy_tpu import telemetry

    cfg = transformer.DecoderConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=2, n_kv_heads=1, head_width=64, d_ff=64, max_seq_len=256,
        dtype=jnp.float32, layer_types=("full_attention", "sliding_attention"), scan_layers=False,
        sliding_window=64, sliding_heads=3, attn_gate=True,
    )
    x = jax.random.normal(jax.random.key(2), (1, 256, 128), jnp.float32)
    pos = jnp.arange(256, dtype=jnp.int32)[None]
    events = []

    class Recorder(telemetry.Telemetry):
        def event(self, name, **attrs):
            events.append((name, attrs))
            super().event(name, **attrs)

    for kind, window in (("sliding_attention", 64), ("full_attention", 0)):
        layer = transformer.Attention(cfg, kind)
        params = layer.init(jax.random.key(0), x, pos)
        want = layer.apply(params, x, pos, mutable=["intermediates"])[0]  # the XLA path under the same mask
        monkeypatch.setattr(transformer, "flash_tileable", lambda *a: None)
        with telemetry.current(Recorder(worker="t")):
            jaxpr = jax.make_jaxpr(lambda p: layer.apply(p, x, pos, mutable=["intermediates"])[0])(params)
            got = layer.apply(params, x, pos, mutable=["intermediates"])[0]
        monkeypatch.undo()
        assert count(jaxpr.jaxpr)["flash_fwd"] == 1
        kernel = [a for n, a in events if n == "attention.kernel"][-1]
        assert kernel["kernel"] == "flash" and kernel["window"] == window and kernel["backward"] == "fused"
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- the refusals


@pytest.mark.parametrize("bad", [
    dict(decode=True), dict(sliding_window=0), dict(attention_fn=transformer.default_attention),
    dict(sparse_topk=16, index_heads=2, index_head_dim=16), dict(sliding_heads=5), dict(rope_share=0.3),
    dict(rope_yarn=(8.0, 64)), dict(select_bias_std=0.1), dict(layer_types=("full_attention", "window") + ("conv",) * 3),
], ids=["decode-with-a-window", "no-window", "own-attention", "selection", "heads-off-the-groups", "odd-share",
        "short-yarn", "softmax-bias", "unknown-kind"])
def test_config_refuses_what_the_layers_cannot_do(tiny, bad):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError):
        dataclasses.replace(pcfg, **bad)


@pytest.mark.parametrize("fields", [
    dict(d_model=3072, n_heads=72, n_kv_heads=8, head_width=128),
    dict(d_model=80, n_heads=6, n_kv_heads=2, head_width=32, sliding_heads=4),
], ids=["72-heads-of-128-over-3072", "six-heads-over-80"])
def test_head_width_lifts_the_division_of_the_model_by_its_heads(fields):
    cfg = transformer.DecoderConfig(**fields)
    assert cfg.head_dim == fields["head_width"]
    with pytest.raises(ValueError, match="divisible"):
        transformer.DecoderConfig(**dict(fields, head_width=0))


def test_softmax_router_takes_a_scaling_and_a_shared_expert(tiny):
    _cfg, _ref, _sizes, pcfg = tiny
    assert dataclasses.replace(pcfg, routed_scaling=1.8, n_shared_experts=2).routed_scaling == 1.8
    assert dataclasses.replace(pcfg, n_shared_experts=0).n_shared_experts == 0


def test_decode_with_a_window_says_why(tiny):
    _cfg, _ref, _sizes, pcfg = tiny
    with pytest.raises(ValueError, match="page allocator"):
        dataclasses.replace(pcfg, decode=True)
    full = dataclasses.replace(pcfg, layer_types=(), sliding_window=0, sliding_heads=0, experts_held=0, decode=True)
    assert full.decode  # a model with no sliding layer decodes as before
