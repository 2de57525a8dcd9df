"""The pieces under ``evabyte`` (EVA attention: exact keys inside a window of
the row's grid, one learned summary a chunk before it, one softmax over both,
under a head of several next-byte predictions): the summaries, the two masks,
the attention through the interpreted flash kernels (``ops/eva.py``: the
windows folded into rows, the summaries under a selection, the rows'
log-sum-exp merged) and through the XLA path against explicit masks, outputs
and every gradient, ``phi`` and ``mu`` among them; the two limits of the
equations; what a document cannot see; the dispatch and its event; the
eight-offset loss at document ends; the refusals. The model against its plain
reference ``benchmark/references/eva_dense.py`` is ``test_evabyte_model.py``'s,
which takes this file's helpers and fixtures (seeded weights at small sizes
with the published shape, ``benchmark/checks/tiny.evabyte.json``).

Both sides compute in float32 here, so what differs is the order of the sums.
The chip run's comparison, in bfloat16, is the cell's
(``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import json
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
from benchmark.references import eva_dense as reference  # noqa: E402
from maggy_tpu.models import transformer  # noqa: E402
from maggy_tpu.ops import eva  # noqa: E402
from maggy_tpu.ops.attention import NEG_INF  # noqa: E402
from maggy_tpu.ops.flash import FLASH_RESIDUALS  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402
from test_flash_residuals import count, kernels  # noqa: E402  (Pallas kernels in a jaxpr)

KIND = "train_packed_ref"
SEED = 23
S = 128


def load(**over):
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.evabyte.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(bench_run.merge(configs.load("benchmark/configs/evabyte.json"), small), over)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=S)
    return cfg, ref, sizes, transformer.DecoderConfig(**fields)


@pytest.fixture(scope="module")
def tiny():
    return load()


def packed(docs, rng, s=S, vocab=320):
    tok = rng.integers(1, vocab, size=(len(docs), s), dtype=np.int32)
    pos, seg = np.zeros((len(docs), s), np.int32), np.zeros((len(docs), s), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
        tok[r, at:] = 0
    return {k: jnp.asarray(v) for k, v in
            dict(tokens=tok, positions=pos, segment_ids=seg, loss_mask=(seg > 0).astype(np.int32)).items()}


DOCS = [[45, 70], [19, 90, 19]]


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 128 under windows of 32 and chunks of 4: a document
    of 45 (it ends inside a chunk and inside the second window) before one of
    70 and padding; and one of 19, one of 90 (it spans four windows) and one
    of 19: every document but a row's first starts inside a chunk."""
    return packed(DOCS, np.random.default_rng(3))


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    _cfg, ref, sizes, pcfg = tiny
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    named = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = transformer.Decoder(pcfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(treedef, [named[ref.ref_name(p)].reshape(a.shape) for p, a in flat])
    assert sorted(ref.ref_name(p) for p, _ in flat) == sorted(spec)  # every leaf has one name, every name a leaf
    leaves = {ref.to_reference(n): a for n, a in named.items()}  # in the reference's own names
    return leaves, model, params


def program_outputs(model, params, batch):
    return model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )


# ------------------------------------------------- summaries, masks, attention

B, ROW, H, D, W, C = 2, 256, 2, 64, 64, 8  # heads as wide as the kernels tile, in the interpreter


def rows_of_ids(docs, s):
    seg = np.zeros((len(docs), s), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            seg[r, at:at + n] = j + 1
            at += n
    return seg


@pytest.fixture(scope="module")
def pieces():
    """q, k, v, phi, mu and a cotangent at ``[2, 256, 2, 64]``, windows of 64,
    chunks of 8; documents of 37, 113, 51 and padding, and of 100 and 156."""
    keys = jax.random.split(jax.random.key(5), 6)
    q, k, v, g = (jax.random.normal(keys[i], (B, ROW, H, D), jnp.float32) for i in range(4))
    phi, mu = (0.5 * jax.random.normal(keys[i], (H, D), jnp.float32) for i in (4, 5))
    return (q, k, v, phi, mu), g, jnp.asarray(rows_of_ids([[37, 113, 51], [100, 156]], ROW))


def by_hand(q, k, v, phi, mu, seg, window=W, chunk=C):
    """One softmax over the explicit ``[S, S]`` and ``[S, S / C]`` scores side by side."""
    s, d = q.shape[1], q.shape[3]
    ks, vs = eva.summaries(k, v, phi, mu, seg, chunk)
    exact = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / d**0.5
    summed = jnp.einsum("bqhd,bkhd->bhqk", q, ks, precision="highest") / d**0.5
    local, remote = reference.masks(jnp.arange(s), seg, {"window": window, "chunk": chunk})
    scores = jnp.concatenate([
        jnp.where(local[:, None], exact, NEG_INF), jnp.where(remote[:, None], summed, NEG_INF),
    ], axis=-1)
    p = jax.nn.softmax(scores, axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :s], v, precision="highest")
            + jnp.einsum("bhqk,bkhd->bqhd", p[..., s:], vs, precision="highest"))


def on_kernels(q, k, v, phi, mu, seg, window=W, chunk=C):
    ks, vs = eva.summaries(k, v, phi, mu, seg, chunk)
    return eva.eva_attention(q, k, v, ks, vs, seg, window=window, chunk=chunk, interpret=True)


def on_xla(q, k, v, phi, mu, seg, window=W, chunk=C):
    ks, vs = eva.summaries(k, v, phi, mu, seg, chunk)
    return eva.eva_attention_xla(q, k, v, ks, vs, seg, window=window, chunk=chunk)


PATHS = {"kernels": on_kernels, "xla": on_xla}


def test_summaries_against_the_reference(pieces):
    (_q, k, v, phi, mu), _g, seg = pieces
    sizes = {"chunk": C}
    got = eva.summaries(k, v, phi, mu, seg, C)
    want = reference.summaries(k, v, phi, mu, seg, sizes)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    # chunk 4 of row 0 holds positions 32..39: the document that starts at 37 owns its last position,
    # so the summary weighs 37, 38, 39 alone and the five positions before the start not at all
    moved = eva.summaries(k.at[0, 32:37].add(3.0), v.at[0, 32:37].add(3.0), phi, mu, seg, C)
    np.testing.assert_array_equal(moved[0][0, 4], got[0][0, 4])
    np.testing.assert_array_equal(moved[1][0, 4], got[1][0, 4])
    crossed = reference.summaries(k, v, phi, mu, seg, sizes, {"fault": "summaries_cross_documents"})
    assert float(jnp.abs(crossed[0][0, 4] - want[0][0, 4]).max()) > 1e-3


def test_masks_against_the_reference_and_what_each_query_sees(pieces):
    _leaves, _g, seg = pieces
    sizes = {"window": W, "chunk": C}
    local, remote = reference.masks(jnp.arange(ROW), seg, sizes)
    np.testing.assert_array_equal(eva.remote_mask(seg, W, C), remote)
    local, remote = np.asarray(local), np.asarray(remote)
    # the query at 130 of row 0 (document 2, which starts at 37; its window starts at 128): the exact keys
    # 128..130, and the chunks 4..15 (chunk 4 ends at 39, inside the document; chunk 16 is its own window's)
    assert np.flatnonzero(local[0, 130]).tolist() == [128, 129, 130]
    assert np.flatnonzero(remote[0, 130]).tolist() == list(range(4, 16))
    # the query at 151 (document 3 starts at 150, inside chunk 18 and inside the third window): itself and 150, no summary
    assert np.flatnonzero(local[0, 151]).tolist() == [150, 151] and not remote[0, 151].any()
    assert not remote[:, :W].any()  # a row's first window has nothing before it
    none = reference.masks(jnp.arange(ROW), seg, sizes, {"fault": "no_summaries"})[1]
    assert not np.asarray(none).any()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_outputs_against_explicit_masks(pieces, path):
    leaves, _g, seg = pieces
    np.testing.assert_allclose(PATHS[path](*leaves, seg), by_hand(*leaves, seg), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_gradient_against_explicit_masks(pieces, path):
    leaves, g, seg = pieces
    grad = lambda f: jax.grad(lambda *a: (f(*a, seg) * g).sum(), argnums=(0, 1, 2, 3, 4))(*leaves)
    got, want = grad(PATHS[path]), grad(by_hand)
    for name, a, b in zip(("q", "k", "v", "phi", "mu"), got, want):
        assert float(jnp.abs(b).max()) > 0.1, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_chunks_of_one_with_zero_vectors_are_full_causal_attention(pieces, path):
    """C = 1, phi = 0, mu = 0: every summary is its token, so a query sees its
    window's keys and every key before the window: the whole causal row."""
    (q, k, v, _phi, _mu), _g, seg = pieces
    zero = jnp.zeros((H, D), jnp.float32)
    got = PATHS[path](q[:, :128], k[:, :128], v[:, :128], zero, zero, seg[:, :128], window=32, chunk=1)
    want = transformer.default_attention(q[:, :128], k[:, :128], v[:, :128], causal=True, segment_ids=seg[:, :128])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_later_byte_or_another_document_changes_no_output_bit(pieces, path):
    (q, k, v, phi, mu), _g, seg = pieces
    run = jax.jit(lambda q, k, v: PATHS[path](q, k, v, phi, mu, seg))
    base = run(q, k, v)
    # row 0, document 2 is 37..149: everything after position 100 changed, and every other document
    later = np.zeros((B, ROW, 1, 1), np.float32)
    later[0, 101:] = 1.0
    later[0, :37] = 1.0
    moved = run(q + later, k + 2.0 * later, v - later)
    np.testing.assert_array_equal(moved[0, 37:101], base[0, 37:101])
    np.testing.assert_array_equal(moved[1], base[1])
    assert float(jnp.abs(moved[0, 101:150] - base[0, 101:150]).max()) > 1e-3


def test_the_kernels_run_twice_forward_twice_backward_and_a_replay_runs_none(pieces):
    leaves, g, seg = pieces
    loss = lambda *a: (on_kernels(*a, seg) * g).sum()
    assert kernels(count(jax.make_jaxpr(loss)(*leaves).jaxpr)) == {"flash_fwd": 2}
    assert kernels(count(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*leaves).jaxpr)) == {
        "flash_fwd": 2, "flash_bwd": 2,
    }
    kept = jax.checkpoint(loss, policy=transformer.REMAT_POLICIES["nothing"])
    counted = count(jax.make_jaxpr(jax.grad(kept, argnums=(0, 1, 2, 3, 4)))(*leaves).jaxpr)
    assert kernels(counted) == {"flash_fwd": 2, "flash_bwd": 2}  # a replayed kernel would make flash_fwd 4
    assert all(counted["name:" + name] == 1 for name in FLASH_RESIDUALS)  # the joint output and log-sum-exp


def test_tiles_visited_share_counts_both_grids():
    seg = rows_of_ids([[300, 212], [512]], 512)
    t = eva.tiles(512, 128, 8, 64)
    assert t["local"][:2] == (128, 128) and t["remote"][:2] == (512, 64)
    # one tile a window, and the one block of 64 summaries that a row's 512 queries share
    assert eva.tiles_visited_share(seg, window=128, chunk=8, head_dim=64) == 1.0
    assert eva.tiles_visited_share(seg[:, :500], window=128, chunk=8) is None
    # at the cell's sizes a query block of the first window visits no summary
    big = np.ones((1, 16384), np.int32)
    t = eva.tiles(16384, 2048, 16, 128)
    assert t["local"] == (512, 512, 512, 512) and t["remote"] == (1024, 512, 512, 512)
    local = 8 * (1 + 2 + 3 + 4)  # a window of four blocks: the causal tiles
    remote = sum(-(-w * 128 // 512) for w in range(8) for _half in range(2))  # two query blocks a window
    assert eva.tiles_visited_share(big, window=2048, chunk=16) == pytest.approx((local + remote) / (8 * 16 + 16 * 2))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_remote_tiles_from_the_documents_runs_are_the_masks_tiles(seed):
    """What the host counts from the documents' runs is what the kernels'
    visit table finds in the selection itself, tile for tile."""
    from maggy_tpu.ops.flash import needed_tiles

    rng = np.random.default_rng(seed)
    s, window, chunk, bq, bk = 1024, 128, 8, 64, 16
    cuts = np.sort(rng.choice(np.arange(1, s), size=rng.integers(0, 9), replace=False))
    docs = np.diff([0, *cuts, s]).tolist()
    if seed % 2:
        docs[-1] = 0  # the row ends in padding where the last document stood
    seg = rows_of_ids([docs], s)
    if seed % 2:
        seg[0, sum(docs):] = 0
    want = needed_tiles(None, causal=False, sq=s, sk=s // chunk, block_q=bq, block_k=bk,
                        selected=eva.remote_mask(seg, window, chunk))[0]
    np.testing.assert_array_equal(eva.remote_tiles_needed(seg[0], window, chunk, bq, bk), want)


# --------------------------------------------------------------- the layer


def attention_leaves(pcfg, key):
    x = jnp.zeros((1, 2 * pcfg.eva_window, pcfg.d_model), jnp.float32)
    at = jnp.arange(x.shape[1])[None]
    params = transformer.Attention(pcfg, "eva_attention").init(key, x, at, jnp.ones_like(at))["params"]
    return nn.meta.unbox(params)


def test_a_row_within_one_window_is_full_attention_with_the_same_weights(tiny, batch):
    _cfg, _ref, sizes, pcfg = tiny
    wide = dataclasses.replace(pcfg, eva_window=S)  # the rows of 128 are one window
    params = attention_leaves(pcfg, jax.random.key(1))
    assert params["eva_phi"].shape == params["eva_mu"].shape == (4, 16)
    x = jax.random.normal(jax.random.key(2), (2, S, sizes["d_model"]), jnp.float32)
    got = transformer.Attention(wide, "eva_attention").apply(
        {"params": params}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )[0]
    plain = {k: v for k, v in params.items() if not k.startswith("eva_")}
    want = transformer.Attention(wide, "full_attention").apply({"params": plain}, x, batch["positions"], batch["segment_ids"])
    np.testing.assert_array_equal(got, want)
    narrow = transformer.Attention(pcfg, "eva_attention").apply(
        {"params": params}, x, batch["positions"], batch["segment_ids"], mutable=["intermediates"]
    )[0]
    assert float(jnp.abs(narrow - want).max()) > 1e-4  # four windows: the summaries stand in for exact keys


def test_the_dispatch_records_the_form_the_window_and_the_chunk(pieces):
    from maggy_tpu import telemetry

    (q, k, v, phi, mu), _g, seg = pieces
    ks, vs = eva.summaries(k, v, phi, mu, seg, C)
    tel = telemetry.Telemetry(worker="t")
    with telemetry.current(tel):
        out = transformer.auto_eva_attention(q, k, v, ks, vs, segment_ids=seg, window=W, chunk=C)
    (event,) = [e for e in tel.drain_events() if e["name"] == "attention.kernel"]
    np.testing.assert_array_equal(out, on_xla(q, k, v, phi, mu, seg))
    attrs = event["attrs"]
    assert (attrs["kernel"], attrs["form"], attrs["window"], attrs["chunk"]) == ("xla_dense", "eva", W, C)
    assert "backend is cpu" in attrs["reason"] and attrs["segmented"] is True
    with telemetry.current(tel):
        big = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
        transformer.record_attention_kernel("flash", big, big, seg, window=2048, chunk=16)
        transformer.record_attention_kernel("flash", big, big, seg)
    eva_event, plain = [e["attrs"] for e in tel.drain_events() if e["name"] == "attention.kernel"]
    assert (eva_event["form"], eva_event["chunk"], eva_event["window"]) == ("eva", 16, 2048)
    assert [eva_event[n] for n in ("block_q", "block_k", "bwd_block_q", "bwd_block_k")] == [512, 512, 512, 512]
    assert eva_event["remote_blocks"] == [1024, 512, 512, 512] and eva_event["lanes"] == "full"
    assert "form" not in plain and "chunk" not in plain and "remote_blocks" not in plain and plain["window"] == 0


# ------------------------------------------------- the heads' losses at document ends


def test_the_further_heads_loss_masks_each_offset_at_document_ends():
    """Head ``i`` predicts the byte ``i + 1`` ahead: a position counts where
    that byte lies in its document, so a document of ``n`` bytes gives head
    ``i`` ``n - 1 - i`` targets and one shorter than ``i + 2`` none."""
    rng = np.random.default_rng(0)
    batch = packed([[3, 9, 20], [32]], rng, s=32)
    logits = jnp.asarray(rng.normal(size=(2, 32, 8, 320)), jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok, seg = np.asarray(batch["tokens"]), np.asarray(batch["segment_ids"])
    each = []
    for i in range(8):
        ll, n = 0.0, 0
        for r in range(2):
            for t in range(32 - i - 1):
                if seg[r, t] > 0 and seg[r, t + i + 1] == seg[r, t]:
                    ll, n = ll + float(logp[r, t, i, tok[r, t + i + 1]]), n + 1
        assert n == sum(max(d - 1 - i, 0) for d in (3, 9, 20, 32))
        each.append(-ll / n)
    np.testing.assert_allclose(trainer_mod.lm_loss_fn(logits[:, :, 0], batch), each[0], rtol=1e-5)
    mods = {"intermediates": {"mtp_logits": (logits[:, :, 1:],)}}
    np.testing.assert_allclose(trainer_mod.mtp_loss(mods, batch), np.mean(each[1:]), rtol=1e-5)
    one = {"intermediates": {"mtp_logits": (logits[:, :, 1],)}}  # the older form: one head, two ahead
    np.testing.assert_allclose(trainer_mod.mtp_loss(one, batch), each[1], rtol=1e-5)
    assert trainer_mod.mtp_loss({}, batch) is None


# ------------------------------------------------------------------- refusals


EVA = dict(layer_types=("eva_attention",) * 2, n_layers=2, n_heads=4, n_kv_heads=4, eva_window=32, eva_chunk=4)


@pytest.mark.parametrize("fields,says", [
    (dict(EVA, decode=True), "no decode state"),
    (dict(EVA, eva_chunk=5), "eva_chunk that divides"),
    (dict(EVA, eva_chunk=0), "needs eva_window"),
    (dict(EVA, eva_window=0), "needs eva_window"),
    (dict(EVA, n_kv_heads=2), "n_kv_heads == n_heads"),
    (dict(EVA, attn_gate=True), "ungated"),
    (dict(EVA, sparse_topk=8, index_heads=2, index_head_dim=8), "Attention's"),
    (dict(EVA, attention_fn=transformer.default_attention), "automatic dispatch"),
    (dict(pred_heads=0), "pred_heads"),
    (dict(pred_heads=8, tie_embeddings=True), "untied"),
], ids=["decode", "chunk_off_window", "no_chunk", "no_window", "grouped_keys", "gate", "selection", "attention_fn",
        "no_head", "tied_heads"])
def test_config_refuses(fields, says):
    with pytest.raises(ValueError, match=says):
        transformer.DecoderConfig.tiny(**fields)


def test_the_new_fields_default_to_the_model_every_other_cell_builds():
    cfg = transformer.DecoderConfig.tiny()
    assert (cfg.eva_window, cfg.eva_chunk, cfg.pred_heads, cfg.norm_unit_offset, cfg.residual_f32) == (0, 0, 1, False, False)
    assert cfg.mtp_weight == 0.0 and "eva_attention" in transformer.LAYER_KINDS
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = nn.meta.unbox(transformer.Decoder(cfg).init(jax.random.key(0), tokens)["params"])
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any("eva_" in n for n in names) and params["lm_head"]["kernel"].shape == (64, 256)
    assert float(params["final_norm"]["scale"][0]) == 1.0
    logits, mods = transformer.Decoder(cfg).apply({"params": params}, tokens, mutable=["intermediates"])
    assert logits.shape == (1, 16, 256) and not mods.get("intermediates")
    eight = dataclasses.replace(cfg, pred_heads=8, norm_unit_offset=True, residual_f32=True)
    params = nn.meta.unbox(transformer.Decoder(eight).init(jax.random.key(0), tokens)["params"])
    assert params["lm_head"]["kernel"].shape == (64, 8 * 256) and float(params["final_norm"]["scale"][0]) == 0.0
    logits, mods = transformer.Decoder(eight).apply({"params": params}, tokens, mutable=["intermediates"])
    assert logits.shape == (1, 16, 256) and mods["intermediates"]["mtp_logits"][0].shape == (1, 16, 7, 256)
    assert eight.mtp_weight == 7.0


@pytest.mark.parametrize("s,window,chunk,says", [
    (100, 32, 4, "the window the row"), (128, 32, 5, "chunk divides the window"),
], ids=["row_off_window", "chunk_off_window"])
def test_a_row_off_the_grid_is_refused_on_both_paths(s, window, chunk, says):
    q = jnp.zeros((1, s, 2, 64), jnp.float32)
    ks = jnp.zeros((1, max(s // chunk, 1), 2, 64), jnp.float32)
    for call in (eva.eva_attention_xla, eva.eva_attention):
        with pytest.raises(ValueError, match=says):
            call(q, q, q, ks, ks, None, window=window, chunk=chunk)


def test_heads_the_kernels_cannot_tile_are_refused_by_name():
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    ks = jnp.zeros((1, 32, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="head_dim 16"):
        eva.eva_attention(q, q, q, ks, ks, None, window=32, chunk=4, interpret=True)
    assert eva.untileable(16384, 2048, 16, 128, compiled=True) is None
    assert "multiple of 128" in eva.untileable(4096, 2048, 64, 128, compiled=True)  # 64 summaries: less than the lanes
    assert eva.untileable(4096, 2048, 64, 128, compiled=False) is None
