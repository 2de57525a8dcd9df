"""The glm4_moe_lite family (latent attention, a sigmoid-routed dropless expert
share layer beside a shared expert, a leading dense layer, multi-token
prediction) against its plain reference ``benchmark/references/mla_moe.py``,
on seeded weights at small sizes with the published ratios
(``benchmark/checks/tiny.glm-4.7-flash.json``).

Both sides compute in float32 here, so what differs is the order of the sums:
tolerances are a few float32 roundings of the compared quantity's scale
(``TOL``), except where a note says otherwise. The chip run's comparison, in
bfloat16, is the cell's (``benchmark/kinds/train_packed_ref.py``).
"""

import dataclasses
import functools
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
from benchmark.references import mla_moe  # noqa: E402
from maggy_tpu.models import moe, sown, transformer  # noqa: E402
from maggy_tpu.ops.flash import flash_attention  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

KIND = "train_packed_ref"
# float32 sums in another order: a few roundings (1.2e-7 each) of the
# quantity's own scale, over reductions a few hundred long
TOL = dict(rtol=2e-5, atol=2e-6)
SEED = 11


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(REPO, "benchmark", "checks", "tiny.glm-4.7-flash.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load("benchmark/configs/glm-4.7-flash.json"), small)
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = dict(ref.program_fields(cfg, KIND), dtype=jnp.float32, remat=False, max_seq_len=64)
    return cfg, ref, sizes, moe.MoEConfig(**fields)


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of 64: documents of uneven length and some padding."""
    rng = np.random.default_rng(3)
    docs = [[20, 9, 30, 5], [40, 3, 14]]
    tok = rng.integers(1, 512, size=(2, 64), dtype=np.int32)
    pos, seg = np.zeros((2, 64), np.int32), np.zeros((2, 64), np.int32)
    for r, row in enumerate(docs):
        at = 0
        for j, n in enumerate(row):
            pos[r, at:at + n], seg[r, at:at + n] = np.arange(n), j + 1
            at += n
        tok[r, at:] = 0
    return {k: jnp.asarray(v) for k, v in
            dict(tokens=tok, positions=pos, segment_ids=seg, loss_mask=(seg > 0).astype(np.int32)).items()}


@pytest.fixture(scope="module")
def seeded(tiny, batch):
    """The reference's leaves from the seed, and the same numbers in the
    program's tree (as the benchmark's kind puts them there)."""
    _cfg, ref, sizes, pcfg = tiny
    spec, key = ref.leaf_spec(sizes), weights.base_key(SEED)
    leaves = {n: weights.stacked(key, n, spec, spec[n][1]) for n in spec}
    model = moe.MoEDecoder(pcfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), batch["tokens"]))["params"]
    shapes = nn.meta.unbox(shapes)  # the logical-axis boxes: placement only
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(
        treedef, [leaves[ref.ref_name(p)].reshape(a.shape) for p, a in flat]
    )
    assert {ref.ref_name(p) for p, _ in flat} == set(spec)  # every leaf has one name, every name a leaf
    return leaves, model, params


def moe_weights(leaves, layer=0):
    return {n: leaves[f"moe.{n}"][layer] for n in mla_moe.MOE_LEAVES}


def sub(params, *keys):
    for k in keys:
        params = params[k]
    return params


def test_latent_attention_output(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    x = jax.random.normal(jax.random.key(5), (2, 64, sizes["d_model"]), jnp.float32)
    got = transformer.LatentAttention(pcfg).apply(
        {"params": jax.tree.map(lambda a: a[0], sub(params, "layers", "layer", "attn"))},
        x, batch["positions"], batch["segment_ids"],
    )
    want = mla_moe.latent_attention(x, moe_weights(leaves), batch["positions"], batch["segment_ids"], sizes, None)
    np.testing.assert_allclose(got, want, **TOL)


def test_router_choices_and_weights(tiny, seeded):
    """The same experts chosen for every token (no near-tie flips at this
    seed: both sides score in float32) and the same weights on them."""
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, _params = seeded
    xn = jax.random.normal(jax.random.key(6), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    np.testing.assert_array_equal(pcfg.select_bias(), mla_moe.select_bias(sizes))
    logits = jnp.einsum("bsd,de->bse", xn, leaves["moe.router"][0], precision="highest")
    sel, w = moe.sigmoid_route(logits, bias, pcfg.top_k, pcfg.routed_scaling)
    sel_ref, w_ref = mla_moe.route(xn, leaves["moe.router"][0], bias, sizes)
    np.testing.assert_array_equal(sel, sel_ref)
    np.testing.assert_allclose(w, w_ref, **TOL)
    np.testing.assert_allclose(w.sum(-1), sizes["routed_scaling"], rtol=1e-5)  # normalised over the chosen, then scaled


def block_params(params):
    return jax.tree.map(lambda a: a[0], sub(params, "layers", "layer", "moe"))


def test_expert_layer_output_and_counters(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, _model, params = seeded
    xn = jax.random.normal(jax.random.key(7), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    got, mods = moe.ExpertShareBlock(pcfg).apply(
        {"params": block_params(params)}, xn, bias, mutable=["intermediates"]
    )
    want, slots = mla_moe.expert_layer(xn, moe_weights(leaves), bias, sizes)
    np.testing.assert_allclose(got, want, **TOL)
    load = mods["intermediates"]["expert_load"][0]
    assert load.shape == (sizes["held"],) and int(load.sum()) == int(slots) > 0
    assert int(mods["intermediates"]["slots_dropped"][0]) == 0


def test_shares_add_up_to_the_uncut_layer(tiny, seeded):
    """The guide's section 4: the routed parts that all the shares give, with
    the shared expert counted once, are the uncut layer's result."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    e, held = sizes["n_experts"], sizes["held"]
    key = jax.random.key(8)
    full = {n: 0.05 * jax.random.normal(jax.random.fold_in(key, i), (e, *s))
            for i, (n, s) in enumerate({"gate": (64, 48), "up": (64, 48), "down": (48, 64)}.items())}
    xn = jax.random.normal(jax.random.fold_in(key, 9), (2, 64, sizes["d_model"]), jnp.float32)
    bias = jnp.asarray(pcfg.select_bias()[0])
    base = block_params(params)
    total, load = 0.0, []
    for share in range(e // held):
        mine = dict(base, **{f"w_{n}": a[share * held:(share + 1) * held] for n, a in full.items()})
        y, mods = moe.ExpertShareBlock(dataclasses.replace(pcfg, expert_offset=share)).apply(
            {"params": mine}, xn, bias, mutable=["intermediates"]
        )
        total = total + y
        load.append(mods["intermediates"]["expert_load"][0])
    shared = moe.MLPBlock(dataclasses.replace(pcfg, d_ff=sizes["moe_d_ff"])).apply({"params": base["shared"]}, xn)
    uncut = dict(sizes, held=e, offset=0)
    w = {"router": base["router"]["kernel"], **{f"experts_{n}": a for n, a in full.items()},
         **{f"shared_{n}": base["shared"][f"w_{n}"]["kernel"] for n in ("gate", "up", "down")}}
    want, slots = mla_moe.expert_layer(xn, w, bias, uncut)
    np.testing.assert_allclose(total - (e // held - 1) * shared, want, rtol=2e-5, atol=1e-5)
    assert int(jnp.concatenate(load).sum()) == int(slots) == 2 * 64 * sizes["top_k"]  # every slot on exactly one share


def test_token_with_no_held_expert_gets_the_shared_expert_only(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    xn = jax.random.normal(jax.random.key(12), (1, 16, sizes["d_model"]), jnp.float32)
    bias = jnp.where(jnp.arange(sizes["n_experts"]) < sizes["held"], -10.0, 0.0)  # never a held expert
    base = block_params(params)
    y, mods = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias, mutable=["intermediates"])
    shared = moe.MLPBlock(dataclasses.replace(pcfg, d_ff=sizes["moe_d_ff"])).apply({"params": base["shared"]}, xn)
    np.testing.assert_allclose(y, shared, **TOL)
    assert int(mods["intermediates"]["expert_load"][0].sum()) == 0


# ------------------------------------------- the buffer, chunk by chunk


def as_reference(base):
    """A block's parameters under the reference's leaf names."""
    return {"router": base["router"]["kernel"],
            **{f"experts_{n}": base[f"w_{n}"] for n in ("gate", "up", "down")},
            **{f"shared_{n}": base["shared"][f"w_{n}"]["kernel"] for n in ("gate", "up", "down")}}


# slots on held experts -> rows of the chunks that hold them: 128 tokens x
# top-2 = 256 slots, chunks of 32 rows
LOADS = {"one_chunk": (20, 32), "some_chunks": (100, 128), "on_the_edge": (64, 64), "one_past_the_edge": (65, 96),
         "whole_buffer": (256, 256)}


def steered(base, sizes, n_slots):
    """[2, 64, d] inputs that put exactly ``n_slots`` slots on the two held
    experts: each token is noise plus a push along the router's columns, so
    that it chooses both held experts, the first alone, or neither."""
    w = base["router"]["kernel"]
    both, one = w[:, 0] + w[:, 1], w[:, 0] - w[:, 1]
    t = 128
    kinds = np.full(t, -1)
    kinds[:n_slots // 2], kinds[n_slots // 2:n_slots // 2 + n_slots % 2] = 2, 1
    kinds = np.random.default_rng(n_slots).permutation(kinds)
    push = {2: 12 * both / (both @ both), 1: 12 * one / (one @ one), -1: -12 * both / (both @ both)}
    x = 0.3 * jax.random.normal(jax.random.key(n_slots), (t, sizes["d_model"]), jnp.float32)
    x = x - (x @ w[:, :2]) @ jnp.linalg.pinv(w[:, :2])  # the noise leaves the held experts' logits alone
    return (x + jnp.stack([push[k] for k in kinds])).reshape(2, 64, -1)


@pytest.fixture(scope="module", params=list(LOADS))
def load_case(request, tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    base = block_params(seeded[2])
    n_slots, rows = LOADS[request.param]
    xn = steered(base, sizes, n_slots)
    bias = jnp.zeros(sizes["n_experts"])
    _, slots = mla_moe.expert_layer(xn, as_reference(base), bias, sizes)
    assert int(slots) == n_slots  # the steering holds, by the reference's own router
    return sizes, pcfg, base, xn, bias, n_slots, rows


def test_every_load_agrees_with_the_plain_reference(load_case):
    """Output, and the gradient of every leaf and of the input, at loads in
    the first chunk, in some, on a chunk's edge, one slot past it and in the
    whole buffer: every chunk is the same function. Gradients per leaf as
    the norm of the difference over the leaf's norm."""
    sizes, pcfg, base, xn, bias, _n, _rows = load_case
    cot = jnp.cos(jnp.arange(xn.size, dtype=jnp.float32)).reshape(xn.shape)

    def program(base, xn):
        y = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias)
        return (y * cot).sum(), y

    def reference(base, xn):
        y, _ = mla_moe.expert_layer(xn, as_reference(base), bias, sizes)
        return (y * cot).sum(), y

    (_, got), got_g = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(base, xn)
    (_, want), want_g = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(base, xn)
    np.testing.assert_allclose(got, want, **TOL)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-6)), got_g, want_g
    )
    worst = max(jax.tree_util.tree_leaves_with_path(gaps), key=lambda kv: kv[1])
    assert worst[1] < 1e-4, (jax.tree_util.keystr(worst[0]), worst[1])  # as test_gradient_of_every_leaf


def test_every_load_drops_nothing_and_visits_its_chunks(load_case):
    _sizes, pcfg, base, xn, bias, n_slots, rows = load_case
    _, mods = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias, mutable=["intermediates"])
    sown = mods["intermediates"]
    assert int(sown["expert_load"][0].sum()) == n_slots
    assert int(sown["slots_dropped"][0]) == 0
    assert [int(v) for v in sown["rows_visited"][0]] == [rows, 256]


def test_every_load_reads_the_tiles_that_hold_its_slots(load_case):
    """128 tokens are one block of the token-side sum, and the slots on the
    two held experts stand first in the buffer: the tiles of 16 rows up to
    the load, of 256 rows that the two gathers fetch."""
    _sizes, pcfg, base, xn, bias, n_slots, _rows = load_case
    _, mods = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias, mutable=["intermediates"])
    tile = moe.TOKEN_TILES[1]
    assert [int(v) for v in mods["intermediates"]["combine_rows"][0]] == [-(-n_slots // tile) * tile, 256]


@pytest.mark.parametrize("slots,held,n_experts,of_load,want", [
    (65536, 8, 64, 0.0, 8192),  # the GLM cell: an eighth of 16,384 x 4
    (65536, 64, 64, 0.0, 65536),  # every expert held: every slot is, one chunk
    (100, 2, 8, 0.0, 13),  # no multiple of 8: rounded up, the last chunk overhangs
    (131072, 8, 64, 0.0, 16384),  # the LFM2 cell: an eighth of 32,768 x 4
    (262144, 16, 128, 0.0, 32768),  # an eighth of the experts held: an eighth is the expected load itself
    (262144, 16, 128, 0.75, 24576),  # three quarters of the expected load
    (100, 2, 8, 0.75, 19),  # 18.75 rows: rounded up
    (65536, 64, 64, 0.75, 65536),  # every expert held: one chunk whatever the fraction
])
def test_chunk_rows(slots, held, n_experts, of_load, want):
    assert moe.chunk_rows(slots, held, n_experts, of_load) == want


def test_chunk_of_load_belongs_to_the_share_form():
    with pytest.raises(ValueError, match="chunk_of_load"):
        moe.MoEConfig(chunk_of_load=0.75)


def test_a_buffer_that_is_no_whole_number_of_chunks(tiny, seeded):
    """25 tokens x top-2 = 50 slots in chunks of 7: the eighth chunk overhangs
    the buffer, and the layer still is the plain reference's."""
    _cfg, _ref, sizes, pcfg = tiny
    base = block_params(seeded[2])
    xn = jax.random.normal(jax.random.key(21), (1, 25, sizes["d_model"]), jnp.float32)
    bias = jnp.where(jnp.arange(sizes["n_experts"]) < sizes["held"], 10.0, 0.0)  # every slot on a held expert
    got, mods = moe.ExpertShareBlock(pcfg).apply({"params": base}, xn, bias, mutable=["intermediates"])
    want, slots = mla_moe.expert_layer(xn, as_reference(base), bias, sizes)
    np.testing.assert_allclose(got, want, **TOL)
    assert int(slots) == 50 and [int(v) for v in mods["intermediates"]["rows_visited"][0]] == [50, 50]


@pytest.mark.parametrize("sizes", [(300, 0, 412), (0, 0, 0), (512, 256, 256)], ids=["an_empty_group", "no_slot", "full"])
def test_grouped_kernel_in_the_interpreter_is_the_ragged_dot(sizes):
    """The Pallas kernels a TPU runs the products through (``grouped_dot``),
    in the interpreter at their own tiles: the rows of the groups and both
    gradients as ``jax.lax.ragged_dot`` gives them (bfloat16 operands, float32
    sums; one reduction tile, so the same sums), rows past the groups left
    out of the comparison as the layer leaves them out."""
    m, k, n = 2 * moe.GROUPED_TILES[0], 64, 48
    x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (len(sizes), k, n), jnp.bfloat16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(m) < sum(sizes))[:, None]

    def run(product):
        def loss(x, w):
            y = jnp.where(live, product(x, w, group_sizes), 0)
            return (y.astype(jnp.float32) * jnp.cos(jnp.arange(n))).sum(), y
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(x, w)

    (_, got), (got_x, got_w) = run(functools.partial(moe.grouped_kernel, interpret=True))
    (_, want), (want_x, want_w) = run(jax.lax.ragged_dot)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(jnp.where(live, got_x, 0), jnp.where(live, want_x, 0))
    np.testing.assert_array_equal(got_w, want_w)
    assert moe.grouped_dot(x, w, group_sizes).shape == (m, n)  # off the TPU: ragged_dot itself


# the token-side sum: tokens, top_k, held of experts, width, type, (tokens a block, rows a tile, rows a buffer),
# rows of the buffer past the slots' (an overhanging last chunk), and how the choices are drawn
TOKEN_SUMS = {
    "float32_rows": (128, 2, 2, 8, 128, jnp.float32, (32, 8, 32), 0, "even"),
    # every choice held, loads off the tiles: the runs of neighbouring experts share tiles, and a
    # table that gave a shared tile to both (15 tiles for 12) sums its rows twice; six fills of the buffer
    "every_expert_held_in_one_block": (64, 3, 4, 4, 128, jnp.bfloat16, (64, 16, 32), 0, "even"),
    "tokens_no_multiple_of_the_block": (100, 4, 4, 4, 128, jnp.bfloat16, (32, 16, 64), 0, "even"),
    "top_k_8": (96, 8, 3, 16, 256, jnp.bfloat16, (32, 16, 32), 48, "even"),
    "top_k_10_one_expert_busy": (96, 10, 3, 16, 128, jnp.bfloat16, (32, 16, 32), 16, "busy"),
    "a_block_on_one_expert": (64, 4, 2, 8, 128, jnp.bfloat16, (32, 16, 64), 0, "block_on_one"),
    "no_slot_held": (64, 4, 2, 8, 128, jnp.bfloat16, (32, 16, 32), 0, "none_held"),
    "an_overhanging_last_chunk": (50, 2, 2, 8, 128, jnp.float32, (16, 8, 16), 12, "even"),
    # a buffer of two parts of ``PLACE_COLS`` columns, the second part partly filled
    "two_parts_of_the_placement": (192, 6, 6, 8, 128, jnp.bfloat16, (128, 16, 512), 0, "even"),
}


@pytest.fixture(scope="module", params=list(TOKEN_SUMS))
def token_sum_case(request):
    """Choices, their counting sort and the kernel's table, with rows that
    hold NaN from the load on (``_by_token``: "a row past the load may hold
    anything")."""
    t, k, held, n_experts, d, dtype, tiles, spare, how = TOKEN_SUMS[request.param]
    rng = np.random.default_rng(len(request.param))
    p = np.ones(n_experts)
    if how == "busy":
        p[0] = 30
    if how == "none_held":
        p[:held] = 1e-12
    sel = np.argsort(-(rng.gumbel(size=(t, n_experts)) + np.log(p)), axis=1)[:, :k]
    if how == "block_on_one":  # the second block's tokens all choose expert 1 and no other held one
        sel[32:] = np.argsort(-rng.gumbel(size=(32, n_experts - held)), axis=1)[:, :k] + held
        sel[32:, 2] = 1
    is_held = sel < held
    key = jnp.asarray(np.where(is_held, sel, held).reshape(-1).astype(np.int32))
    inv, load, ends = moe.counting_sort(key, held + 1, every=tiles[0] * k)
    load, inv = load[:held], inv.reshape(t, k)
    runs = moe.token_tiles(ends[:, :held], load, tiles[1])
    n = (-(-t * k // tiles[1]) + spare // tiles[1]) * tiles[1]
    rows = jax.random.normal(jax.random.key(1), (n, d), jnp.float32).astype(dtype).at[int(load.sum()):].set(jnp.nan)
    weights = jax.random.uniform(jax.random.key(2), (t, k), jnp.float32, 0.1, 1.0).astype(dtype)
    if request.param in ("float32_rows", "top_k_8"):
        assert not is_held.any(-1).all()  # a token with no held choice is among them
    return rows, runs, load, inv, jnp.asarray(is_held), weights, tiles


@pytest.mark.parametrize("weighted", [True, False], ids=["combine", "dispatch_backward"])
def test_token_sum_kernel_in_the_interpreter_is_the_gathers(token_sum_case, weighted):
    """``slots_to_tokens`` (what a TPU runs in the place of ``_by_token``'s
    ``top_k`` gathers), in the interpreter at small tiles, with the weights
    (the forward's combine) and without (``d_tokens`` in the backward):
    bfloat16 rows to the bit after the cast (a product of two bfloat16 is
    exact in float32 and both sum in float32), float32 rows to 1e-6."""
    rows, runs, load, inv, is_held, weights, tiles = token_sum_case
    w = weights if weighted else None
    got = moe.slots_to_tokens(rows, runs, load.sum(), inv, is_held, w, tiles=tiles, interpret=True)
    want = moe._by_token(rows, inv, is_held, w).astype(rows.dtype)
    assert got.dtype == rows.dtype and bool(jnp.isfinite(want).all())
    if rows.dtype == jnp.bfloat16:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_token_tiles_are_the_tiles_that_hold_a_blocks_slots(token_sum_case):
    """The table against a count by numpy: for every block of tokens, the
    tiles listed (expert by expert, ``count`` from ``first``) ascend, none
    twice, and are the tiles in which a held choice of one of the block's
    tokens has its row. A tile that two experts' runs share and that the
    table gave to both would be summed twice."""
    _rows, runs, load, inv, is_held, _weights, (block, tile, _width) = token_sum_case
    first, count = np.asarray(runs)
    inv, is_held = np.asarray(inv), np.asarray(is_held)
    for b in range(first.shape[0]):
        listed = [tile_ for e in range(first.shape[1]) for tile_ in range(first[b, e], first[b, e] + count[b, e])]
        mine = slice(b * block, (b + 1) * block)
        assert listed == sorted(set(inv[mine][is_held[mine]] // tile))
    if first.shape == (1, 4):  # "every_expert_held_in_one_block": the case does have runs that share a tile
        hi = np.cumsum(np.asarray(load))
        assert int(((hi - 1) // tile - (hi - np.asarray(load)) // tile + 1).sum()) > int(count.sum()) == 12


@pytest.mark.parametrize("case", ["random", "ties_only", "empty_held_set", "one_key_absent", "single", "none"])
def test_counting_sort_is_the_stable_argsort(case):
    n_keys = 9
    rng = np.random.default_rng(4)
    key = {
        "random": rng.integers(0, n_keys, 4096),
        "ties_only": np.full(512, 3),
        "empty_held_set": np.full(512, n_keys - 1),  # every slot on an absent expert
        "one_key_absent": rng.choice([0, 1, 2, 4, 5, 6, 7, 8], 777),
        "single": np.array([5]),
        "none": np.zeros(0, np.int64),
    }[case].astype(np.int32)
    inv, load = jax.jit(moe.counting_sort, static_argnums=1)(jnp.asarray(key), n_keys)
    order = np.argsort(key, kind="stable")
    np.testing.assert_array_equal(np.asarray(inv)[order], np.arange(len(key)))
    np.testing.assert_array_equal(order, jnp.argsort(jnp.asarray(key), stable=True))
    np.testing.assert_array_equal(load, np.bincount(key, minlength=n_keys))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (list, tuple)) else [v]:
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _makers(jaxpr, shapes, found):
    """Names of the primitives, at any depth, that make a value of one of
    ``shapes``."""
    for eqn in jaxpr.eqns:
        if any(tuple(getattr(v.aval, "shape", ())) in shapes for v in eqn.outvars):
            found.add(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            _makers(sub, shapes, found)
    return found


def test_no_work_on_a_whole_buffer_of_rows(tiny, seeded):
    """In the toy model's loss and gradient (every layer recomputed, as the
    cell runs it) a value of ``[T * top_k, d_model]`` is the buffer the
    chunks are written into, empty at first, and nothing else: no gather, no
    ``where``, no sum makes one, nor one of ``[T, top_k, d_model]``. A
    whole-buffer gather that comes back fails here, on the CPU, and not only
    on the chip."""
    _cfg, _ref, sizes, pcfg = tiny
    _leaves, _model, params = seeded
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    b, s = 3, 16  # 48 tokens, 96 slots: [96, 64] is nothing else's shape
    batch = {k: jnp.ones((b, s), jnp.int32) for k in ("tokens", "positions", "segment_ids", "loss_mask")}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: program_loss(model, pcfg.mtp_weight, p, batch)[0]))(params)
    t, k, d = b * s, sizes["top_k"], sizes["d_model"]
    carriers = {"broadcast_in_dim", "dynamic_update_slice", "while", "pjit", "custom_vjp_call", "checkpoint",
                "custom_vjp_call_jaxpr", "scan", "custom_jvp_call", "remat"}
    assert _makers(jaxpr.jaxpr, {(t * k // 8, d)}, set()) - carriers >= {"gather", "mul"}  # the chunks' rows are seen
    assert _makers(jaxpr.jaxpr, {(t * k, d), (t, k, d)}, set()) <= carriers


def program_outputs(model, params, batch):
    logits, mods = model.apply(
        {"params": params}, batch["tokens"], batch["positions"], batch["segment_ids"],
        mutable=["intermediates"],
    )
    return logits, mods


@pytest.mark.parametrize("head", ["main", "mtp"])
def test_logits_of_both_heads(tiny, batch, seeded, head):
    """Embedding-scale inputs through three layers: the logits are of order
    1, so the absolute tolerance is a few roundings of that."""
    _cfg, _ref, sizes, _pcfg = tiny
    leaves, model, params = seeded
    logits, mods = program_outputs(model, params, batch)
    want = mla_moe.logits_of(leaves, batch, sizes)
    got = logits if head == "main" else mods["intermediates"]["mtp_logits"][0]
    np.testing.assert_allclose(got, want[head == "mtp"], rtol=1e-4, atol=2e-5)


def program_loss(model, mtp_weight, params, batch):
    logits, mods = program_outputs(model, params, batch)
    main = trainer_mod.lm_loss_fn(logits, batch)
    mtp = trainer_mod.mtp_loss(mods, batch)
    return main + mtp_weight * mtp, (main, mtp, sown.step_counters(mods))


def test_loss_parts_and_slots(tiny, batch, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    total, (main, mtp, counters) = program_loss(model, pcfg.mtp_weight, params, batch)
    want, parts = mla_moe.losses(leaves, batch, sizes)
    np.testing.assert_allclose([total, main, mtp], [want, parts["main"], parts["mtp"]], rtol=1e-5)
    assert float(counters["moe_slots"]) == float(parts["slots"]) and float(counters["moe_slots_dropped"]) == 0
    assert float(counters["moe_load_max_over_mean"]) >= 1.0


def test_mtp_targets_stay_in_the_predictors_document(batch):
    """Targets two ahead count only where positions i, i+1 and i+2 share a
    document: a document of n tokens gives n - 2 of them."""
    logits = jnp.zeros((2, 64, 7))
    _, weight = trainer_mod._lm_loss_parts(logits, dict(batch, tokens=batch["tokens"] % 7), ahead=2)
    docs = [20, 9, 30, 5, 40, 3, 14]
    assert int(weight) == sum(n - 2 for n in docs)
    _, weight1 = trainer_mod._lm_loss_parts(logits, dict(batch, tokens=batch["tokens"] % 7))
    assert int(weight1) == sum(n - 1 for n in docs)


def test_gradient_of_every_leaf(tiny, batch, seeded):
    """Per leaf: the norm of the difference over the leaf's norm, floored at
    the median leaf's (some gradients are all but zero). 1e-4: the loss's
    gradient passes three layers' worth of float32 sums in each direction."""
    _cfg, ref, sizes, pcfg = tiny
    leaves, model, params = seeded
    got = jax.grad(lambda p: program_loss(model, pcfg.mtp_weight, p, batch)[0])(params)
    want = jax.grad(lambda p: mla_moe.losses(p, batch, sizes)[0])(leaves)
    got = {n: np.asarray(a).reshape(want[n].shape) for n, a in ref.named_leaves(got).items()}
    assert set(got) == set(want)
    norms = {n: float(np.linalg.norm(a)) for n, a in want.items()}
    floor = float(np.median(list(norms.values())))
    assert all(v > 0 for v in norms.values())  # every leaf is trained, the router and the MTP module too
    worst = max((float(np.linalg.norm(got[n] - want[n])) / max(norms[n], floor), n) for n in want)
    assert worst[0] < 1e-4, worst


def test_selection_bias_gets_no_gradient_and_moves_the_choice(tiny, seeded):
    _cfg, _ref, sizes, pcfg = tiny
    logits = jax.random.normal(jax.random.key(2), (4, 16, sizes["n_experts"]))
    bias = jnp.zeros(sizes["n_experts"]).at[3].set(5.0)
    sel, w = moe.sigmoid_route(logits, bias, pcfg.top_k, 1.0)
    assert bool(jnp.all(jnp.any(sel == 3, axis=-1)))  # a large bias wins the selection
    g = jax.grad(lambda b: moe.sigmoid_route(logits, b, pcfg.top_k, 1.0)[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0  # and enters nothing else


# ------------------------------------------------------ the trainer's step


def test_trainer_step_reports_mtp_loss_and_counters(tiny, batch):
    import optax

    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    _cfg, _ref, _sizes, pcfg = tiny
    model = moe.MoEDecoder(dataclasses.replace(pcfg, remat=True, remat_policy="nothing"))
    tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
    host = {k: np.asarray(v) for k, v in batch.items()}
    state = tr.make_state(jax.random.key(0), host)
    state, out = tr.fit(state, iter([host] * 3), num_steps=3)
    assert {"loss", "mtp_loss", "total_loss", "moe_slots", "moe_slots_dropped", "moe_load_max_over_mean",
            "moe_rows_visited_share"} <= set(out)
    assert out["moe_slots_dropped"] == 0 and out["moe_slots"] > 0
    # three expert layers of 256 slots, each through the eighths that hold its load
    assert 0 < out["moe_rows_visited_share"] <= 1 and (out["moe_rows_visited_share"] * 24) % 1 == 0
    assert out["moe_rows_visited_share"] * 3 * 256 >= out["moe_slots"]
    np.testing.assert_allclose(out["total_loss"], out["loss"] + pcfg.mtp_weight * out["mtp_loss"], rtol=1e-5)


def test_dense_decoder_step_is_untouched():
    """A model that sows nothing gets no new metric (the Mistral cell's step)."""
    import optax

    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    model = transformer.Decoder(transformer.DecoderConfig.tiny())
    tr = trainer_mod.Trainer(model, optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
    host = {"tokens": np.ones((2, 16), np.int32)}
    state = tr.make_state(jax.random.key(0), host)
    _, out = tr.fit(state, iter([host]), num_steps=1)
    assert set(out) - {"steps_per_sec"} == {"loss", "aux_loss", "total_loss", "grad_norm", "step"}
    assert "moe_rows_visited_share" not in out


@pytest.mark.parametrize("gauge,key", [
    ("moe.rows_visited_share", "moe_rows_visited_share"), ("moe.combine_rows_share", "moe_combine_rows_share"),
])
def test_fit_publishes_the_rows_visited_gauge_for_a_share_model_only(tiny, batch, gauge, key):
    import optax

    from maggy_tpu import telemetry
    from maggy_tpu.parallel.mesh import make_mesh
    from maggy_tpu.parallel.spec import ShardingSpec

    mesh = make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1])
    runs = [
        (moe.MoEDecoder(tiny[3]), {k: np.asarray(v) for k, v in batch.items()}),
        (transformer.Decoder(transformer.DecoderConfig.tiny()), {"tokens": np.ones((2, 16), np.int32)}),
    ]
    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            if name == gauge:
                seen[-1].append(value)
            super().gauge(name, value)

    seen = []
    with telemetry.current(Recorder(worker="t")):
        for model, host in runs:
            seen.append([])
            tr = trainer_mod.Trainer(model, optax.adamw(1e-3), mesh)
            _, out = tr.fit(tr.make_state(jax.random.key(0), host), iter([host]), num_steps=1)
            if key in out:
                assert seen[-1] == [out[key]]
    assert [len(v) for v in seen] == [1, 0]


@pytest.mark.parametrize("bad", [
    dict(v_head_dim=0), dict(qk_rope_head_dim=7), dict(n_kv_heads=2), dict(decode=True),
    dict(experts_held=3), dict(expert_offset=4), dict(moe_d_ff=0), dict(n_dense_layers=3), dict(mtp_depth=2),
    dict(ablated=("attn",)),
])
def test_config_refuses_what_the_layers_cannot_do(tiny, bad):
    with pytest.raises(ValueError):
        dataclasses.replace(tiny[3], **bad)


# ------------------------------------------- the flash kernels at width 256


@pytest.mark.parametrize("packed", [False, True])
def test_flash_at_head_width_256_against_the_dense_path(packed):
    """The kernels (forward and fused backward) in the Pallas interpreter at
    the width latent attention gives them (192 + 64 = 256 = v), 128-row tiles, against
    ``default_attention``; packed rows mask across documents and skip tiles."""
    b, s, h, d = 2, 256, 2, 256
    q, k, v = (0.5 * jax.random.normal(jax.random.key(i), (b, s, h, d), jnp.float32) for i in range(3))
    seg = None
    if packed:
        seg = jnp.asarray(np.stack([np.repeat([1, 2, 3, 0], [100, 60, 90, 6]), np.repeat([1, 1, 2, 2], [128, 28, 50, 50])]))

    def run(fn, **kw):
        def f(q, k, v):
            out = fn(q, k, v, causal=True, segment_ids=seg, **kw)
            return jnp.sum(out * jnp.cos(jnp.arange(d))), out
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out, grads = run(flash_attention, block_q=128, block_k=128, interpret=True)
    want, want_grads = run(transformer.default_attention)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
