"""``head_dot_general``, the ``dot_general`` that every projection of
``Attention`` to heads takes (``wq``, ``wk``, ``wv``), ``merge_dot_general``,
the same rule turned round for ``wo`` (heads and width contracted as one), and
``matrix_dot_general``, the rule for the three matrices of ``MLPBlock``:
the forward is ``jax.lax.dot_general``'s bit for bit, the backward rule gives
autodiff's two gradients to the order of the float32 sums, alone, under
``jax.checkpoint`` inside ``nn.scan`` and unrolled, and on a sharded mesh, in
bfloat16 and float32; the weight's gradient is held by a barrier always, the
input's where the product narrows; the modules' parameters and the decode path
do not change."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from maggy_tpu.models import Decoder, DecoderConfig, MoEConfig, transformer
from maggy_tpu.models.moe import ExpertShareBlock
from maggy_tpu.models.transformer import (
    Attention, LatentAttention, MLPBlock, head_dot_general, matrix_dot_general, merge_dot_general,
)
from maggy_tpu.parallel.mesh import make_mesh
from maggy_tpu.parallel.spec import ShardingSpec

D = 96  # the model's width here; heads, head widths and feed-forward widths are the cells'
RULES = {"head": head_dot_general, "matrix": matrix_dot_general, "merge": merge_dot_general}


def dims(x):
    return (((x.ndim - 1,), (0,)), ((), ()))


def merge_dims(x):
    """``wo``'s contraction as ``nn.DenseGeneral(axis=(-2, -1))`` hands it over."""
    return (((x.ndim - 2, x.ndim - 1), (0, 1)), ((), ()))


def plain(x, w, dimension_numbers, precision=None, preferred_element_type=None):
    return jax.lax.dot_general(x, w, dimension_numbers)


def operands(heads, width, dtype, batch=2, tokens=24):
    kx, kw, kt = jax.random.split(jax.random.key(heads * 1000 + width), 3)
    x = jax.random.normal(kx, (batch, tokens, D), dtype)
    w = (jax.random.normal(kw, (D, heads, width), jnp.float32) * 0.05).astype(dtype)
    t = jax.random.normal(kt, (batch, tokens, heads, width), dtype)
    return x, w, t


def close(a, b, dtype):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    # float32: the order of the sums alone; bfloat16: one rounding of a result summed in another order
    rtol = 2e-5 if dtype == jnp.float32 else 2**-7
    np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol * float(np.abs(b).max()))


# the six cells that run Attention: query heads, key heads, head width (wq's form, wk's and wv's, and wo's turned one)
CELLS = {"laguna-sliding": (72, 8, 128), "laguna-full": (48, 8, 128), "mistral": (32, 8, 128),
         "lfm2": (32, 8, 64), "keye": (32, 4, 128), "evabyte": (32, 32, 128), "sdar": (32, 4, 128)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("cell", CELLS)
def test_rule_against_autodiff_at_the_cells_head_forms(cell, dtype):
    """``wq``'s form (wide in the Laguna, Keye and SDAR cells, square in the
    others), the key and value heads' and ``wo``'s, ``[.., heads, width]`` by
    ``[heads, width, D]``: the forward's bits and both gradients."""
    q_heads, kv_heads, width = CELLS[cell]
    xq, wq, tq = operands(q_heads, width, dtype)
    xk, wk, tk = operands(kv_heads, width, dtype)
    to, wo, xo = xq, jnp.moveaxis(wq, 0, -1), tq  # wo reads what wq's form writes: [B, S, heads, width] -> [B, S, D]
    cases = ((head_dot_general, dims, tq), (head_dot_general, dims, tk), (merge_dot_general, merge_dims, to))

    @jax.jit
    def both(*operands):
        pairs = list(zip(operands[::2], operands[1::2]))

        def objective(rules):
            return lambda *a: sum(
                (rule(x, w, form(x)).astype(jnp.float32) * t.astype(jnp.float32)).sum()
                for (rule, form, t), x, w in zip(rules, a[::2], a[1::2])
            )

        plains = [(plain, form, t) for _, form, t in cases]
        outs = [rule(x, w, form(x)) for rules in (cases, plains) for (rule, form, _), (x, w) in zip(rules, pairs)]
        return outs, [jax.grad(objective(rules), argnums=tuple(range(6)))(*operands) for rules in (cases, plains)]

    args = (xq, wq, xk, wk, xo, wo)
    outs, (grads, ref) = both(*args)
    for out, r, shape in zip(outs[:3], outs[3:], ((2, 24, q_heads, width), (2, 24, kv_heads, width), (2, 24, D))):
        assert out.dtype == dtype and out.shape == shape
        assert np.array_equal(np.asarray(out, np.float32), np.asarray(r, np.float32))  # the forward's bits
    for g, r, operand in zip(grads, ref, args):
        assert g.dtype == operand.dtype and g.shape == operand.shape
        close(g, r, dtype)


# the five cells that run MLPBlock: the feed-forward's width (the shared expert's where the cell has one)
FEED_FORWARDS = {"mistral": 14336, "glm-dense": 10240, "glm-shared": 1536, "lfm2": 11776, "laguna-dense": 12288,
                 "laguna-shared": 1024, "evabyte": 11008}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("cell", FEED_FORWARDS)
def test_matrix_rule_against_autodiff_at_the_cells_feed_forward_widths(cell, dtype):
    """``w_gate`` and ``w_up``'s form ``[D, d_ff]`` and ``w_down``'s ``[d_ff, D]``."""
    d_ff = FEED_FORWARDS[cell]
    kx, kw, kt = jax.random.split(jax.random.key(d_ff), 3)
    xs = [jax.random.normal(k, (2, 24, d), dtype) for k, d in zip(jax.random.split(kx), (D, d_ff))]
    ws = [(jax.random.normal(k, shape, jnp.float32) * 0.05).astype(dtype)
          for k, shape in zip(jax.random.split(kw), ((D, d_ff), (d_ff, D)))]
    ts = [jax.random.normal(k, (2, 24, d), dtype) for k, d in zip(jax.random.split(kt), (d_ff, D))]

    @jax.jit
    def both(xs, ws):
        def objective(dot):
            return lambda xs, ws: sum(
                (dot(x, w, dims(x)).astype(jnp.float32) * t.astype(jnp.float32)).sum() for x, w, t in zip(xs, ws, ts)
            )

        outs = [[dot(x, w, dims(x)) for x, w in zip(xs, ws)] for dot in (matrix_dot_general, plain)]
        return outs, [jax.grad(objective(dot), argnums=(0, 1))(xs, ws) for dot in (matrix_dot_general, plain)]

    (outs, ref_outs), (grads, ref) = both(xs, ws)
    for out, r, t in zip(outs, ref_outs, ts):
        assert out.dtype == dtype and out.shape == t.shape
        assert np.array_equal(np.asarray(out, np.float32), np.asarray(r, np.float32))  # the forward's bits
    for g, r, operand in zip(jax.tree.leaves(grads), jax.tree.leaves(ref), (*xs, *ws)):
        assert g.dtype == operand.dtype and g.shape == operand.shape
        close(g, r, dtype)


@pytest.mark.parametrize("form", RULES)
def test_rule_refuses_what_it_was_not_written_for(form):
    x, w, t = operands(4, 16, jnp.float32)
    kernels = {"head": w, "matrix": w.reshape(D, 64), "merge": jnp.moveaxis(w, 0, -1)}
    rule, name, w = RULES[form], f"{form}_dot_general", kernels.pop(form)
    x, form_of = (t, merge_dims) if form == "merge" else (x, dims)
    with pytest.raises(ValueError, match=name):
        rule(x, kernels["head" if form == "matrix" else "matrix"], form_of(x))  # a kernel of another rank
    with pytest.raises(ValueError, match=name):
        rule(x, w, (((1,), (0,)), ((), ())))  # another contraction
    with pytest.raises(ValueError, match=name):
        rule(x, w, form_of(x), precision=jax.lax.Precision.HIGHEST)
    with pytest.raises(ValueError, match=name):
        rule(x, w, form_of(x), preferred_element_type=jnp.float32)


def tiny(**kw):
    """A tiny decoder whose ``wq`` is wider than the model (4 heads of 32 from
    64) and whose ``wk`` and ``wv`` are square."""
    return DecoderConfig.tiny(**{"head_width": 32, **kw})


def rule_calls(monkeypatch, form="head"):
    """Counts the projections that reach the rule of ``form`` from here on."""
    calls, rule = [], RULES[form]
    monkeypatch.setattr(
        transformer, f"{form}_dot_general", lambda x, w, *a, **k: calls.append(w.shape) or rule(x, w, *a, **k)
    )
    return calls


def without_rules(monkeypatch):
    """Every projection takes ``DenseGeneral``'s own ``dot_general`` from here on."""
    for form in RULES:
        monkeypatch.setattr(transformer, f"{form}_dot_general", None)


@pytest.mark.parametrize(
    "cell,d_model,kinds",
    [("laguna", 3072, {"full_attention": 48, "sliding_attention": 72}), ("keye", 2048, {"full_attention": 32}),
     ("mistral", 4096, {"full_attention": 32}), ("lfm2", 2048, {"full_attention": 32}),
     ("evabyte", 4096, {"full_attention": 32}), ("sdar", 2048, {"full_attention": 32}), ("glm", 2048, {})],
)
def test_which_projections_take_the_rule(monkeypatch, cell, d_model, kinds):
    """At the cells' shapes. An ``Attention`` whose leaves AdamW reads (the
    EvaByte cell's unrolled layers, the Laguna and LFM2 cells' leading layer
    and period of one): all four projections, wide, square or the few key and
    value heads: ``wq``, ``wk`` and ``wv`` the head form, ``wo`` the merged
    one, each once. One layer of a scan over several (Mistral, Keye, SDAR):
    ``wq`` where it is wider than the model (Keye, SDAR), nothing else.
    Latent attention (the GLM cell) takes none."""
    head_calls, merge_calls = rule_calls(monkeypatch), rule_calls(monkeypatch, "merge")
    if cell == "glm":
        cfg = DecoderConfig(
            d_model=d_model, n_heads=20, n_kv_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
            qk_rope_head_dim=64, v_head_dim=256, max_seq_len=16,
        )
        x, ids = jax.ShapeDtypeStruct((1, 16, d_model), cfg.dtype), jax.ShapeDtypeStruct((1, 16), jnp.int32)
        jax.eval_shape(LatentAttention(cfg).init, jax.random.key(0), x, ids)
        assert head_calls == merge_calls == []
        return
    kv_heads, width = {"laguna": (8, 128), "keye": (4, 128), "mistral": (8, 128), "lfm2": (8, 64),
                       "evabyte": (32, 128), "sdar": (4, 128)}[cell]
    stacked = cell in ("mistral", "keye", "sdar")
    cfg = DecoderConfig(
        d_model=d_model, n_heads=kinds["full_attention"], n_kv_heads=kv_heads, head_width=width, max_seq_len=16,
        **({"sliding_heads": kinds["sliding_attention"], "sliding_window": 8} if "sliding_attention" in kinds else {}),
    )
    x, ids = jax.ShapeDtypeStruct((1, 16, d_model), cfg.dtype), jax.ShapeDtypeStruct((1, 16), jnp.int32)
    for kind, heads in kinds.items():
        del head_calls[:], merge_calls[:]
        jax.eval_shape(Attention(cfg, kind, stacked).init, jax.random.key(0), x, ids)
        if stacked:
            assert head_calls == [(d_model, heads, width)] * (cell != "mistral"), head_calls
            assert merge_calls == []
        else:
            assert head_calls == [(d_model, heads, width)] + 2 * [(d_model, kv_heads, width)], (kind, head_calls)
            assert merge_calls == [(heads, width, d_model)], (kind, merge_calls)


@pytest.mark.parametrize(
    "model,overrides,stacked",
    [
        ("dense", dict(n_layers=2), {"layers/layer/attn": True}),
        ("dense", dict(n_layers=1), {"layers/layer/attn": False}),
        ("dense", dict(n_layers=2, scan_layers=False), {"layers_0/layer/attn": False, "layers_1/layer/attn": False}),
        ("experts", dict(n_layers=2), {"layers/layer/attn": True}),
        ("experts", dict(n_layers=3, n_dense_layers=1), {"dense_0/layer/attn": False, "layers/layer/attn": True}),
        ("experts", dict(n_layers=2, n_dense_layers=1), {"dense_0/layer/attn": False, "layers/layer/attn": False}),
        ("experts", dict(n_layers=2, scan_layers=False), {"layers_0/layer/attn": False, "layers_1/layer/attn": False}),
        ("experts", dict(n_layers=2, layer_types=("conv", "full_attention")), {"layers/layer_1/layer/attn": False}),
        ("experts", dict(n_layers=4, layer_types=("conv", "full_attention") * 2), {"layers/layer_1/layer/attn": True}),
        ("experts", dict(n_layers=3, layer_types=("conv", "full_attention", "conv")), {"layers/layer_1/layer/attn": False}),
    ],
    ids=["scan-of-two", "scan-of-one", "unrolled", "experts-scan-of-two", "experts-leading-layer-and-scan-of-two",
         "experts-leading-layer-and-scan-of-one", "experts-unrolled", "period-of-two-once", "period-of-two-twice",
         "period-of-two-once-and-a-tail"],
)
def test_the_stack_builders_say_which_layers_are_stacked(model, overrides, stacked):
    """``Decoder``, ``MoEDecoder`` and its period hand ``Attention`` the one
    fact it cannot see: whether its layer is one of a scan over several, whose
    gradients land in the scan's stacked buffer. Unrolled layers, the leading
    dense layers, the tail, and a scan of length one, which XLA unrolls, are
    not: their leaves are what AdamW reads."""
    from maggy_tpu.models.moe import MoEDecoder

    if model == "dense":
        built = Decoder(DecoderConfig.tiny(**overrides))
    else:
        built = MoEDecoder(MoEConfig.tiny_moe(**overrides))
    seen = {}

    def note(next_fun, args, kwargs, context):
        if isinstance(context.module, Attention) and context.method_name == "__call__":
            seen["/".join(context.module.path)] = context.module.stacked
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(note):
        jax.eval_shape(built.init, jax.random.key(0), jax.ShapeDtypeStruct((1, 16), jnp.int32))
    assert seen == stacked


def decoder_grads(cfg, variables, tokens):
    def objective(params):
        return jnp.square(Decoder(cfg).apply({"params": params}, tokens).astype(jnp.float32)).mean()

    return jax.jit(jax.value_and_grad(objective))(nn.meta.unbox(variables["params"]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_rule_under_checkpoint_inside_scan(monkeypatch, scan_layers, dtype):
    """A decoder, scanned or unrolled, each layer under ``jax.checkpoint`` with
    the policy ``nothing``: loss and every leaf's gradient as with plain
    ``dot_general``s. Unrolled, ``wq``, ``wk`` and ``wv`` take the head form
    and ``wo`` the merged one; under the scan of two layers the wide ``wq``
    alone does; the feed-forward's three matrices take the matrix form in
    both. The forms' ``dx`` (``wq``'s, ``w_gate``'s and ``w_up``'s held by the
    barrier, ``wk``'s, ``wv``'s, ``wo``'s and ``w_down``'s not) reach every
    leaf below them: the norms' scales, the layers under the last, the
    embedding."""
    cfg = tiny(dtype=dtype, scan_layers=scan_layers, remat=True, remat_policy="nothing")
    tokens = jnp.asarray(np.arange(2 * 16).reshape(2, 16) % cfg.vocab_size, jnp.int32)
    variables = jax.jit(Decoder(cfg).init)(jax.random.key(3), tokens)
    calls, matrix_calls, merge_calls = (rule_calls(monkeypatch, form) for form in ("head", "matrix", "merge"))
    loss, grads = decoder_grads(cfg, variables, tokens)
    wide, merged = (cfg.d_model, cfg.n_heads, 32), (cfg.n_heads, 32, cfg.d_model)
    assert set(calls) == ({wide} if scan_layers else {wide, (cfg.d_model, cfg.n_kv_heads, 32)})
    assert set(matrix_calls) == {(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)}
    assert set(merge_calls) == (set() if scan_layers else {merged})
    without_rules(monkeypatch)
    ref_loss, ref_grads = decoder_grads(cfg, variables, tokens)
    assert float(loss) == float(ref_loss)
    # float32: the order of the sums; bfloat16: a rounding of each product on the way down, against the leaf's largest
    rtol, atol = (2e-4, 2e-6) if dtype == jnp.float32 else (2**-5, 2**-7)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(g, r, rtol=rtol, atol=atol * float(np.abs(r).max()), err_msg=str(path))


@pytest.mark.parametrize(
    "form,degrees,tokens,kernel,out",
    [
        ("head", dict(dp=2, fsdp=2, tp=2), P(("data", "fsdp")), P("fsdp", "tensor"), P(("data", "fsdp"), None, "tensor")),
        ("matrix", dict(dp=2, fsdp=2, tp=2), P(("data", "fsdp")), P("fsdp", "tensor"), P(("data", "fsdp"), None, "tensor")),
        ("matrix", dict(dp=2, fsdp=2, tp=2), P(("data", "fsdp"), None, "tensor"), P("tensor", "fsdp"), P(("data", "fsdp"))),
        ("matrix", dict(fsdp=2, sp=4), P("fsdp", "seq"), P("fsdp"), P("fsdp", "seq")),
        ("down", dict(dp=2, fsdp=2, tp=2), P(("data", "fsdp"), None, "tensor"), P("tensor", "fsdp"), P(("data", "fsdp"))),
        ("merge", dict(dp=2, fsdp=2, tp=2), P(("data", "fsdp"), None, "tensor"), P("tensor", None, "fsdp"), P(("data", "fsdp"))),
    ],
    ids=["heads-on-tensor", "gate-mlp-on-tensor", "down-mlp-on-tensor", "seq-sharded", "down-widens-on-tensor",
         "wo-heads-on-tensor"],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rule_on_a_sharded_mesh(form, degrees, tokens, kernel, out, dtype):
    """Tokens over ``data`` x ``fsdp`` and the kernel over ``fsdp`` and
    ``tensor`` (the heads of ``[embed, heads, width]``; ``mlp`` in ``w_gate``'s
    and in ``w_down``'s orientation), and tokens over ``fsdp`` and ``seq``: the
    gradients come back in the operands' shardings and types and equal the
    unsharded rule's. ``dx`` is held by the barrier in the first four cases
    (96 inputs, 128 features) and left to autodiff in the last two, the matrix
    form at ``w_down``'s own shape (128 inputs, 96 features) and ``wo``'s
    (``[tokens, heads, width]`` by ``[heads, width, embed]``, heads over
    ``tensor``: 8 x 16 inputs read as 128, 96 features). (That the
    partitioner rematerialises nothing in a whole step on a ``seq``-sharded
    mesh is ``test_packed_sequences.py``'s to see: its tiny decoder's
    feed-forward takes the matrix form.)"""
    mesh, rule = make_mesh(ShardingSpec(**degrees)), RULES.get(form, matrix_dot_general)  # "down" is a matrix too
    x, w, t = operands(8, 16, dtype, batch=4)
    if form == "merge":
        x, w, t = t, jnp.moveaxis(w, 0, -1), x
    elif form != "head":
        w, t = w.reshape(D, 128), t.reshape(4, 24, 128)
    if form == "down":
        x, w, t = t, w.T, x
    xs, ws, ts = (NamedSharding(mesh, spec) for spec in (tokens, kernel, out))
    form_of = merge_dims if form == "merge" else dims

    def grads(x, w, t):
        return jax.grad(lambda x, w: (rule(x, w, form_of(x)) * t).sum(), argnums=(0, 1))(x, w)

    sharded = jax.jit(grads, in_shardings=(xs, ws, ts), out_shardings=(xs, ws))
    dx, dw = sharded(jax.device_put(x, xs), jax.device_put(w, ws), jax.device_put(t, ts))
    assert dx.sharding.is_equivalent_to(xs, x.ndim) and dw.sharding.is_equivalent_to(ws, w.ndim)
    assert dx.dtype == dw.dtype == dtype
    rdx, rdw = jax.jit(grads)(x, w, t)
    close(dx, rdx, dtype)
    close(dw, rdw, dtype)


def barriers(jaxpr):
    """The shapes that ``optimization_barrier``s hold in a jaxpr and in the jaxprs inside it."""
    held = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            held += [v.aval.shape for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            held += barriers(sub)
    return held


@pytest.mark.parametrize("form", RULES)
def test_which_gradients_the_barrier_holds(form):
    """In the backward's jaxpr: the weight's gradient is held always, as the
    matrix ``[d, features]`` (``wo``'s as ``[heads x width, d]``); the input's
    where the product narrows (``wq`` wider than the model, ``w_gate`` and
    ``w_up``: ``[tokens, features]`` by ``[features, d]`` with ``features >
    d``), not where it widens (``w_down``, whose ``dx`` is ``[tokens,
    d_ff]``; ``wo`` from more heads than the model is wide) or keeps the
    width (a square ``wq`` or ``wo``)."""
    x, w, t = operands(8, 16, jnp.bfloat16)  # 8 x 16 = 128 features from D = 96
    narrow, same = operands(2, 16, jnp.bfloat16), operands(6, 16, jnp.bfloat16)  # 32 and 96 features from 96
    cases = [((x, w, t), [(D, 128), x.shape]), (narrow, [(D, 32)]), (same, [(D, 96)])]
    if form == "matrix":
        cases = [((x, w.reshape(D, -1), t.reshape(2, 24, -1)), held) for (x, w, t), held in cases]
        cases.append(((t.reshape(2, 24, 128), w.reshape(D, 128).T, x), [(128, D)]))  # w_down's orientation
    if form == "merge":  # wo of a wide and of a square attention: dw held as the matrix, dx never
        cases = [((t, jnp.moveaxis(w, 0, -1), x), [(w.shape[1] * w.shape[2], D)]) for (x, w, t), _ in (cases[0], cases[2])]
    form_of = merge_dims if form == "merge" else dims
    for (x, w, t), held in cases:
        jaxpr = jax.make_jaxpr(
            jax.grad(lambda x, w: (RULES[form](x, w, form_of(x)).astype(jnp.float32) * t).sum(), argnums=(0, 1))
        )(x, w)
        assert sorted(barriers(jaxpr.jaxpr)) == sorted(held), (w.shape, barriers(jaxpr.jaxpr))


def partitioned_tree(boxed):
    return {
        "/".join(k.key for k in path): (leaf.value.shape, leaf.value.dtype, leaf.names)
        for path, leaf in jax.tree_util.tree_leaves_with_path(boxed, is_leaf=lambda a: isinstance(a, nn.Partitioned))
    }


@pytest.mark.parametrize("module", ["mlp", "shared-expert"])
def test_feed_forward_parameters_are_what_a_parent_checkpoint_holds(module):
    """Names, shapes, dtypes and logical axes of an ``MLPBlock``'s parameters,
    alone and as the shared expert of an ``ExpertShareBlock``."""
    f32 = jnp.dtype("float32")
    x = jnp.zeros((1, 8, 48), jnp.bfloat16)
    if module == "mlp":
        cfg, d_ff, under = DecoderConfig(d_model=48, n_heads=6, n_kv_heads=2, d_ff=80, max_seq_len=32), 80, ""
        boxed = jax.eval_shape(MLPBlock(cfg).init, jax.random.key(0), x)["params"]
    else:
        cfg = MoEConfig(
            d_model=48, n_heads=6, n_kv_heads=2, d_ff=80, moe_d_ff=24, n_experts=4, experts_held=2, top_k=2,
            n_shared_experts=2, max_seq_len=32,
        )
        d_ff, under = 48, "shared/"
        boxed = jax.eval_shape(ExpertShareBlock(cfg).init, jax.random.key(0), x)["params"]
    tree = {k: v for k, v in partitioned_tree(boxed).items() if k.startswith(under + "w_")}
    assert tree == {
        under + "w_gate/kernel": ((48, d_ff), f32, ("embed", "mlp")),
        under + "w_up/kernel": ((48, d_ff), f32, ("embed", "mlp")),
        under + "w_down/kernel": ((d_ff, 48), f32, ("mlp", "embed")),
    }


def test_attention_parameters_are_what_a_parent_checkpoint_holds():
    """Names, shapes, dtypes and logical axes of an ``Attention``'s parameters:
    the tree a checkpoint of the parent commit holds. ``wo`` reads its kernel
    as a matrix in the forward; the leaf stays ``[heads, width, embed]``."""
    cfg = DecoderConfig(d_model=48, n_heads=6, n_kv_heads=2, head_width=16, qk_norm=True, max_seq_len=32)  # wq is wide
    x, ids = jnp.zeros((1, 8, 48), cfg.dtype), jnp.zeros((1, 8), jnp.int32)
    boxed = jax.eval_shape(Attention(cfg).init, jax.random.key(0), x, ids)["params"]
    f32 = jnp.dtype("float32")
    assert partitioned_tree(boxed) == {
        "wq/kernel": ((48, 6, 16), f32, ("embed", "heads", None)),
        "wk/kernel": ((48, 2, 16), f32, ("embed", "kv", None)),
        "wv/kernel": ((48, 2, 16), f32, ("embed", "kv", None)),
        "wo/kernel": ((6, 16, 48), f32, ("heads", None, "embed")),
        "q_norm/scale": ((16,), f32, ("norm",)),
        "k_norm/scale": ((16,), f32, ("norm",)),
    }


def test_decode_values_unchanged(monkeypatch):
    """The decode path shares the forward: a prompt's prefill and a cached
    step give the bits ``DenseGeneral``'s own ``dot_general`` gives."""
    cfg = tiny(decode=True, n_layers=1)
    tokens = jnp.asarray(np.arange(2 * 10).reshape(2, 10) % cfg.vocab_size, jnp.int32)

    def run():
        model = Decoder(cfg)
        variables = jax.jit(model.init)(jax.random.key(5), tokens[:, :1])
        cache, outs = variables["cache"], []
        for lo, hi in ((0, 9), (9, 10)):
            positions = jnp.broadcast_to(jnp.arange(lo, hi), (2, hi - lo))
            logits, mods = jax.jit(functools.partial(model.apply, mutable=["cache"]))(
                {"params": variables["params"], "cache": cache}, tokens[:, lo:hi], positions
            )
            cache = mods["cache"]
            outs.append(np.asarray(logits, np.float32))
        return outs

    calls, matrix_calls, merge_calls = (rule_calls(monkeypatch, form) for form in ("head", "matrix", "merge"))
    ours = run()
    assert calls and matrix_calls and merge_calls  # the three forms stand in the decode path's forward
    without_rules(monkeypatch)
    for a, b in zip(ours, run()):
        assert np.array_equal(a, b)


def test_the_projections_share_reads_a_recorded_trace():
    """``train.attn_qkv_proj_share`` on the benchmark's recorded train trace
    (a small decoder's steps on a chip): the operations under ``attn/wq``,
    ``wk``, ``wv`` are a part of those under ``attn``; without a trace the
    reader returns nothing."""
    import os
    import types

    from benchmark import run, spans

    recorded = os.path.join(os.path.dirname(run.__file__), "checks", "recorded", "train.xplane.pb")
    cell = types.SimpleNamespace(trace_dir="recorded-train-trace", chips=1)
    spans._LOADED[cell.trace_dir] = spans.Timeline(recorded, chips=1, span_names=spans.TRAIN_SPANS)
    try:
        obs = {"cell": cell, "trace": {}, "needed_flops": 1.0}
        share = run.reader("train.attn_qkv_proj_share").read(obs)
        assert 0.0 < share < run.reader("train.scope_attn_share").read(obs) < 100.0
        assert run.reader("train.attn_qkv_proj_share").read({"cell": cell, "trace": None}) is None
    finally:
        del spans._LOADED[cell.trace_dir]
