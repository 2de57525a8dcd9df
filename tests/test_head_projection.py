"""``head_dot_general``, the ``dot_general`` that a head-shaped projection of
``Attention`` takes where it is wider than the model, and
``matrix_dot_general``, the same rule for the three matrices of ``MLPBlock``:
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
from maggy_tpu.models.transformer import Attention, MLPBlock, head_dot_general, matrix_dot_general
from maggy_tpu.parallel.mesh import make_mesh
from maggy_tpu.parallel.spec import ShardingSpec

D = 96  # the model's width here; heads, head widths and feed-forward widths are the cells'
RULES = {"head": head_dot_general, "matrix": matrix_dot_general}


def dims(x):
    return (((x.ndim - 1,), (0,)), ((), ()))


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


# the four cells that run Attention: query heads, key heads, head width
CELLS = {"laguna-sliding": (72, 8, 128), "laguna-full": (48, 8, 128), "mistral": (32, 8, 128),
         "lfm2": (32, 8, 64), "keye": (32, 4, 128)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("cell", CELLS)
def test_rule_against_autodiff_at_the_cells_head_forms(cell, dtype):
    q_heads, kv_heads, width = CELLS[cell]
    xq, wq, tq = operands(q_heads, width, dtype)
    xk, wk, tk = operands(kv_heads, width, dtype)

    @jax.jit
    def both(xq, wq, xk, wk):
        def objective(dot):
            return lambda *a: sum(
                (dot(x, w, dims(x)).astype(jnp.float32) * t.astype(jnp.float32)).sum()
                for x, w, t in ((a[0], a[1], tq), (a[2], a[3], tk))
            )

        outs = [dot(x, w, dims(x)) for dot in (head_dot_general, plain) for x, w in ((xq, wq), (xk, wk))]
        return outs, [jax.grad(objective(dot), argnums=(0, 1, 2, 3))(xq, wq, xk, wk) for dot in (head_dot_general, plain)]

    (oq, ok, rq, rk), (grads, ref) = both(xq, wq, xk, wk)
    for out, r, heads in ((oq, rq, q_heads), (ok, rk, kv_heads)):
        assert out.dtype == dtype and out.shape == (2, 24, heads, width)
        assert np.array_equal(np.asarray(out, np.float32), np.asarray(r, np.float32))  # the forward's bits
    for g, r, operand in zip(grads, ref, (xq, wq, xk, wk)):
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
    x, w, _ = operands(4, 16, jnp.float32)
    kernels = {"head": w, "matrix": w.reshape(D, 64)}
    rule, name, w = RULES[form], f"{form}_dot_general", kernels.pop(form)
    (other,) = kernels.values()  # the other form's kernel
    with pytest.raises(ValueError, match=name):
        rule(x, other, dims(x))
    with pytest.raises(ValueError, match=name):
        rule(x, w, (((1,), (0,)), ((), ())))  # another contraction
    with pytest.raises(ValueError, match=name):
        rule(x, w, dims(x), precision=jax.lax.Precision.HIGHEST)
    with pytest.raises(ValueError, match=name):
        rule(x, w, dims(x), preferred_element_type=jnp.float32)


def tiny(**kw):
    """A tiny decoder whose ``wq`` is wider than the model (4 heads of 32 from 64): the rule engages."""
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
     ("mistral", 4096, {"full_attention": 32}), ("lfm2", 2048, {"full_attention": 32})],
)
def test_which_projections_take_the_rule(monkeypatch, cell, d_model, kinds):
    """At the four cells' shapes: ``wq`` where ``heads x width`` exceeds the
    model's width (both kinds of Laguna layer, Keye), never ``wk`` or ``wv``,
    nothing in a square projection (Mistral, LFM2)."""
    kv_heads, width = {"laguna": (8, 128), "keye": (4, 128), "mistral": (8, 128), "lfm2": (8, 64)}[cell]
    cfg = DecoderConfig(
        d_model=d_model, n_heads=kinds["full_attention"], n_kv_heads=kv_heads, head_width=width, max_seq_len=16,
        **({"sliding_heads": kinds["sliding_attention"], "sliding_window": 8} if "sliding_attention" in kinds else {}),
    )
    x, ids = jax.ShapeDtypeStruct((1, 16, d_model), cfg.dtype), jax.ShapeDtypeStruct((1, 16), jnp.int32)
    for kind, heads in kinds.items():
        calls = rule_calls(monkeypatch)
        jax.eval_shape(Attention(cfg, kind).init, jax.random.key(0), x, ids)
        assert calls == ([(d_model, heads, width)] if cell in ("laguna", "keye") else []), (kind, calls)


def decoder_grads(cfg, variables, tokens):
    def objective(params):
        return jnp.square(Decoder(cfg).apply({"params": params}, tokens).astype(jnp.float32)).mean()

    return jax.jit(jax.value_and_grad(objective))(nn.meta.unbox(variables["params"]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_rule_under_checkpoint_inside_scan(monkeypatch, scan_layers, dtype):
    """A decoder, scanned or unrolled, each layer under ``jax.checkpoint`` with
    the policy ``nothing``: loss and every leaf's gradient as with plain
    ``dot_general``s; ``wq`` takes the head form, the feed-forward's three
    matrices the matrix form. Both forms' ``dx`` (``wq``'s, ``w_gate``'s and
    ``w_up``'s held by the barrier, ``w_down``'s not) reach every leaf below
    them: the norms' scales, the layers under the last, the embedding."""
    cfg = tiny(dtype=dtype, scan_layers=scan_layers, remat=True, remat_policy="nothing")
    tokens = jnp.asarray(np.arange(2 * 16).reshape(2, 16) % cfg.vocab_size, jnp.int32)
    variables = jax.jit(Decoder(cfg).init)(jax.random.key(3), tokens)
    calls, matrix_calls = rule_calls(monkeypatch), rule_calls(monkeypatch, "matrix")
    loss, grads = decoder_grads(cfg, variables, tokens)
    assert calls and set(calls) == {(cfg.d_model, cfg.n_heads, 32)}
    assert set(matrix_calls) == {(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)}
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
    ],
    ids=["heads-on-tensor", "gate-mlp-on-tensor", "down-mlp-on-tensor", "seq-sharded", "down-widens-on-tensor"],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rule_on_a_sharded_mesh(form, degrees, tokens, kernel, out, dtype):
    """Tokens over ``data`` x ``fsdp`` and the kernel over ``fsdp`` and
    ``tensor`` (the heads of ``[embed, heads, width]``; ``mlp`` in ``w_gate``'s
    and in ``w_down``'s orientation), and tokens over ``fsdp`` and ``seq``: the
    gradients come back in the operands' shardings and types and equal the
    unsharded rule's. ``dx`` is held by the barrier in the first four cases
    (96 inputs, 128 features) and left to autodiff in the last, the matrix
    form at ``w_down``'s own shape (128 inputs, 96 features). (That the
    partitioner rematerialises nothing in a whole step on a ``seq``-sharded
    mesh is ``test_packed_sequences.py``'s to see: its tiny decoder's
    feed-forward takes the matrix form.)"""
    mesh, rule = make_mesh(ShardingSpec(**degrees)), RULES.get(form, matrix_dot_general)  # "down" is a matrix too
    x, w, t = operands(8, 16, dtype, batch=4)
    if form != "head":
        w, t = w.reshape(D, 128), t.reshape(4, 24, 128)
    if form == "down":
        x, w, t = t, w.T, x
    xs, ws, ts = (NamedSharding(mesh, spec) for spec in (tokens, kernel, out))

    def grads(x, w, t):
        return jax.grad(lambda x, w: (rule(x, w, dims(x)) * t).sum(), argnums=(0, 1))(x, w)

    sharded = jax.jit(grads, in_shardings=(xs, ws, ts), out_shardings=(xs, ws))
    dx, dw = sharded(jax.device_put(x, xs), jax.device_put(w, ws), jax.device_put(t, ts))
    assert dx.sharding.is_equivalent_to(xs, 3) and dw.sharding.is_equivalent_to(ws, w.ndim)
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
    matrix ``[d, features]``; the input's where the product narrows (``wq``
    wider than the model, ``w_gate`` and ``w_up``: ``[tokens, features]`` by
    ``[features, d]`` with ``features > d``), not where it widens
    (``w_down``, whose ``dx`` is ``[tokens, d_ff]``) or keeps the width."""
    x, w, t = operands(8, 16, jnp.bfloat16)  # 8 x 16 = 128 features from D = 96
    narrow, same = operands(2, 16, jnp.bfloat16), operands(6, 16, jnp.bfloat16)  # 32 and 96 features from 96
    cases = [((x, w, t), [(D, 128), x.shape]), (narrow, [(D, 32)]), (same, [(D, 96)])]
    if form == "matrix":
        cases = [((x, w.reshape(D, -1), t.reshape(2, 24, -1)), held) for (x, w, t), held in cases]
        cases.append(((t.reshape(2, 24, 128), w.reshape(D, 128).T, x), [(128, D)]))  # w_down's orientation
    for (x, w, t), held in cases:
        jaxpr = jax.make_jaxpr(
            jax.grad(lambda x, w: (RULES[form](x, w, dims(x)).astype(jnp.float32) * t).sum(), argnums=(0, 1))
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
    the tree a checkpoint of the parent commit holds."""
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

    calls, matrix_calls = rule_calls(monkeypatch), rule_calls(monkeypatch, "matrix")
    ours = run()
    assert calls and matrix_calls  # both forms stand in the decode path's forward
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
