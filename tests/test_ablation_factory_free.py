"""Factory-free model ablation: DecoderConfig.without
gating, the generic param-subtree masking fallback, and the driver's
auto-derivation — reference parity with Keras-JSON layer surgery
(loco.py:82-136) minus the user plumbing."""

import importlib
import tempfile

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ablation.masking import ParamMaskedModel, auto_ablate
from maggy_tpu.models import Decoder, DecoderConfig


def _tokens(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)


# --------------------------------------------------------------- cfg.without

def test_without_validates_and_merges():
    cfg = DecoderConfig.tiny()
    c2 = cfg.without("mlp").without(["layers.0", "layers.1.attn"])
    assert c2.ablated == frozenset({"mlp", "layers.0", "layers.1.attn"})
    with pytest.raises(ValueError, match="Unknown ablated component"):
        cfg.without("pooler")
    with pytest.raises(ValueError, match="out of range"):
        cfg.without("layers.7")
    with pytest.raises(ValueError, match="Unknown ablated component"):
        cfg.without("layers.0.norm")


def test_without_gates_match_zeroed_params():
    """Gating 'mlp' out must equal running the full model with every MLP
    param zeroed (zero-param SwiGLU outputs exactly zero), and differ from
    the baseline."""
    cfg = DecoderConfig.tiny()
    tokens = _tokens(cfg)
    model = Decoder(cfg)
    params = model.init(jax.random.key(0), tokens)["params"]

    base = model.apply({"params": params}, tokens)
    ablated = Decoder(cfg.without("mlp")).apply({"params": params}, tokens)
    assert not np.allclose(np.asarray(base), np.asarray(ablated))

    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.zeros_like(leaf)
        if "mlp" in jax.tree_util.keystr(p) and "norm" not in jax.tree_util.keystr(p)
        else leaf,
        params,
    )
    ref = model.apply({"params": zeroed}, tokens)
    np.testing.assert_allclose(np.asarray(ablated), np.asarray(ref), atol=1e-5)


def test_without_single_layer_gate_unscanned_parity():
    """Per-layer gates must agree between the scanned and unscanned stacks."""
    # fp32: scan vs python-loop accumulate differently at bf16
    cfg = DecoderConfig.tiny(dtype=jnp.float32).without("layers.1")
    cfg_py = DecoderConfig.tiny(dtype=jnp.float32, scan_layers=False).without("layers.1")
    tokens = _tokens(cfg)
    scanned = Decoder(cfg)
    p = scanned.init(jax.random.key(0), tokens)["params"]
    out_scan = scanned.apply({"params": p}, tokens)

    # re-layout layer-stacked params into the unscanned tree
    unscanned = Decoder(cfg_py)
    p_py = unscanned.init(jax.random.key(0), tokens)["params"]
    from maggy_tpu.parallel.sharding import unbox

    pu, ps = unbox(p_py), unbox(p)
    rebuilt = dict(pu)
    for i in range(cfg.n_layers):
        rebuilt[f"layers_{i}"] = {
            "layer": jax.tree.map(lambda a, idx=i: a[idx], ps["layers"]["layer"])
        }
    rebuilt["embedding"] = ps["embedding"]
    rebuilt["final_norm"] = ps["final_norm"]
    rebuilt["lm_head"] = ps["lm_head"]
    out_py = unscanned.apply({"params": rebuilt}, tokens)
    np.testing.assert_allclose(
        np.asarray(out_scan), np.asarray(out_py), atol=1e-4
    )


def test_ablated_gradients_are_zero():
    cfg = DecoderConfig.tiny().without("layers.0.attn")
    tokens = _tokens(cfg)
    model = Decoder(cfg)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        return model.apply({"params": p}, tokens).sum()

    grads = jax.grad(loss)(params)
    from maggy_tpu.parallel.sharding import unbox

    g = unbox(grads)["layers"]["layer"]["attn"]
    for leaf in jax.tree.leaves(g):
        assert float(jnp.abs(leaf[0]).max()) == 0.0  # layer 0: gated
        assert float(jnp.abs(leaf[1]).max()) > 0.0   # layer 1: live


# ----------------------------------------------------------- generic masking

class _PlainMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        h = nn.Dense(8, name="hidden")(x)
        x = x + nn.Dense(x.shape[-1], name="proj")(nn.relu(h))
        return nn.Dense(2, name="head")(x)


def test_param_masked_model_zeroes_subtree_and_grads():
    base = _PlainMLP()
    x = jnp.ones((3, 4))
    masked = ParamMaskedModel(base, {"proj"})
    variables = masked.init(jax.random.key(0), x)

    ref_params = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.zeros_like(leaf)
        if "proj" in jax.tree_util.keystr(p)
        else leaf,
        base.init(jax.random.key(0), x)["params"],
    )
    np.testing.assert_allclose(
        np.asarray(masked.apply(variables, x)),
        np.asarray(base.apply({"params": ref_params}, x)),
        atol=1e-6,
    )

    def loss(v):
        return masked.apply(v, x).sum()

    g = jax.grad(loss)(variables)["params"]
    for leaf in jax.tree.leaves(g["proj"]):
        assert float(jnp.abs(leaf).max()) == 0.0
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(g["head"]))

    with pytest.raises(ValueError, match="no parameter subtree"):
        ParamMaskedModel(base, {"nonexistent"}).init(jax.random.key(0), x)


def test_moe_without_gates_forward():
    """MoEConfig inherits .without(); the MoEDecoder must actually honor the
    gates (an inherited-but-ignored ablated set would silently no-op)."""
    from maggy_tpu.models import MoEConfig, MoEDecoder

    cfg = MoEConfig.tiny_moe(dtype=jnp.float32)
    tokens = _tokens(cfg)
    model = MoEDecoder(cfg)
    params = model.init(jax.random.key(0), tokens)["params"]
    base = model.apply({"params": params}, tokens)
    ablated = MoEDecoder(cfg.without("layers.1")).apply({"params": params}, tokens)
    assert not np.allclose(np.asarray(base), np.asarray(ablated), atol=1e-5)
    # gating all layers' moe+attn leaves only embed -> norm -> head
    all_off = MoEDecoder(cfg.without(["attn", "mlp"])).apply(
        {"params": params}, tokens
    )
    assert not np.allclose(np.asarray(ablated), np.asarray(all_off), atol=1e-5)
    # the gate also silences the router aux loss of the ablated block
    from maggy_tpu.models.sown import collect_aux_losses

    _, mods_abl = MoEDecoder(cfg.without("mlp")).apply(
        {"params": params}, tokens, mutable=["intermediates"]
    )
    _, mods_full = MoEDecoder(cfg).apply(
        {"params": params}, tokens, mutable=["intermediates"]
    )
    assert float(collect_aux_losses(mods_abl)) == 0.0
    assert float(collect_aux_losses(mods_full)) > 0.0


def test_auto_ablate_tiers():
    # tier 1: config with without()
    m = auto_ablate(Decoder(DecoderConfig.tiny()), frozenset({"mlp"}))
    assert isinstance(m, Decoder) and m.cfg.ablated == frozenset({"mlp"})
    # tier 2: config with an ablated field
    from maggy_tpu.models import Bert, BertConfig

    b = auto_ablate(Bert(BertConfig.tiny()), frozenset({"pooler"}))
    assert isinstance(b, Bert) and b.cfg.ablated == frozenset({"pooler"})
    # tier 3: plain module -> masking wrapper
    p = auto_ablate(_PlainMLP(), frozenset({"hidden"}))
    assert isinstance(p, ParamMaskedModel)


def test_default_dataset_generator_streaming_datasets(tmp_path):
    """Feature ablation on streaming datasets rebuilds a column-filtered
    view — no file rewrites, schema-style like the reference's feature-store
    drop (loco.py:41-80)."""
    from maggy_tpu.ablation.ablationstudy import default_dataset_generator
    from maggy_tpu.train.sharded_dataset import (
        ParquetShardedDataset,
        ShardedDataset,
        write_parquet,
        write_sharded,
    )

    data = {
        "tokens": np.arange(32, dtype=np.int32).reshape(8, 4),
        "extra": np.arange(8, dtype=np.int64),
    }
    write_sharded(str(tmp_path / "npy"), data, num_shards=2)
    ds = ShardedDataset(str(tmp_path / "npy"))
    dropped = default_dataset_generator(ds, "extra")
    assert dropped.fields == ["tokens"]
    assert next(dropped.loader(4, loop=False, shuffle=False)).keys() == {"tokens"}

    pytest.importorskip("pyarrow")
    write_parquet(str(tmp_path / "pq"), data, rows_per_group=4)
    pq_ds = ParquetShardedDataset(str(tmp_path / "pq"))
    pq_dropped = default_dataset_generator(pq_ds, "extra")
    assert pq_dropped.fields == ["tokens"]

    with pytest.raises(KeyError):
        default_dataset_generator(ds, "nope")
    with pytest.raises(ValueError):
        default_dataset_generator(dropped, "tokens")  # only field left


# ------------------------------------------------------------- driver e2e

def test_loco_lagom_zero_factories():
    """Full lagom ablation run with NO set_factory: variants derived from
    AblationConfig(model=...) automatically."""
    experiment = importlib.import_module("maggy_tpu.experiment")
    from maggy_tpu.ablation import AblationStudy
    from maggy_tpu.config import AblationConfig
    from maggy_tpu.core import env as env_mod
    from maggy_tpu.core.env.base import BaseEnv

    env_mod.set_instance(BaseEnv(tempfile.mkdtemp()))
    try:
        cfg = DecoderConfig.tiny()
        tokens = _tokens(cfg, b=4, s=8)
        seen = []

        def train(model, reporter):
            params = model.init(jax.random.key(0), tokens)["params"]
            out = model.apply({"params": params}, tokens)
            seen.append(getattr(model.cfg, "ablated", frozenset()))
            metric = float(jnp.abs(out).mean())
            reporter.broadcast(metric, step=0)
            return metric

        study = AblationStudy()
        study.model.layers.include("mlp", "layers.0")
        result = experiment.lagom(
            train,
            AblationConfig(
                ablation_study=study,
                model=Decoder(cfg),
                direction="max",
                hb_interval=0.05,
            ),
        )
        assert result["num_trials"] == 3  # baseline + 2 components
        assert frozenset() in seen
        assert frozenset({"mlp"}) in seen
        assert frozenset({"layers.0"}) in seen
    finally:
        env_mod.set_instance(None)
