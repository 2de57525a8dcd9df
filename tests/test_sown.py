"""The seam between a model and the trainer (``maggy_tpu/models/sown.py``):
what each of the benchmark's architectures hands back from a step, that every
row of the table names a registered gauge, that a new row reaches
``Trainer.step``'s output and ``fit``'s gauges with no line of ``train/``
changed, and that ``train/`` names no layer."""

import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import configs, run as bench_run  # noqa: E402
from maggy_tpu import models, telemetry  # noqa: E402
from maggy_tpu.models import sown  # noqa: E402
from maggy_tpu.parallel.mesh import make_mesh  # noqa: E402
from maggy_tpu.parallel.spec import ShardingSpec  # noqa: E402
from maggy_tpu.telemetry import metrics  # noqa: E402
from maggy_tpu.train import trainer as trainer_mod  # noqa: E402

KIND = "train_packed_ref"
S = 128
EXPERT = {"moe_slots", "moe_slots_dropped", "moe_load_max_over_mean", "moe_rows_visited_share", "moe_combine_rows_share"}
# what each architecture's step reports beside its losses, at the sizes of benchmark/checks/tiny.<name>.json
REPORTS = {
    "glm-4.7-flash": EXPERT,
    "lfm2-24b-a2b": EXPERT | {"conv_taps_masked_share"},
    "keye-vl-2.0-30b-a3b": EXPERT | {"index_loss", "sparse_selected_share", "sparse_rows_off_k"},
    "laguna-s-2.1": EXPERT | {"window_pairs_share"},
    "smallthinker-21ba3b-instruct": EXPERT | {"window_pairs_share", "moe_hidden_zero_share"},
    "evabyte": {"eva_remote_share", "eva_chunks_cut_share"},
    "ling-3.0-flash": EXPERT | {"conv_taps_masked_share", "kda_chunks_cut_share", "kda_log_decay_mean"},
}
FURTHER_HEADS = {"glm-4.7-flash", "evabyte"}  # the architectures that sow ``mtp_logits``


def tiny_model(name):
    with open(os.path.join(REPO, "benchmark", "checks", f"tiny.{name}.json")) as f:
        small = json.load(f)["config"]
    cfg = bench_run.merge(configs.load(f"benchmark/configs/{name}.json"), small)
    fields = configs.load_reference(cfg).program_fields(cfg, KIND)
    section = cfg[KIND]
    return getattr(models, section["model"])(
        getattr(models, section["config_class"])(**dict(fields, dtype=jnp.float32, remat=False, max_seq_len=S))
    )


@pytest.mark.parametrize("name", list(REPORTS))
def test_step_counters_of_every_architecture_and_their_registered_gauges(name):
    model = tiny_model(name)
    row = jax.ShapeDtypeStruct((2, S), jnp.int32)  # shapes only: the key set is decided while tracing

    def counters(tokens, positions, segment_ids):
        params = model.init(jax.random.key(0), tokens)["params"]
        _logits, mods = model.apply({"params": params}, tokens, positions, segment_ids, mutable=["intermediates"])
        return sown.step_counters(mods), sown.collect_aux_losses(mods), sown.mtp_logits(mods)

    out, aux, mtp = jax.eval_shape(counters, row, row, row)
    assert set(out) == REPORTS[name] and all(v.shape == () for v in out.values())
    assert aux.shape == () and (mtp is not None) == (name in FURTHER_HEADS)
    keys = [key for counter in sown.COUNTERS for key in counter.gauges]
    assert len(keys) == len(set(keys)) and REPORTS[name] <= set(keys)  # one row a key
    for counter in sown.COUNTERS:
        for gauge in counter.gauges.values():
            assert gauge in metrics.GAUGES and metrics.GAUGE_UNITS[gauge] in metrics.VALID_UNITS, gauge


class Probe(nn.Module):
    """A layer kind the trainer has never heard of: it sows how many tokens it saw."""

    @nn.compact
    def __call__(self, tokens):
        self.sow("intermediates", "probe_seen", jnp.stack([jnp.sum(tokens > 0), tokens.size]))
        return nn.Dense(16)(nn.Embed(16, 8)(tokens))


def test_a_new_row_reaches_the_step_output_and_the_gauges_with_no_line_of_train(monkeypatch):
    def seen_share(leaves):
        real, of = jnp.concatenate([a.reshape(-1, 2) for a in leaves]).astype(jnp.float32).sum(0)
        return {"probe_seen_share": real / of}

    row = sown.Counter(("probe_seen",), seen_share, {"probe_seen_share": "probe.seen_share"})
    monkeypatch.setattr(sown, "COUNTERS", sown.COUNTERS + (row,))
    seen = {}

    class Recorder(telemetry.Telemetry):
        def gauge(self, name, value):
            seen[name] = value
            super().gauge(name, value)

    tokens = np.arange(32, dtype=np.int32).reshape(2, 16) % 4  # a quarter of them 0
    host = {"tokens": tokens}
    with telemetry.current(Recorder(worker="t")):
        tr = trainer_mod.Trainer(Probe(), optax.adamw(1e-3), make_mesh(ShardingSpec(fsdp=1), jax.devices()[:1]))
        state = tr.make_state(jax.random.key(0), host)
        _state, stepped = tr.step(state, tr.shard_batch(host))
        assert float(stepped["probe_seen_share"]) == 0.75
        state = tr.make_state(jax.random.key(0), host)
        _state, out = tr.fit(state, iter([host] * 2), num_steps=2)
    assert out["probe_seen_share"] == 0.75 and seen["probe.seen_share"] == 0.75
    assert not set(seen) & {g for counter in sown.COUNTERS[:-1] for g in counter.gauges.values()}


def test_train_names_no_layer_and_the_adapter_does_not_import_the_trainer():
    """The sown names and the layer kinds live behind ``models/``: a new layer
    kind edits no line of ``train/``."""
    names = ["expert_load", "taps_masked", "sparse_counts", "window_pairs", "eva_counts", "eva_attention", '"conv"']
    source = {}
    for module in ("trainer.py", "pipeline_adapter.py"):
        with open(os.path.join(REPO, "maggy_tpu", "train", module)) as f:
            source[module] = f.read()
        assert [n for n in names if n in source[module]] == [], module
        assert not re.search(r"def \w+_counters|def _sown|def collect_aux_losses", source[module]), module
    assert not re.search(r"train\.trainer|train import trainer|from \.trainer", source["pipeline_adapter.py"])
    for name in os.listdir(os.path.join(REPO, "maggy_tpu", "models")):
        if name.endswith(".py"):
            with open(os.path.join(REPO, "maggy_tpu", "models", name)) as f:
                assert "maggy_tpu.train" not in f.read(), name
