"""BASELINE config 5 (scaled down): MoE HPO with expert-parallel trial placement.

ASHA searches router/optimizer hyperparameters of a Mixtral-style MoE decoder;
each trial trains expert-parallel over its leased devices. Swap tiny_moe for
MoEConfig.mixtral_8x7b() on a pod.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mixtral_moe_hpo.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import dataclasses

import jax
import optax

from maggy_tpu import Searchspace, experiment
from maggy_tpu.config import HyperparameterOptConfig
from maggy_tpu.models import MoEConfig, MoEDecoder
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.train import TrainContext
from maggy_tpu.train.data import synthetic_lm_batches

BASE = MoEConfig.tiny_moe()


def train(hparams, budget, reporter, devices):
    cfg = dataclasses.replace(
        BASE,
        top_k=hparams["top_k"],
        capacity_factor=hparams["capacity_factor"],
        router_aux_weight=hparams["aux_weight"],
    )
    # expert-parallel mesh over this trial's device lease
    n = max(1, len(devices or []))
    ep = cfg.n_experts if n % cfg.n_experts == 0 else 1
    ctx = TrainContext.create(ShardingSpec(ep=ep, dp=n // ep), devices=devices or None)
    trainer = ctx.trainer(MoEDecoder(cfg), optax.adamw(hparams["lr"]))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    loss = None
    for step in range(int(budget) * 10):
        state, metrics = trainer.step(state, trainer.shard_batch(next(data)))
        if step % 5 == 4:
            loss = float(metrics["loss"])
            reporter.broadcast(-loss, step=step)
    return {"metric": -loss, "loss": loss}


if __name__ == "__main__":
    sp = Searchspace(
        lr=("DOUBLE", [1e-4, 1e-2]),
        top_k=("DISCRETE", [1, 2]),
        capacity_factor=("DOUBLE", [1.0, 2.0]),
        aux_weight=("DOUBLE", [0.0, 0.05]),
    )
    config = HyperparameterOptConfig(
        num_trials=6,
        optimizer="asha",
        searchspace=sp,
        direction="max",
        es_policy="none",
        devices_per_trial=4,
        hb_interval=0.2,
        seed=0,
    )
    result = experiment.lagom(train, config)
    print("best:", result["best"]["params"], "loss:", -result["best"]["metric"])
