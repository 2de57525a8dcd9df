"""BASELINE config 3 (scaled down): LLaMA-style decoder distributed training.

FSDP sharding over every visible device; swap DecoderConfig.tiny() for
DecoderConfig.llama3_8b() on a v5p pod. On CPU, run with
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 to
simulate 8 devices.

    python examples/llama_distributed.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import optax

from maggy_tpu import experiment
from maggy_tpu.config import DistributedConfig
from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.train.data import synthetic_lm_batches

CFG = DecoderConfig.tiny()


def train(model, dataset, hparams, reporter, ctx):
    trainer = ctx.trainer(model, optax.adamw(hparams["lr"]))
    state = trainer.make_state(jax.random.key(0), next(dataset))
    state, metrics = trainer.fit(
        state,
        dataset,
        num_steps=hparams["steps"],
        reporter=reporter,
        report_every=10,
        metric_sign=-1.0,  # metric is -loss (direction="max")
    )
    return {"metric": -metrics["loss"], "loss": metrics["loss"]}


if __name__ == "__main__":
    config = DistributedConfig(
        module=Decoder(CFG),
        dataset=synthetic_lm_batches(CFG.vocab_size, batch_size=8, seq_len=64),
        hparams={"lr": 3e-3, "steps": 60},
        sharding="fsdp",
        hb_interval=0.2,
    )
    result = experiment.lagom(train, config)
    print("final:", result)
