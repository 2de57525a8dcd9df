"""Pipeline-parallel training of the LLaMA-style decoder (1F1B schedule).

``ShardingSpec(pp=N)`` is honored directly by the Trainer: layer stages live
on different devices along the ``stage`` mesh axis, activations flow
stage→stage via ppermute, and the one-forward-one-backward schedule keeps
every stage busy after warmup with O(stages) activation memory. The reference
explicitly rejects pipeline engines (core/patching/modules.py:106-109); here
it is one config knob, composable with data AND tensor parallelism — pass
``--tp`` to also shard each stage's attention heads / MLP hidden / vocab
over the ``tensor`` axis (the layout a stage too large for one device
needs; stage attention stays on the flash kernel via a nested
tensor-manual shard_map).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_pipeline.py [--tp]
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import optax

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.train import TrainContext
from maggy_tpu.train.data import synthetic_lm_batches

if __name__ == "__main__":
    n = len(jax.devices())
    if n < 4 or n % 2:
        raise SystemExit(
            f"This example needs an even device count >= 4 (got {n}); run with "
            "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    use_tp = "--tp" in sys.argv
    if use_tp and n % 4:
        raise SystemExit(f"--tp needs a device count divisible by 4 (got {n})")
    pp = 2
    tp = 2 if use_tp else 1
    dp = n // (pp * tp)
    ctx = TrainContext.create(ShardingSpec(pp=pp, tp=tp, dp=dp))

    # llama-shaped in miniature: 4 layers -> 2 per stage
    cfg = DecoderConfig.tiny(n_layers=4, max_seq_len=64)
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
    n_micro = 2 * pp  # amortizes the 1F1B bubble
    trainer.n_microbatches = n_micro
    batch_size = n_micro * dp  # each microbatch still shards rows over dp

    data = synthetic_lm_batches(cfg.vocab_size, batch_size, 64, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))

    print(f"pipeline: {pp} stages x {tp}-way tensor x {dp}-way data parallel, "
          f"{n_micro} microbatches/step")
    for step in range(20):
        state, metrics = trainer.step(state, trainer.shard_batch(next(data)))
        if (step + 1) % 5 == 0:
            print(f"step {step + 1}: loss={float(metrics['loss']):.4f} "
                  f"grad_norm={float(metrics['grad_norm']):.3f}")
    print("pipeline-parallel training OK")
