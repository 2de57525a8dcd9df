"""Long-context training with ring attention (sequence parallelism).

The sequence axis is sharded over the ``seq`` mesh ring; KV blocks rotate via
ppermute so no device ever holds the full [S, S] score matrix. Scale
``seq_len``/mesh to the pod; on CPU run with
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8.

    python examples/long_context_ring.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import optax

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.parallel.ringattention import make_ring_attention
from maggy_tpu.parallel.spec import ShardingSpec
from maggy_tpu.train import TrainContext
from maggy_tpu.train.data import synthetic_lm_batches

if __name__ == "__main__":
    n = len(jax.devices())
    if n < 2 or n % 2:
        raise SystemExit(
            f"This example needs an even device count >= 2 (got {n}); run with "
            "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    sp = max(2, n // 2)
    ctx = TrainContext.create(ShardingSpec(sp=sp, dp=n // sp))
    cfg = DecoderConfig.tiny(
        max_seq_len=32 * sp,
        attention_fn=make_ring_attention(ctx.mesh),
    )
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
    data = synthetic_lm_batches(cfg.vocab_size, batch_size=2 * (n // sp), seq_len=32 * sp)
    state = trainer.make_state(jax.random.key(0), next(data))
    print(f"mesh: sp={sp} dp={n // sp}, seq_len={32 * sp} sharded over the ring")
    for step in range(6):
        state, metrics = trainer.step(state, trainer.shard_batch(next(data)))
        if step % 3 == 2:
            print(f"step {step + 1}: loss {float(metrics['loss']):.4f}")
    print("done — the [S, S] score matrix never existed on any single device")
