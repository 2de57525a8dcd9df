"""Long-context attention table on one chip (SURVEY §5.7 headline area).

Records, per sequence length {2k, 4k, 8k, 16k}:

* fwd+bwd step time of the attention op — Pallas flash (auto tiles) vs the
  XLA blockwise schedule (dense fused is included at S<=4k where it fits);
* one FULL-model train step at S=8192 (bs=2, remat) — the "trains where
  dense cannot" claim with a measured tok/s number.

Prints one JSON line.

    python tools/bench_longcontext.py [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()

    from bench import on_cpu

    cpu = on_cpu()

    import jax
    import jax.numpy as jnp

    from maggy_tpu.models.transformer import default_attention
    from maggy_tpu.ops.attention import blockwise_attention
    from maggy_tpu.ops.flash import flash_attention

    quick = cpu or args.quick
    B, H, D = (1, 2, 128) if quick else (2, 8, 128)
    seqs = [256, 512] if quick else [2048, 4096, 8192, 16384]

    def timed_grad(fn, S):
        q = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.bfloat16)

        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(g(q, q, q))  # compile
        steps = 3 if quick else 10
        t0 = time.perf_counter()
        for _ in range(steps):
            out = g(q, q, q)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e3

    table = []
    for S in seqs:
        row = {"seq": S}
        row["flash_ms"] = round(
            timed_grad(lambda q, k, v: flash_attention(q, k, v, causal=True), S), 2
        )
        row["blockwise_ms"] = round(
            timed_grad(
                lambda q, k, v: blockwise_attention(q, k, v, causal=True), S
            ),
            2,
        )
        if S <= 4096:  # the [S,S] score matrix fits
            row["dense_ms"] = round(
                timed_grad(
                    lambda q, k, v: default_attention(q, k, v, causal=True), S
                ),
                2,
            )
        table.append(row)

    # full-model long-context train step: the single-chip "trains where the
    # dense score matrix cannot exist" datapoint
    model_row = None
    try:
        import optax

        from maggy_tpu.models import Decoder, DecoderConfig
        from maggy_tpu.train import TrainContext
        from maggy_tpu.train.data import synthetic_lm_batches

        if quick:
            cfg = DecoderConfig.tiny(max_seq_len=512)
            bs, S = 1, 512
        else:
            cfg = DecoderConfig(
                vocab_size=32_000, d_model=1024, n_layers=12, n_heads=8,
                n_kv_heads=8, d_ff=4096, max_seq_len=8192, remat=True,
            )
            bs, S = 2, 8192
        # one-device mesh: bs is tiny by design and must not need to divide
        # an 8-device CPU test mesh
        ctx = TrainContext.create("dp", devices=jax.devices()[:1])
        trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
        data = synthetic_lm_batches(cfg.vocab_size, bs, S, seed=0)
        state = trainer.make_state(jax.random.key(0), next(data))
        batch = trainer.shard_batch(next(data))
        state, m = trainer.step(state, batch)
        float(m["loss"])
        steps = 2 if quick else 5
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer.step(state, batch)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        model_row = {
            "seq": S, "batch": bs, "step_ms": round(dt * 1e3, 1),
            "tok_per_sec": round(bs * S / dt, 1),
        }
    except Exception as e:  # noqa: BLE001 - the op table alone is still data
        model_row = {"error": f"{type(e).__name__}: {e}"}

    print(json.dumps({
        "metric": "longcontext_attention_table",
        "value": table[-1]["flash_ms"],
        "unit": "ms fwd+bwd at max S",
        "vs_baseline": None,
        "extra": {
            "on_cpu": cpu,
            "geometry": f"B={B} H={H} D={D}",
            "table": table,
            "model_step_s8k": model_row,
            "device": str(jax.devices()[0]),
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
