"""Decode throughput microbench.

Round-2 geometry for comparability: 267M decoder, B=8,
64-token prompt -> 512-token buffer, greedy. Measures generate (prefix
recompute) vs generate_cached (KV cache, now length-adaptive chunked reads)
and prints one JSON line. Target: >= 2x the recorded 3123 tok/s cached rate.

    python tools/bench_decode.py [--quick]
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()

    from bench import on_cpu  # repo root on sys.path (line 18)

    cpu = on_cpu()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.models.generate import generate, generate_cached

    if cpu or args.quick:
        cfg = DecoderConfig.tiny(max_seq_len=256)
        B, PROMPT, BUF = 2, 16, 128
    else:
        # the round-2 bench geometry
        cfg = DecoderConfig(
            vocab_size=32_000, d_model=1024, n_layers=12, n_heads=8,
            n_kv_heads=8, d_ff=4096, max_seq_len=1024,
        )
        B, PROMPT, BUF = 8, 64, 512

    model = Decoder(cfg)
    rng = np.random.default_rng(0)
    prompt = np.zeros((B, BUF), np.int32)
    prompt[:, :PROMPT] = rng.integers(1, cfg.vocab_size, (B, PROMPT))
    prompt = jnp.asarray(prompt)
    prompt_len = jnp.full((B,), PROMPT, jnp.int32)
    variables = model.init(jax.random.key(0), prompt[:, :8])
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))

    def timed(fn, *a, **k):
        jax.block_until_ready(fn(*a, **k))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a, **k))
        dt = time.perf_counter() - t0
        new_tokens = B * (BUF - PROMPT)
        return new_tokens / dt, dt / (BUF - PROMPT) * 1e3

    cached_tps, cached_ms = timed(
        generate_cached, decode_model, variables["params"], prompt, prompt_len
    )
    recompute_tps, recompute_ms = timed(
        generate, model, variables, prompt, prompt_len
    )

    # one-pass prefill (r5): the whole prompt through the decode model in a
    # single apply vs PROMPT single-token applies (what generate_cached does)
    from maggy_tpu.models.generate import prefill

    pre_tokens = prompt[:, :PROMPT]
    pre_pos = jnp.broadcast_to(jnp.arange(PROMPT, dtype=jnp.int32), (B, PROMPT))
    # hoisted: a fresh jit-wrapped lambda per call would recompile every
    # time and the "timed" run would measure XLA compilation
    prefill_jit = jax.jit(
        lambda p: prefill(decode_model, variables["params"], p, pre_pos)[0]
    )

    def run_prefill():
        return prefill_jit(pre_tokens)

    jax.block_until_ready(run_prefill())  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(run_prefill())
    prefill_tps = B * PROMPT / (time.perf_counter() - t0)

    print(json.dumps({
        "metric": "decode_tok_per_sec_cached",
        "value": round(cached_tps, 1),
        "unit": "tok/s",
        # r2 record only comparable at the full geometry on silicon
        "vs_baseline": (
            round(cached_tps / 3123.0, 3)
            if not (cpu or args.quick)
            else None
        ),
        "extra": {
            "on_cpu": cpu,
            "cached_ms_per_token_batch": round(cached_ms, 3),
            "recompute_tok_per_sec": round(recompute_tps, 1),
            "prefill_tok_per_sec": round(prefill_tps, 1),
            "decode_chunk": cfg.decode_chunk,
            "geometry": f"B={B} prompt={PROMPT} buf={BUF} S={cfg.max_seq_len}",
            "device": str(jax.devices()[0]),
        },
    }))


if __name__ == "__main__":
    main()
