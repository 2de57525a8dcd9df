"""Packed serving: one-pass prefill of a multi-document prompt buffer, then
KV-cached continuation of each row's last segment.

Packing is how long-context training keeps the MXU fed; this example shows
the SAME batches serve efficiently too (the reference has no decode path at
all): `prefill()` runs the fully-packed buffer through the `decode=True`
model in a single apply — segment ids are cached alongside K/V, and every
cache read is masked to the query's segment, so the packed contexts stay
isolated exactly as during training — and `generate_cached_packed()`
continues each row's final segment.

    JAX_PLATFORMS=cpu python examples/packed_serving.py
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.models.generate import generate_cached, generate_cached_packed

if __name__ == "__main__":
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = Decoder(cfg)
    rng = np.random.default_rng(0)

    # two rows, each packing a 6-token context doc + a 10-token prompt
    B, MAX_NEW = 2, 8
    rows, poss, segs, last_prompts = [], [], [], []
    for _ in range(B):
        ctx_doc = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        prompt = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
        rows.append(np.concatenate([ctx_doc, prompt]))
        poss.append(np.concatenate([np.arange(6), np.arange(10)]))
        segs.append(np.concatenate([np.zeros(6), np.ones(10)]))
        last_prompts.append(prompt)
    packed = jnp.asarray(np.stack(rows).astype(np.int32))
    positions = jnp.asarray(np.stack(poss).astype(np.int32))
    segment_ids = jnp.asarray(np.stack(segs).astype(np.int32))

    variables = model.init(jax.random.key(7), packed)
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))

    logits, new_tokens = generate_cached_packed(
        decode_model, variables["params"], packed, positions, segment_ids,
        max_new=MAX_NEW,
    )
    print(f"prefill logits: {logits.shape}  new tokens: {new_tokens.shape}")

    # proof of segment isolation: decoding each row's prompt ALONE (no packed
    # context doc in the cache at all) yields the same greedy continuation
    for r, prompt in enumerate(last_prompts):
        buf = np.zeros((1, 10 + MAX_NEW), np.int32)
        buf[0, :10] = prompt
        ref = generate_cached(
            decode_model, variables["params"], jnp.asarray(buf),
            jnp.asarray([10], jnp.int32),
        )
        match = bool(
            (np.asarray(new_tokens)[r] == np.asarray(ref)[0, 10:]).all()
        )
        print(f"row {r}: packed continuation == per-sequence decode: {match}")
        assert match
    print("packed serving OK")
