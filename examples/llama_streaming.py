"""Train a decoder straight from a Parquet directory it never fully loads.

The streaming input pipeline (reference petastorm parity, §2.9): token
sequences live in Parquet files as fixed-size-list columns, **row groups**
are the shard unit split round-robin across processes (exactly petastorm's
RANK/WORLD_SIZE semantics, reference dataloader.py:100-144), batches are
assembled by the C++ row-gather on a background thread with a two-level
shuffle, and fed through ``shard_batch(local=True)``. The pre-split ``.npy``
layout (``ShardedDataset``/``write_sharded``) remains for corpora already
converted. Also prints the loader's standalone batch rate vs the training
step time — input is overlapped, so it only needs to be >= the step rate.

    python examples/llama_streaming.py
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import numpy as np
import optax

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.train import ParquetShardedDataset, TrainContext, write_parquet

CFG = DecoderConfig.tiny(max_seq_len=256)
BATCH, SEQ, STEPS = 8, 128, 30


def main():
    work = tempfile.mkdtemp(prefix="maggy_stream_")
    rng = np.random.default_rng(0)
    # a mixture of repeated-token rows: learnable next-token structure
    base = rng.integers(0, CFG.vocab_size, (2048, 1), dtype=np.int32)
    tokens = np.tile(base, (1, SEQ))
    write_parquet(
        os.path.join(work, "lm"), {"tokens": tokens},
        rows_per_group=64, num_files=4,  # 32 row-group shards
    )

    ds = ParquetShardedDataset(os.path.join(work, "lm"))
    ctx = TrainContext.create("dp" if len(jax.devices()) == 1 else "fsdp")
    trainer = ctx.trainer(Decoder(CFG), optax.adamw(1e-2))
    loader = ds.loader(batch_size=BATCH, ctx=ctx)

    state = trainer.make_state(jax.random.key(0), next(loader))
    state, m = trainer.step(state, trainer.shard_batch(next(loader), local=True))
    float(m["loss"])  # compile barrier

    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, m = trainer.step(state, trainer.shard_batch(next(loader), local=True))
    final = float(m["loss"])
    step_ms = (time.perf_counter() - t0) / STEPS * 1e3

    # standalone loader rate (no device work): how fast input CAN flow
    t0 = time.perf_counter()
    for _ in range(STEPS):
        next(loader)
    load_ms = (time.perf_counter() - t0) / STEPS * 1e3
    loader.close()

    print(
        f"final_loss={final:.3f} step={step_ms:.1f}ms "
        f"loader_batch={load_ms:.2f}ms overlap_ok={load_ms <= step_ms}"
    )


if __name__ == "__main__":
    main()
