"""Flash-attention tile sweep (fwd AND bwd grids) on real hardware.

Round-2 found 512-row forward q tiles ~2.7x faster than the conventional
128; the backward kernels were left on the forward's tiles. This sweeps
bwd_block_q/bwd_block_k independently on the bench geometry and prints a
ranked table — run it on the chip, then bake the winner into _auto_blocks'
backward variant.

The tile grid is the autopilot knob registry's ``FLASH_TILE_CHOICES``
(maggy_tpu/autopilot/knobs.py) — the manual sweep and the Planner's
compute-bound recommendations draw candidates from the same table, so a
tile this tool can measure is always one the autopilot may legally plan.

    python tools/tune_flash.py [--seq 1024] [--steps 10]
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()

    from bench import on_cpu

    cpu = on_cpu()

    import jax
    import jax.numpy as jnp

    from maggy_tpu.ops.flash import flash_attention

    # bench-geometry attention shape: d_model 1024, 8 heads -> head_dim 128
    B, S, H, D = (2, 256, 2, 128) if (cpu or args.quick) else (16, args.seq, 8, 128)
    q = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(2), (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(3), (B, S, H, D), jnp.bfloat16)

    from maggy_tpu.autopilot.knobs import FLASH_TILE_CHOICES

    cands = [c for c in FLASH_TILE_CHOICES if c <= S] or [S]
    if cpu or args.quick:
        cands = cands[:2]

    def time_one(bq, bk, bbq, bbk):
        def loss(q, k, v):
            o = flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                bwd_block_q=bbq, bwd_block_k=bbk,
            )
            return (o.astype(jnp.float32) ** 2).sum()

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(g(q, k, v))  # compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = g(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.steps * 1e3

    rows = []
    fwd_best = (512 if 512 in cands else cands[-1], 512 if 512 in cands else cands[-1])
    for bbq, bbk in itertools.product(cands, cands):
        try:
            ms = time_one(fwd_best[0], fwd_best[1], bbq, bbk)
            rows.append({"bwd_block_q": bbq, "bwd_block_k": bbk, "ms": round(ms, 3)})
            print(f"bwd ({bbq:4d},{bbk:4d}): {ms:8.3f} ms")
        except Exception as e:  # noqa: BLE001 - a tile that fails to lower is data
            print(f"bwd ({bbq:4d},{bbk:4d}): FAILED {type(e).__name__}")
    rows.sort(key=lambda r: r["ms"])
    print(json.dumps({
        "geometry": f"B={B} S={S} H={H} D={D}",
        "fwd_tiles": fwd_best,
        "ranking": rows[:5],
        "device": str(jax.devices()[0]),
    }))


if __name__ == "__main__":
    main()
