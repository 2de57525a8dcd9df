"""Flash-attention tile sweep, forward and backward grids, on the chip.

    python tools/tune_flash.py --packed        # the packed train cell's shape
    python tools/tune_flash.py [--seq 4096]    # the same shape, no segment ids

    python tools/tune_flash.py --packed --mix packed8k --heads 20 --kv-heads 20 --head-dim 256

    python tools/tune_flash.py --packed --mix packed8k-r4 --heads 32 --kv-heads 8 --head-dim 64

    python tools/tune_flash.py --packed --mix packed8k-r1 --heads 72 --kv-heads 8 --window 512

Both modes time one attention call in bfloat16, by default at B 2 and 32 query
and 8 key-value heads of 128 (Mistral-7B's; ``--heads``, ``--kv-heads``,
``--head-dim`` give another model's). ``--packed`` takes its segment ids from
a benchmark mix (``--mix``, default ``packed4k``; ``benchmark.traffic``, read only):
every row of the pool, the mix's rows a step a call, so a tile choice is timed
on the packings the cell trains on and printed beside the share of the grid's
tiles it visits. The forward is timed alone, the backward (one fused kernel
where ``ops.flash.backward_form`` says so, else two) as forward plus backward
less the forward at the same forward tiles. ``_auto_blocks`` in
``maggy_tpu/ops/flash.py`` returns the winners; PERF.md has the table this
printed when they were chosen.

The candidates are the autopilot knob registry's ``FLASH_TILE_CHOICES``
(maggy_tpu/autopilot/knobs.py) from 256 up, so a tile this tool can measure
is one the autopilot may plan. Off the chip it runs two toy choices under the
Pallas interpreter, to show the command works; its times mean nothing there.
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def packed_segment_ids(mix_name):
    """[calls, rows a call, seq_len] int32: the mix's pool of batches (ids
    1.. in each row, 0 for the padded tail), every row of the mix."""
    import numpy as np

    from benchmark import traffic

    mix = traffic.load_mix(mix_name)
    pool, _ = traffic.packed_pool(mix, seed=0, vocab=2)
    return np.stack([batch["segment_ids"] for batch in pool])


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--steps", type=int, default=5, help="timed passes over the calls")
    parser.add_argument("--packed", action="store_true")
    parser.add_argument("--mix", default="packed4k", help="--packed: the benchmark mix whose rows are timed (its seq_len is the length)")
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--window", type=int, default=0, help="a sliding layer's window (0: none)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from maggy_tpu.autopilot.knobs import FLASH_TILE_CHOICES
    from maggy_tpu.ops.flash import _auto_blocks, backward_form, flash_attention, tiles_visited_share

    toy = jax.default_backend() != "tpu"
    if args.packed and not toy:
        segs = packed_segment_ids(args.mix)
        args.seq = segs.shape[-1]
    B, S, H, KH, D = (2, 512, 2, 1, 128) if toy else (
        segs.shape[1] if args.packed else 2, args.seq, args.heads, args.kv_heads, args.head_dim
    )
    dt = jnp.bfloat16
    q, do = (jax.random.normal(jax.random.key(i), (B, S, H, D), dt) for i in (1, 4))
    k, v = (jax.random.normal(jax.random.key(i), (B, S, KH, D), dt) for i in (2, 3))
    if args.packed and toy:
        segs = np.repeat(np.arange(1, 5, dtype=np.int32), S // 4)[None, None].repeat(B, 1)
    elif not args.packed:
        segs = [None]
    calls = [None if s is None else jnp.asarray(s) for s in segs]
    cands = [c for c in FLASH_TILE_CHOICES if 256 <= c <= S] or [S]
    pairs = list(itertools.product(cands, cands))[: 2 if toy else None]

    def attend(tiles, q, k, v, seg):
        bq, bk, bbq, bbk = tiles
        return flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk,
            bwd_block_q=bbq, bwd_block_k=bbk, segment_ids=seg, window=args.window,
        )

    def time_ms(fn, *arrays):
        """Milliseconds a call, over every call of the pool ``--steps`` times."""
        for seg in calls:  # compile, warm; one call's outputs held at a time
            out = fn(seg, *arrays)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            for seg in calls:
                out = fn(seg, *arrays)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.steps * len(calls)) * 1e3

    def forward(tiles):
        return time_ms(jax.jit(lambda seg, q, k, v: attend(tiles, q, k, v, seg)), q, k, v)

    def forward_backward(tiles):
        def f(seg, q, k, v, do):
            _, pull = jax.vjp(lambda q, k, v: attend(tiles, q, k, v, seg), q, k, v)
            return pull(do)

        return time_ms(jax.jit(f), q, k, v, do)

    def visited(bq, bk):
        if calls[0] is None:
            return None
        shares = [tiles_visited_share(s, block_q=bq, block_k=bk, window=args.window) for s in segs]
        return round(float(np.mean(shares)), 4)

    def sweep(what, run, tiles_of):
        rows = []
        for bq, bk in pairs:
            try:
                ms = run(tiles_of(bq, bk))
            except Exception as e:  # noqa: BLE001 - a tile that fails to lower is data
                print(f"{what} ({bq:4d},{bk:4d}): FAILED {type(e).__name__}: {str(e)[:120]}")
                continue
            rows.append({"block_q": bq, "block_k": bk, "ms": round(ms, 4), "visited": visited(bq, bk)})
            print(f"{what} ({bq:4d},{bk:4d}): {ms:8.4f} ms a call, visits {rows[-1]['visited']}")
        return sorted(rows, key=lambda r: r["ms"])

    fwd = sweep("fwd", forward, lambda bq, bk: (bq, bk, bq, bk))
    best = (fwd[0]["block_q"], fwd[0]["block_k"])
    both = sweep("fwd+bwd", forward_backward, lambda bq, bk: best + (bq, bk))
    for r in both:
        r["bwd_ms"] = round(r["ms"] - fwd[0]["ms"], 4)
    auto = _auto_blocks(S, S, args.packed, D)
    print(json.dumps({
        "geometry": f"B={B} S={S} H={H} KH={KH} D={D} packed={args.packed} window={args.window} calls={len(calls)}",
        "forward": fwd,
        "forward_tiles_under_backward": best,
        "forward_plus_backward": both,
        "auto_blocks": auto,
        "auto_ms": {"fwd": round(forward(auto), 4), "fwd+bwd": round(forward_backward(auto), 4)},
        "backward": backward_form(S, D),
        "device": str(jax.devices()[0]),
    }))


if __name__ == "__main__":
    main()
