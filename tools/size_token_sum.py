"""Size the share layer's token-side sum on the chip: ``moe.slots_to_tokens``
alone against ``moe._by_token`` alone, the forward's form (with the weights)
and the backward's (without), at the expert cells' shapes and loads, over the
kernel's tiles (PERF.md section 6, PR 49).

    python tools/size_token_sum.py [--shapes sdar,laguna] [--tiles 512x16x1024,...] [--out NAME]

Routing is drawn on the host from ``--seed``: every token chooses ``top_k`` of
the experts, the held ones weighted so that about ``load`` slots land on them,
evenly or with one held expert at three times the others' mean. Prints a row a
case and writes them to ``chiprun_out/<NAME>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from maggy_tpu.models import moe  # noqa: E402

# T, top_k, d_model, held, experts, a layer's slots on held experts (the ledger's ``train.moe_slots_mean`` over the layers)
SHAPES = {
    "sdar": (32768, 8, 2048, 16, 128, (34_000,)),
    "lfm2": (32768, 4, 2048, 8, 64, (22_000, 88_000)),
    "glm": (16384, 4, 2048, 8, 64, (10_000, 30_000)),
    "smallthinker": (16384, 6, 2560, 16, 64, (26_000,)),
    "laguna": (8192, 10, 3072, 8, 256, (2_000, 20_000)),
}
TILES = [(b, r, 2 * b) for b in (256, 512) for r in (16, 32, 64)] + [(512, 16, 2048)]


def choices(rng, t, k, held, n_experts, load, skewed):
    """[T, k] expert numbers, about ``load`` of the slots on experts below ``held``."""
    share = load / (t * k)
    w = share * (n_experts - held) / (held * (1 - share))
    p = np.ones(n_experts)
    p[:held] = w
    if skewed:
        p[:held] = w * (held - 3) / (held - 1)
        p[0] = 3 * w
    g = rng.gumbel(size=(t, n_experts)) + np.log(p)
    return np.argsort(-g, axis=1)[:, :k].astype(np.int32)


def ms(f, *args, n=10):
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tiles", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="size_token_sum")
    args = ap.parse_args()
    tiles = [tuple(int(v) for v in s.split("x")) for s in args.tiles.split(",")] if args.tiles else TILES
    if jax.default_backend() != "tpu":
        raise SystemExit("a sizing is a chip run: no TPU here")
    rng = np.random.default_rng(args.seed)
    plain = jax.jit(lambda rows, inv, held, w: moe._by_token(rows, inv, held, w).astype(rows.dtype))
    out = []
    for name in args.shapes.split(","):
        t, k, d, held, n_experts, loads = SHAPES[name]
        rows = jax.random.normal(jax.random.key(args.seed), (t * k, d), jnp.bfloat16)
        weights = jax.random.uniform(jax.random.key(args.seed + 1), (t, k), jnp.bfloat16)
        for load in loads:
            for skewed in (False, True):
                sel = jnp.asarray(choices(rng, t, k, held, n_experts, load, skewed))
                is_held = sel < held
                key = jnp.where(is_held, sel, held).reshape(-1)
                inv = moe.counting_sort(key, held + 1)[0].reshape(t, k)
                forms = (("fwd", weights), ("bwd", None))
                base = {form: ms(plain, rows, inv, is_held, w) for form, w in forms}
                want = {form: plain(rows, inv, is_held, w) for form, w in forms}
                for tile in tiles:
                    _, counts, ends = moe.counting_sort(key, held + 1, every=tile[0] * k)
                    counts = counts[:held]
                    runs = moe.token_tiles(ends[:, :held], counts, tile[1])
                    row = dict(
                        shape=name, t=t, top_k=k, d=d, held=held, experts=n_experts, load=int(counts.sum()),
                        load_max_over_mean=float(counts.max() / counts.mean()), skewed=skewed, tiles=list(tile),
                        rows_share=float(runs[1].sum() * tile[1] / (t * k)),
                    )
                    for form, w in forms:
                        f = lambda *a, w=w: moe.slots_to_tokens(*a, w, tiles=tile)  # noqa: E731
                        a = (rows, runs, counts.sum(), inv, is_held)
                        row[f"kernel_{form}_ms"] = ms(f, *a)
                        row[f"plain_{form}_ms"] = base[form]
                        row[f"same_{form}"] = bool(jnp.array_equal(f(*a), want[form]))
                    out.append(row)
                    print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out + ".json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
