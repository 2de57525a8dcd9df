"""Profile the chunked delta rule on the chip: ``ops.kda.kda`` forward and
backward alone at a cell's shape, under the profiler, with the device's time
put down to the compiled program's operations by the scope and primitive of
each (``op_name``), so that a ``perf_opt`` session sees which part of
``kda.scan`` takes the time before it changes any (PERF.md section 5).

    python tools/profile_kda.py [--seq 8192] [--heads 32] [--dim 128] [--chunk 64] [--out NAME]

One packed row of documents of about 600 positions. Prints ms a call (forward
and backward together) for the top operations and writes them, with the sums
by primitive, to ``chiprun_out/<NAME>.json``. It refuses to run off a TPU.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402
from maggy_tpu.ops import kda as ops_kda  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=ops_kda.CHUNK)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--out", default="profile_kda")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("a device profile: run it on the chip")
    s, h, d = args.seq, args.heads, args.dim
    rng = np.random.default_rng(0)
    lengths = np.clip(rng.lognormal(np.log(600), 1.0, 64).astype(int), 16, s)
    seg = np.repeat(np.arange(1, 65), lengths)[:s]
    seg = jnp.asarray(np.pad(seg, (0, s - len(seg)))[None], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 6)
    unit = lambda z: z / jnp.linalg.norm(z.astype(jnp.float32), axis=-1, keepdims=True)
    q, k, v = (jax.random.normal(keys[i], (1, s, h, d), jnp.float32) for i in range(3))
    q, k, v = (unit(q) * d**-0.5).astype(jnp.bfloat16), unit(k).astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    a = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (1, s, h, d)) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, h)))
    w = jax.random.normal(keys[5], (1, s, h, d), jnp.bfloat16)

    def loss(q, k, v, a, beta):
        with jax.named_scope("kda.scan"):
            o = ops_kda.kda(q, k, v, a, beta, seg, args.chunk)
        return (o * w).astype(jnp.float32).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    compiled = step.lower(q, k, v, a, beta).compile()
    text = compiled.as_text()
    jax.block_until_ready(compiled(q, k, v, a, beta))
    out_dir = os.path.join(ROOT, "chiprun_out")
    trace_dir = os.path.join(ROOT, ".bench_out", "profile_kda")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.calls):
        jax.block_until_ready(compiled(q, k, v, a, beta))
    jax.profiler.stop_trace()
    summary = trace.reduce(trace_dir)
    # an operation's scope and primitive from the program's text
    meta = dict(re.findall(r"%?([\w.\-]+) = [^\n]*op_name=\"([^\"]*)\"", text))
    rows, by_kind = [], collections.Counter()
    for label, seconds in summary["device_ops"]:
        name = label.split(" ")[0]
        op_name = meta.get(name, "")
        ms = seconds * 1e3 / args.calls
        rows.append({"op": label, "ms": ms, "op_name": op_name[-120:]})
        kind = "kernel " + name.split(".")[0] if name.startswith("kda_") else (
            "backward" if "transpose" in op_name else "forward") + " " + op_name.rsplit("/", 1)[-1]
        by_kind[kind] += ms
    total = summary["busy_s"] * 1e3 / args.calls
    print(f"busy {total:.2f} ms a call (forward and backward), memory {compiled.memory_analysis().temp_size_in_bytes / 2**30:.2f} GiB of temporaries")
    for kind, ms in by_kind.most_common(25):
        print(f"  {ms:8.3f} ms  {kind}")
    for row in rows[:40]:
        print(f"  {row['ms']:8.3f} ms  {row['op'][:60]:60s} {row['op_name'][-90:]}")
    with open(os.path.join(out_dir, args.out + ".json"), "w") as f:
        json.dump({"args": vars(args), "busy_ms": total, "by_kind": dict(by_kind), "ops": rows[:200]}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
