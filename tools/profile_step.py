"""Capture a profiler trace of the bench-geometry train step. Writes a
TensorBoard-readable trace to chiprun_out/profile_step/ (the directory a
chip run brings back) for MFU-gap analysis.

    python tools/profile_step.py [--steps 5]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument(
        "--out",
        default=os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "chiprun_out", "profile_step",
        ),
    )
    args = parser.parse_args()

    from bench import bench_setup, on_cpu

    cpu = on_cpu()

    import jax

    # shared with bench.py — the trace is only useful if it profiles exactly
    # the step (model, sharding, optimizer, batch, warmup) the record was
    # set on; compile happens inside bench_setup, outside the trace
    trainer, state, batch, cfg, batch_size, seq_len = bench_setup(cpu)

    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            state, m = trainer.step(state, batch)
        jax.block_until_ready(m)
    print(f"trace written to {args.out} ({args.steps} steps, cpu={cpu})")
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = trainer.step(state, batch)
    jax.block_until_ready(m)
    print(f"untraced step: {(time.perf_counter() - t0) / args.steps * 1e3:.2f} ms")


if __name__ == "__main__":
    sys.exit(main())
