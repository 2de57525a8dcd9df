"""The serve engine under one profiler trace, reduced with ``benchmark/spans.py``:
device idle time by scheduler phase, decode device time by scope. Not a cell
and not a metric: the evidence that the scheduler's spans and the decode
scopes are the ones a serve cell will want (PERF.md section 7).

    python3 benchmark/tools/serve_timeline.py [--requests 30] [--rate 0.8] [--seed 1] [--rehearse-cpu]

One process: ``build_server`` (``mistral-7b-v0.3`` widths at 6 layers, 32
slots, page pool 4,609 x 16), a warm-up pass of requests drawn like the
measured ones, then ``--requests`` requests offered at ``--rate`` a second
inside ``jax.profiler`` (Python tracer off: the program's spans are
``TraceAnnotation``s and stay). Prompts log-normal median 256, answers median
96, as PERF.md's ``serve-mistral7b-chat`` row has them. Prints one JSON
object, also written to ``chiprun_out/serve_timeline.json``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SERVE_SPANS = ("serve.sweep", "serve.admit", "serve.preempt", "serve.decode_step", "serve.emit",
               "serve.idle_wait", "serve.tick", "serve.prefill", "serve.prefix_admit", "serve.kv_admit",
               "serve.spill", "serve.reconfigure")
DECODE_GROUPS = {
    "decode_attn": "decode_attn", "kv_write": "kv_write", "sample": "sample",
    "mlp": "mlp", "mlp_norm": "mlp", "attn": "attn (projections, rope)", "attn_norm": "attn (projections, rope)",
    "lm_head": "head", "final_norm": "head",
}
WIDTHS = dict(vocab_size=32768, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=1e6)


def draw(rng, n, vocab, max_seq_len, toy):
    import numpy as np

    p = np.clip(rng.lognormal(np.log(256), 1.0, n), 32, 2048).astype(int)
    a = np.clip(rng.lognormal(np.log(96), 0.7, n), 16, 384).astype(int)
    if toy:
        p, a = np.clip(p // 16, 4, 40), np.clip(a // 16, 2, 8)
    p = np.minimum(p, max_seq_len - a - 1)
    return [(rng.integers(1, vocab, int(pl)).tolist(), int(al)) for pl, al in zip(p, a)]


def offer(sched, requests, rate):
    """Submit at fixed due times (a generator thread), return the Requests
    once all are terminal."""
    from maggy_tpu.serve import SamplingParams

    out = []
    t0 = time.time()
    for i, (prompt, max_new) in enumerate(requests):
        due = t0 + i / rate
        time.sleep(max(0.0, due - time.time()))
        out.append(sched.submit(prompt, SamplingParams(max_new=max_new)))
    deadline = time.time() + 1200
    while time.time() < deadline and not all(r.done_ts for r in out):
        time.sleep(0.05)
    return out


def module_of(modules, start):
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= start else None


def reduce(trace_dir, requests):
    from benchmark import spans, trace

    path = trace.find_xplane(trace_dir)
    tl = spans.Timeline(path, chips=1, span_names=SERVE_SPANS)
    devices, _hosts = trace.read_planes(path)
    modules = sorted((s, e, n.split("(")[0]) for s, e, n in trace.line_events(devices[0], "XLA Modules"))
    loop = tl.thread_of("serve.decode_step")
    idle = spans.idle_by_span(tl.gaps, loop or [])
    host = defaultdict(int)
    for s, e, n in spans.innermost(loop or []):
        host[n] += e - s
    per_module, decode, rest = defaultdict(int), defaultdict(int), defaultdict(int)
    for s, e, n, op_name in tl.ops[0]:
        m = module_of(modules, s)
        per_module[m] += e - s
        if m and m.startswith("jit__decode_impl"):
            group = spans.scope_of(op_name, DECODE_GROUPS)
            decode[group or ("no op_name" if not op_name else "other")] += e - s
            if group is None:
                rest[f"{trace.op_label(n)} | {(op_name or '')[-70:]}"] += e - s
    decode_total = sum(decode.values()) or 1
    tokens = sum(len(r.tokens) for r in requests)
    gaps = [1e3 * (b - a) for r in requests for a, b in zip(r.token_ts, r.token_ts[1:])]
    gaps.sort()
    return {
        "window_s": tl.window / 1e9, "busy_s": tl.busy / 1e9, "idle_share_pct": (1 - tl.busy / tl.window) * 100,
        "idle_by_phase_pct_of_window": {str(k): v / tl.window * 100 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "loop_thread_time_by_phase_s": {k: v / 1e9 for k, v in sorted(host.items(), key=lambda kv: -kv[1])},
        "device_s_by_program": {str(k): v / 1e9 for k, v in sorted(per_module.items(), key=lambda kv: -kv[1])},
        "decode_device_pct_by_scope": {k: v / decode_total * 100 for k, v in sorted(decode.items(), key=lambda kv: -kv[1])},
        "decode_unnamed_top_pct": {k: v / decode_total * 100 for k, v in sorted(rest.items(), key=lambda kv: -kv[1])[:8]},
        "decode_steps": sum(1 for _s, _e, n in modules if n.startswith("jit__decode_impl")),
        "requests": len(requests), "tokens": tokens,
        "inter_token_gap_ms": {"n": len(gaps), "p50": gaps[len(gaps) // 2], "p90": gaps[int(len(gaps) * 0.9)],
                               "p99": gaps[int(len(gaps) * 0.99)]} if gaps else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=30)
    ap.add_argument("--rate", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true", help="toy sizes on the CPU; no reduction")
    args = ap.parse_args(argv)
    toy = args.rehearse_cpu
    if toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["MAGGY_TPU_LOG_ROOT"] = os.path.join(ROOT, ".bench_out", "logs")

    import jax
    import numpy as np

    from maggy_tpu.serve.__main__ import build_server, parse_args

    # the compile cache's key leaves metadata out by default, so an executable
    # cached by an earlier program comes back with that program's ``op_name``s;
    # what is read here by name has to be compiled under those names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    if not toy and jax.devices()[0].platform != "tpu":
        raise SystemExit("no accelerator")
    fields = dict(WIDTHS, n_layers=6, max_seq_len=2304)
    if toy:
        fields = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, n_layers=2, max_seq_len=64)
    cfg_path = os.path.join(ROOT, ".bench_out", "serve_timeline_config.json")
    os.makedirs(os.path.dirname(cfg_path), exist_ok=True)
    with open(cfg_path, "w") as f:
        json.dump(fields, f)
    t0 = time.time()
    server, _addr, tel = build_server(parse_args([
        "--config", cfg_path, "--slots", "4" if toy else "32", "--mesh", "none", "--host", "127.0.0.1",
        "--port", "0", "--seed", "0", "--exp-dir", os.path.join(ROOT, ".bench_out", "serve_timeline"),
    ]))
    sched = server.scheduler
    rng = np.random.default_rng(args.seed)
    vocab, msl = fields["vocab_size"], fields["max_seq_len"]
    try:
        print(f"[serve_timeline] built in {time.time() - t0:.1f} s", file=sys.stderr, flush=True)
        t0 = time.time()
        warm = offer(sched, draw(rng, args.requests, vocab, msl, toy), rate=4 * args.rate)
        print(f"[serve_timeline] warm-up: {len(warm)} requests in {time.time() - t0:.1f} s, "
              f"compiles {sched.engine.compile_counts}", file=sys.stderr, flush=True)
        counts0 = dict(sched.engine.compile_counts)
        trace_dir = os.path.join(ROOT, ".bench_out", "serve_timeline_trace")
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.time()
        try:
            done = offer(sched, draw(rng, args.requests, vocab, msl, toy), rate=args.rate)
        finally:
            jax.profiler.stop_trace()
        wall = time.time() - t0
        stats = sched.stats()
        result = {
            "device": jax.devices()[0].device_kind, "traced_wall_s": wall,
            "states": sorted({r.state for r in done}),
            "out_tok_s": sum(len(r.tokens) for r in done) / wall,
            "compiles_in_trace": {k: v - counts0[k] for k, v in sched.engine.compile_counts.items()},
            "alerts_firing": [a["alert"] + ":" + str(a.get("program", "")) for a in stats["alerts"]],
            "ttft_ms_p50": stats.get("ttft_ms_p50"),
        }
        if not toy:
            result.update(reduce(trace_dir, done))
    finally:
        server.stop()
        if tel is not None:
            tel.close()
    print(json.dumps(result, indent=1))
    with open(os.path.join(out_dir, "serve_timeline.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
