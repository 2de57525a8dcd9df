"""Continuous-batching serving demo — the ISSUE 2 acceptance run, end to end.

Boots the full serving stack (engine + scheduler + RPC server) on CPU with
B=4 KV-cache slots, drives 8 requests with staggered arrivals through the
socket client, and then PROVES the three acceptance properties:

1. every request's greedy output equals a one-shot ``generate_cached`` over
   the same prompt (continuous batching changes latency, never tokens);
2. the jitted decode step compiled exactly ONCE for the whole run, across
   admissions, evictions, and varying prompt lengths (compile-count
   telemetry);
3. TTFT / queue-depth / tokens-per-sec gauges landed in the exported
   telemetry JSONL, and the monitor's STATUS panel renders them.

    JAX_PLATFORMS=cpu python examples/serving_demo.py
"""

import dataclasses
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.models.generate import generate_cached
from maggy_tpu.parallel.sharding import unbox
from maggy_tpu.serve import Engine, Scheduler, ServeClient, ServeServer
from maggy_tpu.telemetry import worker_telemetry

if __name__ == "__main__":
    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = Decoder(cfg)
    params = unbox(
        model.init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"]
    )

    exp_dir = tempfile.mkdtemp(prefix="maggy_serve_demo_")
    tel = worker_telemetry("serve", exp_dir, role="serve")
    engine = Engine(cfg, params, num_slots=4, telemetry_recorder=tel)
    server = ServeServer(Scheduler(engine))
    host, port = server.start(host="127.0.0.1")
    print(f"serving on {host}:{port} with B=4 slots")

    prompts = [
        [1, 2, 3, 4], [5, 6, 7], [9, 10, 11, 12, 13], [2, 4, 6, 8, 10, 12],
        [7, 3], [20, 21, 22, 23], [30, 31], [40, 41, 42, 44, 45, 46, 47],
    ]
    MAX_NEW = 6
    results = {}

    def drive(i, prompt, delay):
        time.sleep(delay)  # staggered arrivals: requests churn through slots
        with ServeClient((host, port), server.secret) as client:
            t0 = time.time()
            results[i] = client.generate(prompt, max_new=MAX_NEW, timeout=120)
            print(f"  request {i} (len {len(prompt)}): "
                  f"{results[i]}  [{time.time() - t0:.2f}s]")

    threads = [
        threading.Thread(target=drive, args=(i, p, 0.05 * i))
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # 1. greedy equivalence against one-shot generate_cached
    decode_model = Decoder(dataclasses.replace(cfg, decode=True))
    for i, prompt in enumerate(prompts):
        buf = np.zeros((1, len(prompt) + MAX_NEW), np.int32)
        buf[0, : len(prompt)] = prompt
        ref = np.asarray(
            generate_cached(
                decode_model, params, jnp.asarray(buf),
                jnp.asarray([len(prompt)]),
            )
        )[0, len(prompt):]
        assert results[i] == list(ref), (i, results[i], list(ref))
    print("1. greedy outputs == one-shot generate_cached for all 8 requests")

    # 2. compile-once decode step, via the compile-count telemetry
    with ServeClient((host, port), server.secret) as client:
        stats = client.stats()
        status = client._client._request({"type": "STATUS"})
    assert stats["compile_counts"]["decode"] == 1, stats["compile_counts"]
    print(f"2. decode step compiled exactly once "
          f"(compile_counts={stats['compile_counts']})")

    # 3. telemetry gauges in the JSONL export + monitor panel
    from maggy_tpu.monitor import render_status

    panel = render_status(status)
    server.stop()
    tel.close()
    path = os.path.join(exp_dir, "telemetry", "worker_serve.jsonl")
    with open(path) as f:
        gauges = {
            r["name"]
            for r in map(json.loads, f)
            if r.get("kind") == "gauge"
        }
    need = {"serve.ttft_ms", "serve.queue_depth", "serve.tokens_per_sec"}
    assert need <= gauges, (need - gauges, path)
    print(f"3. gauges {sorted(need)} exported to {path}")
    print("\nmonitor panel:\n" + panel)
    print(f"\nttft p50={stats['ttft_ms_p50']:.0f}ms "
          f"p95={stats['ttft_ms_p95']:.0f}ms  "
          f"tokens_out={stats['tokens_out']}")
    print("serving demo OK")
