"""Serving CLI: load a checkpoint onto a mesh and serve it over RPC.

    python -m maggy_tpu.serve --config tiny --slots 8
    python -m maggy_tpu.serve --config llama3_8b --checkpoint /ckpts/run7 \
        --mesh fsdp --slots 16 --port 7777
    # fleet mode: router + N engine replicas behind one address
    python -m maggy_tpu.serve --config tiny --replicas 2 --slo-ttft-ms 2000

Without ``--checkpoint`` the model is randomly initialized (``--seed``) — the
demo/smoke path. The process prints the address and experiment secret on
stderr; point clients (:class:`maggy_tpu.serve.ServeClient`) or the live
monitor (``python -m maggy_tpu.monitor <host:port> <secret> --dashboard``)
at it. With ``--exp-dir`` the engine's telemetry lands in
``<exp_dir>/telemetry/worker_serve.jsonl`` for the Chrome-trace /
TensorBoard exporters.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import signal
import sys
import time


def build_config(name: str, max_seq_len=None):
    """A ``DecoderConfig`` from a preset name or a JSON file of overrides."""
    from maggy_tpu.models import DecoderConfig

    presets = {"tiny": DecoderConfig.tiny, "llama3_8b": DecoderConfig.llama3_8b}
    if name.endswith(".json"):
        with open(name) as f:
            cfg = DecoderConfig(**json.load(f))
    elif name in presets:
        cfg = presets[name]()
    else:
        raise SystemExit(
            f"unknown --config {name!r}: use {sorted(presets)} or a "
            ".json file of DecoderConfig fields"
        )
    if max_seq_len:
        import dataclasses

        cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
    return cfg


def load_or_init_params(model, cfg, checkpoint=None, step=None, seed=0, mesh=None):
    """Checkpoint params (train/checkpoint.py, params-only restore) or a
    seeded random init for checkpoint-free demo serving. With ``mesh`` every
    leaf is placed by the model's logical-axis rules (the random init is born
    sharded, never materialized on one device)."""
    import jax
    import jax.numpy as jnp

    from maggy_tpu.parallel.sharding import params_shardings, unbox

    dummy = jnp.zeros((1, min(8, cfg.max_seq_len)), jnp.int32)

    def init():
        return model.init(jax.random.key(seed), dummy)["params"]

    shardings = (
        None if mesh is None else params_shardings(mesh, jax.eval_shape(init))
    )
    if checkpoint:
        from maggy_tpu.train.checkpoint import Checkpointer

        params = Checkpointer(checkpoint, async_save=False).restore_params(step)
        return params if mesh is None else jax.device_put(params, shardings)
    with mesh if mesh is not None else contextlib.nullcontext():
        return unbox(jax.jit(init, out_shardings=shardings)())


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="python -m maggy_tpu.serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--config", default="tiny",
                        help="DecoderConfig preset name or .json file")
    parser.add_argument("--checkpoint", help="Checkpointer directory to restore")
    parser.add_argument("--step", type=int, help="checkpoint step (default latest)")
    parser.add_argument("--slots", type=int, default=4,
                        help="KV-cache slots = max concurrent requests")
    parser.add_argument("--mesh", default="none",
                        help="'none', a mesh preset (dp/fsdp/tp/...), or "
                             "'auto' to consult the autotuner cache "
                             "(maggy_tpu.tune) for this model+topology and "
                             "fall back to 'none' on a miss")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--secret", help="RPC secret (default: random)")
    parser.add_argument("--seed", type=int, default=0,
                        help="param init seed when serving without a checkpoint")
    parser.add_argument("--max-seq-len", type=int,
                        help="override the config's max_seq_len (cache size)")
    parser.add_argument("--exp-dir",
                        help="directory for telemetry JSONL export")
    parser.add_argument("--name", default="maggy-serve")
    parser.add_argument("--replicas", type=int, default=1,
                        help=">1 serves a fleet: a router front-end over N "
                             "engine replicas (docs/fleet.md)")
    parser.add_argument("--slo-ttft-ms", type=float,
                        help="TTFT budget: fleet admission sheds/queues "
                             "requests whose projected TTFT exceeds it; "
                             "single-engine mode counts slo_ok/slo_miss "
                             "attainment in SSTATS")
    parser.add_argument("--admission", choices=("queue", "shed"),
                        default="queue",
                        help="fleet behavior when projection exceeds the SLO")
    parser.add_argument("--max-restarts", type=int, default=1,
                        help="fleet-wide replica respawn budget")
    parser.add_argument("--paged", dest="paged", action="store_true",
                        default=None,
                        help="paged KV cache (default on; docs/serving.md)")
    parser.add_argument("--no-paged", dest="paged", action="store_false",
                        help="dense row-per-slot KV cache fallback")
    parser.add_argument("--page-size", type=int,
                        help="KV page size in tokens (power of two dividing "
                             "max_seq_len; default 16)")
    parser.add_argument("--num-pages", type=int,
                        help="KV page pool size; default reserves the dense "
                             "equivalent (slots x max_seq_len/page_size + 1)")
    parser.add_argument("--prefill-replicas", type=int, default=0,
                        help="disaggregated fleet: N extra prefill-only "
                             "replicas; prompts prefill there and the KV "
                             "pages hand off to decode replicas "
                             "(docs/fleet.md)")
    args = parser.parse_args(argv)
    if args.prefill_replicas and args.replicas < 1:
        raise SystemExit("--prefill-replicas needs at least one decode replica")
    return args


def build_server(args: argparse.Namespace):
    """Everything the CLI does up to a listening server: config, mesh,
    params, engine (or fleet), scheduler, RPC front-end, started. Returns
    ``(server, (host, port), telemetry_recorder_or_None)``; the caller owns
    ``stop()`` / ``close()``. :func:`main` adds only the signal handler and
    the wait, so an embedding process (``chip_smoke.py``) drives the same
    stack."""
    from maggy_tpu import util
    from maggy_tpu.models import Decoder
    from maggy_tpu.serve import Engine, Scheduler, ServeServer
    from maggy_tpu.telemetry import worker_telemetry

    # before the first jit: the engine's programs land in the same cache the
    # trainer uses
    cache_dir = util.enable_compilation_cache()
    if cache_dir:
        print(f"[serve] compile cache: {cache_dir}", file=sys.stderr)
    cfg = build_config(args.config, args.max_seq_len)
    model = Decoder(cfg)

    mesh = None
    if args.mesh == "auto":
        # tuned-winner lookup (grid-independent alias on the env seam);
        # cache-only — never compiles — so startup cost is one JSON read
        from maggy_tpu.tune import cached_best

        tuned = cached_best(model)
        if tuned is not None:
            mesh = tuned.mesh()
            print(
                f"[serve] mesh auto: tuning cache hit -> {dict(mesh.shape)} "
                f"(source={tuned.source})",
                file=sys.stderr,
            )
        else:
            print(
                "[serve] mesh auto: no tuning-cache record for this "
                "model/topology (run python -m maggy_tpu.tune); serving "
                "unsharded",
                file=sys.stderr,
            )
    elif args.mesh and args.mesh != "none":
        from maggy_tpu.parallel.mesh import mesh_for

        mesh, _ = mesh_for(sharding=args.mesh)
        print(f"[serve] mesh {args.mesh}: {dict(mesh.shape)}", file=sys.stderr)

    t0 = time.time()
    params = load_or_init_params(
        model, cfg, checkpoint=args.checkpoint, step=args.step, seed=args.seed,
        mesh=mesh,
    )
    src = args.checkpoint or f"random init (seed {args.seed})"
    print(f"[serve] params from {src} in {time.time() - t0:.1f}s", file=sys.stderr)

    tel = None
    if args.exp_dir:
        tel = worker_telemetry("serve", args.exp_dir, role="serve")
    if args.replicas > 1 or args.prefill_replicas > 0:
        from maggy_tpu.serve.fleet import ReplicaSpec, launch_fleet

        tel_factory = None
        if args.exp_dir:
            tel_factory = lambda i: worker_telemetry(  # noqa: E731
                f"replica{i}", args.exp_dir, role="serve"
            )
        spec = ReplicaSpec(
            cfg, params, num_slots=args.slots, mesh=mesh,
            telemetry_factory=tel_factory,
            paged=args.paged, page_size=args.page_size,
            num_pages=args.num_pages,
        )
        server = launch_fleet(
            spec,
            replicas=args.replicas,
            secret=args.secret,
            name=args.name,
            slo_ttft_ms=args.slo_ttft_ms,
            admission=args.admission,
            max_restarts=args.max_restarts,
            telemetry_recorder=tel,
            prefill_replicas=args.prefill_replicas,
        )
        host, port = server.start(host=args.host, port=args.port)
        what = f"fleet router ({args.replicas} replicas"
        if args.prefill_replicas:
            what += f" + {args.prefill_replicas} prefill"
        what += ")"
    else:
        engine = Engine(
            cfg, params, num_slots=args.slots, mesh=mesh,
            telemetry_recorder=tel, paged=args.paged,
            page_size=args.page_size, num_pages=args.num_pages,
        )
        scheduler = Scheduler(engine, slo_ttft_ms=args.slo_ttft_ms)
        server = ServeServer(scheduler, secret=args.secret, name=args.name)
        host, port = server.start(host=args.host, port=args.port)
        what = "engine"
    print(
        f"[serve] {what} listening on {host}:{port}\n"
        f"[serve] secret: {server.secret}\n"
        f"[serve] monitor: python -m maggy_tpu.monitor {host}:{port} "
        f"{server.secret} --dashboard",
        file=sys.stderr,
    )
    return server, (host, port), tel


def main(argv=None) -> int:
    server, _, tel = build_server(parse_args(argv))
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    print("[serve] shutting down", file=sys.stderr)
    server.stop()
    if tel is not None:
        tel.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
