"""Autotuner CLI: find the best system config for a model on this host.

    python -m maggy_tpu.tune --config tiny --presets dp,fsdp,2d \
        --batch-sizes 8,16,32 --seq-len 128
    python -m maggy_tpu.tune --config llama3_8b --budget-gb 14 --no-measure

Prints ONE JSON line (the TuneResult) on stdout; progress goes to stderr.
The winner also lands in the tuning cache under the ambient experiment root
(``MAGGY_TPU_LOG_ROOT``/``tune_cache``, local or ``gs://``), where
``python -m maggy_tpu.serve --mesh auto`` picks it up.
"""

from __future__ import annotations

import argparse
import json
import sys


def _csv(text: str, cast=str):
    return tuple(cast(x) for x in text.split(",") if x)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m maggy_tpu.tune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--config", default="tiny",
                        help="DecoderConfig preset name or .json file")
    parser.add_argument("--presets", default="dp,fsdp,2d",
                        help="comma-separated mesh presets")
    parser.add_argument("--batch-sizes", default="8,16,32",
                        help="comma-separated global batch sizes")
    parser.add_argument("--microbatches", default="",
                        help="comma-separated n_microbatches options (pp meshes)")
    parser.add_argument("--remat", default="",
                        help="comma-separated remat policies to try "
                             "(nothing/dots/everything)")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--budget-gb", type=float,
                        help="per-device HBM budget for the AOT prune "
                             "(default: ask the device; CPU has none)")
    parser.add_argument("--no-measure", action="store_true",
                        help="static stage only — rank by flops/bytes")
    parser.add_argument("--no-cache", action="store_true",
                        help="ignore and don't write the tuning cache")
    parser.add_argument("--steps-per-unit", type=int, default=4,
                        help="train steps per unit of ASHA budget")
    parser.add_argument("--max-candidates", type=int, default=64)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    from maggy_tpu.models import Decoder
    from maggy_tpu.serve.__main__ import build_config
    from maggy_tpu.tune import TuneConfig, tune

    model = Decoder(build_config(args.config))
    remat = _csv(args.remat) or (None,)
    micro = _csv(args.microbatches, int) or (None,)
    tune_cfg = TuneConfig(
        presets=_csv(args.presets),
        batch_sizes=_csv(args.batch_sizes, int),
        microbatches=micro,
        remat_policies=remat,
        seq_len=args.seq_len,
        hbm_budget_bytes=(
            int(args.budget_gb * 2**30) if args.budget_gb else None
        ),
        measure=not args.no_measure,
        cache=not args.no_cache,
        steps_per_unit=args.steps_per_unit,
        max_candidates=args.max_candidates,
        seed=args.seed,
    )
    print(
        f"[tune] model={args.config} presets={tune_cfg.presets} "
        f"batch_sizes={tune_cfg.batch_sizes} seq_len={tune_cfg.seq_len}",
        file=sys.stderr,
    )
    result = tune(model, tune_cfg)
    out = result.to_dict()
    out.pop("reports", None)  # one-line summary; full reports live in the cache
    best = result.best
    print(
        f"[tune] {'cache hit' if result.cache_hit else 'tuned'}: "
        f"spec={best.spec} bs={best.batch_size} "
        f"remat={best.remat_policy} source={best.source}",
        file=sys.stderr,
    )
    print(json.dumps(out), file=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
