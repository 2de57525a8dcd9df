"""Put each control of a cell's configuration in the program's place, at the
cell's own size, and judge it as a run would be judged: every variant has to
come out as not correct. The limits of ``correct`` are set from these readings
and the sound runs'. One process for all seeds; no program of the system runs.

    python3 benchmark/tools/control.py --workload W --seeds 1,2,3 [--rehearse-cpu]

Appends one object a seed to ``chiprun_out/control-<W>.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (imports no jax)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    _, _, config, mix = run.load_cell(args.workload, args.rehearse_cpu)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("no accelerator")
    kind = importlib.import_module(f"benchmark.kinds.{mix['kind']}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    passed = []
    for seed in args.seeds.split(","):
        verdicts = kind.controls(config, mix, int(seed), lambda text: print(f"[control] {text}", file=sys.stderr, flush=True))
        row = {"seed": int(seed), "device": jax.devices()[0].device_kind,
               "variants": {name: {"correct": v.correct, "rows": v.rows} for name, v in verdicts.items()}}
        with open(os.path.join(ROOT, "chiprun_out", f"control-{args.workload}.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        passed += [(int(seed), name) for name, v in verdicts.items() if v.correct]
    print("controls judged correct (there should be none):", passed)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
