"""Run one cell several times, each run a new process, and keep every last
line. The parent never touches JAX, so each child has the chip to itself.

    python3 benchmark/tools/runs.py --workload W --seeds 1,2,3 --seconds 10 \
        [--trace 0|1] [--out name]

Writes ``chiprun_out/<out>.jsonl`` (one object a run: seed, wall seconds, exit
code, the set-up phases, the comparisons and the last line) and prints the
spread of every metric as the contract defines it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from spread import spread  # the sibling script: this one is run by path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, (args.out or args.workload) + ".jsonl")
    rows = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, "benchmark/run.py", "--workload", args.workload, "--seed", seed,
               "--seconds", args.seconds, "--trace", args.trace]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        row = {"seed": int(seed), "wall_s": wall, "rc": p.returncode, "trace": int(args.trace),
               "seconds": float(args.seconds)}
        lines = p.stdout.strip().splitlines()
        row["comparisons"] = [json.loads(l[len("comparison "):]) for l in lines if l.startswith("comparison ")]
        row["notes"] = [l for l in p.stderr.splitlines() if l.startswith("[bench]")]
        try:
            row["line"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            row["line"] = None
            row["stderr_tail"] = p.stderr[-3000:]
            with open(os.path.join(out_dir, f"{args.out or args.workload}.seed{seed}.stderr.txt"), "w") as f:
                f.write(p.stderr[-200000:])
        rows.append(row)
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        for c in row["comparisons"]:
            print(f"   {c['name']}: {c['value']:.6g} (limit {c['limit']}) {c['note']}")
        short = {k: round(v["value"], 4) for k, v in (row["line"] or {}).get("metrics", {}).items()}
        print(f"seed {seed} rc {p.returncode} wall {wall:.1f}s correct {(row['line'] or {}).get('correct')} "
              f"{short} {' | '.join(row['notes'][-2:])}", flush=True)
        if row["line"] is None:
            head = [l for l in p.stderr.splitlines() if "Error" in l or "RESOURCE" in l or "Traceback" in l]
            print("\n".join(head[:20]), flush=True)
            print(p.stderr[-1500:], flush=True)
    good = [r["line"] for r in rows if r["line"]]
    names = sorted({n for l in good for n in l["metrics"]})
    for n in names:
        vals = [l["metrics"][n]["value"] for l in good if n in l["metrics"]]
        if len(vals) >= 2:
            print(f"{n}: median {statistics.median(vals):.6g} min {min(vals):.6g} max {max(vals):.6g} "
                  f"spread {spread(vals):.5f} first {vals[0]:.6g} n {len(vals)}")
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
