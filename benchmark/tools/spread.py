"""The spread of each end-to-end metric over two sets of runs of one cell, as
the contract measures it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the wider of
the two sets, and five times that as the bound it suggests. Beside it the
reading the driver holds a bound's tightness to: the mean of the two sets'
spreads with each set's run farthest from its median left out.

    python3 benchmark/tools/spread.py chiprun_out/<set A>.jsonl chiprun_out/<set B>.jsonl
"""

from __future__ import annotations

import json
import statistics
import sys


def load(path):
    rows = [json.loads(l) for l in open(path)]
    return [r for r in rows if r.get("line") and r["trace"] == 0]


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return spread(rest)


def main(paths) -> int:
    sets = [load(p) for p in paths]
    names = sorted({n for s in sets for r in s for n in r["line"]["metrics"]})
    for n in names:
        per_set = []
        for s in sets:
            vals = [r["line"]["metrics"][n]["value"] for r in s if n in r["line"]["metrics"]]
            # the first run of a checkout compiles: its set-up is recorded apart
            if n == "setup_s" and len(vals) > 2 and vals[0] > 1.3 * statistics.median(vals[1:]):
                vals = vals[1:]
            per_set.append(vals)
        med = [statistics.median(v) for v in per_set]
        spr = [spread(v) for v in per_set if len(v) >= 2]
        tight = statistics.mean(trimmed(v) for v in per_set if len(v) >= 3)
        print(f"{n}: medians {[round(m, 4) for m in med]} spreads {[round(x, 5) for x in spr]} "
              f"wider {max(spr):.5f} bound@5x {5 * max(spr):.4f} farthest-left-out mean {tight:.5f} "
              f"second/first median {med[-1] / med[0] - 1:+.4%}")
    bad = [(r["seed"], r["line"]["correct"], r["line"]["failed"]) for s in sets for r in s if not r["line"]["correct"]]
    print("runs not correct:", bad, "of", sum(len(s) for s in sets))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
