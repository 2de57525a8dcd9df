"""A sidecar that owns no chip and does nothing but sleep 20 ms at a time and
write down every wake-up that came more than 0.2 s late, with the wall time and
the seconds the hypervisor took from this machine's cores (``steal`` in
``/proc/stat``, all cores together).
Run beside a set of runs, it tells a frozen machine (its gaps fall on the
runs' slow steps) from a stall of the benchmark's own process (no gap here).

    python3 benchmark/tools/clockwatch.py & ...runs...; kill $!

Appends to ``chiprun_out/clockwatch.jsonl``; ends on SIGTERM.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def stolen_s() -> float:
    with open("/proc/stat") as f:
        return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")


def main() -> int:
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    with open(os.path.join(ROOT, "chiprun_out", "clockwatch.jsonl"), "a") as f:
        start = last = time.time()
        worst, n, stolen0 = 0.0, 0, stolen_s()
        stolen = stolen0
        while not stop:
            time.sleep(0.02)
            now = time.time()
            worst, n = max(worst, now - last - 0.02), n + 1
            if now - last > 0.22:
                f.write(json.dumps({"from": last, "to": now, "late_s": now - last - 0.02,
                                    "stolen_s": stolen_s() - stolen}) + "\n")
                f.flush()
            last, stolen = now, stolen_s()
        f.write(json.dumps({"watched_from": start, "to": last, "wakeups": n, "latest_s": worst,
                            "stolen_s": stolen - stolen0}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
