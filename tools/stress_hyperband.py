"""Controller-level scheduling stress: does Hyperband
keep a 16-executor fleet busy at 256-trial scale, with stragglers?

Simulates the driver's scheduling loop against the REAL controllers (the
same get_suggestion path `core/driver/hpo.py _try_assign` drives, one
decision at a time on one thread — the production discipline) under a
synthetic oracle: trial runtime = budget × unit, with a straggler fraction
running 8× slower. Records, per configuration:

* executor-idle fraction (idle executor-seconds / fleet-seconds to makespan)
* simulated makespan
* controller decisions/second of real Python time (the `_pending` question:
  the gate is consumed within one get_suggestion call, so the measurement
  shows whether serialized decisions could ever throttle a fleet)

Configurations: Hyperband with concurrent cycles (iterations=N — later
cycles' base rungs fill the straggler-gated idle), the same budget as
SERIAL cycles (the pre-`iterations` behavior), and ASHA at a matched trial
count.

    python tools/stress_hyperband.py [--executors 16] [--straggler 0.05]
"""

import argparse
import heapq
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

from maggy_tpu.optimizer import IDLE, get_optimizer
from maggy_tpu.pruner.hyperband import Hyperband
from maggy_tpu.searchspace import Searchspace
from maggy_tpu.trial import Trial


def simulate(controller_factory, n_executors: int, straggler_frac: float,
             seed: int = 0):
    """Run one synthetic experiment to completion; return the stats dict."""
    import random

    py_rng = random.Random(seed)
    trial_store = {}
    final_store = []
    controller = controller_factory(trial_store, final_store)

    clock = 0.0
    busy_until = [0.0] * n_executors
    busy_time = [0.0] * n_executors
    events = []  # (finish_time, executor, trial)
    idle_execs = set(range(n_executors))
    decisions = 0
    py_time = 0.0
    finished_last = None

    def try_fill():
        nonlocal decisions, py_time, finished_last
        progressed = True
        while idle_execs and progressed:
            progressed = False
            ex = min(idle_execs)
            t0 = time.perf_counter()
            suggestion = controller.get_suggestion(finished_last)
            py_time += time.perf_counter() - t0
            finished_last = None
            decisions += 1
            if isinstance(suggestion, Trial):
                budget = suggestion.params.get("budget") or 1.0
                runtime = float(budget)
                if py_rng.random() < straggler_frac:
                    runtime *= 8.0  # straggler
                suggestion.schedule(ex)
                trial_store[suggestion.trial_id] = suggestion
                heapq.heappush(events, (clock + runtime, ex, suggestion))
                busy_until[ex] = clock + runtime
                busy_time[ex] += runtime
                idle_execs.discard(ex)
                progressed = True
            elif suggestion == IDLE:
                break  # nothing schedulable until something finishes
            else:  # None: controller exhausted
                break

    try_fill()
    while events:
        clock, ex, trial = heapq.heappop(events)
        trial_store.pop(trial.trial_id, None)
        trial.begin()
        trial.finalize(py_rng.random())
        final_store.append(trial)
        idle_execs.add(ex)
        finished_last = trial
        try_fill()

    makespan = max(busy_until) if any(busy_until) else 0.0
    fleet_seconds = makespan * n_executors
    idle_frac = 1.0 - (sum(busy_time) / fleet_seconds) if fleet_seconds else 0.0
    return {
        "trials": len(final_store),
        "makespan": round(makespan, 2),
        "idle_fraction": round(idle_frac, 4),
        "decisions": decisions,
        "decisions_per_sec_python": round(decisions / py_time, 1) if py_time else None,
        "controller_s_per_decision_us": round(py_time / decisions * 1e6, 1),
    }


def hyperband_factory(iterations: int, seed: int = 0):
    def make(trial_store, final_store):
        def metric_getter(trial_ids):
            if isinstance(trial_ids, str):
                trial_ids = [trial_ids]
            return {
                t.trial_id: t.final_metric
                for t in final_store
                if t.trial_id in trial_ids
            }

        pruner = Hyperband(
            trial_metric_getter=metric_getter, eta=3, resource_min=1,
            resource_max=9, direction="max", iterations=iterations,
        )
        controller = get_optimizer("randomsearch", seed=seed)
        controller.setup(
            Searchspace(x=("DOUBLE", [0.0, 1.0])),
            pruner.num_trials(),
            trial_store,
            final_store,
            direction="max",
            pruner=pruner,
        )
        return controller

    return make


def asha_factory(num_trials: int, seed: int = 0):
    def make(trial_store, final_store):
        controller = get_optimizer(
            "asha", seed=seed, reduction_factor=3, resource_min=1, resource_max=9
        )
        controller.setup(
            Searchspace(x=("DOUBLE", [0.0, 1.0])),
            num_trials,
            trial_store,
            final_store,
            direction="max",
        )
        return controller

    return make


def run_suite(n_executors: int = 16, straggler: float = 0.05, cycles: int = 12,
              seed: int = 0):
    """Concurrent against serial cycles against ASHA; ~22 trials/cycle x 12 = 264 ≈ the
    256-trial bar."""
    concurrent = simulate(
        hyperband_factory(iterations=cycles, seed=seed), n_executors, straggler,
        seed=seed,
    )
    # pre-`iterations` behavior: the same budget as strictly serial cycles
    serial_total = {"trials": 0, "makespan": 0.0, "decisions": 0}
    idle_accum = 0.0
    for c in range(cycles):
        r = simulate(
            hyperband_factory(iterations=1, seed=seed + c), n_executors,
            straggler, seed=seed + c,
        )
        serial_total["trials"] += r["trials"]
        serial_total["makespan"] += r["makespan"]
        serial_total["decisions"] += r["decisions"]
        idle_accum += r["idle_fraction"] * r["makespan"]
    serial_total["idle_fraction"] = round(
        idle_accum / serial_total["makespan"], 4
    )
    serial_total["makespan"] = round(serial_total["makespan"], 2)
    asha = simulate(
        asha_factory(num_trials=concurrent["trials"], seed=seed), n_executors,
        straggler, seed=seed,
    )
    return {
        "n_executors": n_executors,
        "straggler_fraction": straggler,
        "hyperband_concurrent_cycles": concurrent,
        "hyperband_serial_cycles": serial_total,
        "asha_matched_trials": asha,
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--executors", type=int, default=16)
    parser.add_argument("--straggler", type=float, default=0.05)
    parser.add_argument("--cycles", type=int, default=12)
    args = parser.parse_args()
    print(json.dumps(run_suite(args.executors, args.straggler, args.cycles)))


if __name__ == "__main__":
    main()
