"""The measured window a step at a time, as the program itself recorded it.

``Trainer.fit`` journals one span ``train.step_device`` a step from a thread of
its own (``fit-steps``): from the later of the previous step's end and the
step's dispatch to the moment its output was ready, on ``time.time()``, with
the step's loss and every step counter of the model (``moe_slots``, ...) as
attributes. ``Cell.marks`` are on the same clock, so the window's steps are the
records whose end lies in ``[marks["window"], window end]``, through
``setup_spans.load`` (the run's ``worker_<n>.jsonl``). "First" and "last" are
the window's first and last ``steps_per_chunk`` steps: one ``fit`` call each.

The same thread waits for each step inside a live span ``train.step_wait``,
which the profiler's trace carries on its own clock beside the device's
``XLA Modules`` line: :func:`done_lags_ms` holds the two against each other.

Against a program that records neither span (the parent of the PR that
brought them) every reader returns ``None``. The reductions are plain
functions over record lists (``checks/test_step_record_metrics.py``).
"""

from __future__ import annotations

import statistics
from collections import Counter

from benchmark import setup_spans, trace

STEP, WAIT = "train.step_device", "train.step_wait"


# ------------------------------------------------------------------ reductions


def window_steps(spans, lo, hi) -> list:
    """The ``train.step_device`` records that ended in ``[lo, hi]``, in order."""
    ended = [(r["ts"] + r["dur_ms"] / 1e3, r) for r in spans if r["name"] == STEP]
    return [r for end, r in sorted(ended, key=lambda x: x[0]) if lo <= end <= hi]


def mean_ms(steps):
    return statistics.fmean(r["dur_ms"] for r in steps) if steps else None


def attr_mean(steps, name):
    """The mean of one attribute over the steps that carry it."""
    values = [r["attrs"][name] for r in steps if name in r.get("attrs", {})]
    return statistics.fmean(values) if values else None


def growth(steps, k, name):
    """The last ``k`` steps' mean of an attribute over the first ``k`` steps'."""
    first, last = attr_mean(steps[:k], name), attr_mean(steps[-k:], name)
    return last / first if first and last is not None else None


def done_lags_ms(waits, modules) -> list:
    """How late the host learned of each traced step's end: the end of the
    step's ``train.step_wait`` annotation less the end of its run on the
    device, both ``(start_ns, end_ns)`` on the trace's clock. The steps ran in
    the order they were waited for, so the two lists pair off in order; a trace
    that holds another number of runs than of waits pairs nothing."""
    if not waits or len(waits) != len(modules):
        return []
    return [(w[1] - m[1]) / 1e6 for w, m in zip(sorted(waits), sorted(modules))]


def step_runs(modules) -> list:
    """Of the ``XLA Modules`` line's ``(start, end, name)`` events those of the
    program that ran most often: the train step."""
    names = Counter(name.split("(")[0] for _s, _e, name in modules)
    if not names:
        return []
    step = names.most_common(1)[0][0]
    return [(s, e) for s, e, name in modules if name.split("(")[0] == step]


# ------------------------------------------------------------------- the files


def load(obs):
    """This run's window: its steps in order, or ``None`` without the marks,
    the files or a single ``train.step_device`` record in the window."""
    setup = setup_spans.load(obs)
    if setup is None:
        return None
    return window_steps(setup.spans, setup.hi, setup.end) or None


def chunk(obs) -> int:
    return int(obs["cell"].mix["steps_per_chunk"])


def read(obs, reduction):
    steps = load(obs)
    return None if steps is None else reduction(steps)


def profiler_s(obs, steps) -> float:
    """Seconds of the window that starting and stopping the profiler took (a
    traced run; the harness stamps ``cell.trace_window`` when each call has
    returned): from the window's start to the first stamp, and from the end of
    the last step that ended before the second stamp to that stamp. No step runs
    in either, and the second is seconds long where the trace is large."""
    cell = obs["cell"]
    t0, t1 = getattr(cell, "trace_window", (None, None))
    if t0 is None or t1 is None:
        return 0.0
    ended = [end for end in (r["ts"] + r["dur_ms"] / 1e3 for r in steps) if end <= t1]
    return max(0.0, t0 - cell.window[0]) + (t1 - max(ended) if ended else 0.0)


def traced_lags_ms(obs):
    """:func:`done_lags_ms` of this run's trace, or ``None`` without one, without
    the waits or with another number of runs than of waits."""
    cell = obs.get("cell")
    if obs.get("trace") is None or cell is None:
        return None
    try:
        devices, hosts = trace.read_planes(trace.find_xplane(cell.trace_dir))
    except FileNotFoundError:
        return None
    waits = [
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in hosts for line in plane.lines for ev in line.events if ev.name == WAIT
    ]
    runs = step_runs(trace.line_events(devices[0], "XLA Modules")) if devices else []
    return done_lags_ms(waits, runs) or None
