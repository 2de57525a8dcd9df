"""Set-up as the program itself recorded it: the spans and counters of the
run's worker recorder, clipped by the harness's clock marks.

The program journals every span (``Telemetry.span``, and the compile
pipeline's stages, which ``jax.monitoring`` reports and the recorder writes as
``compile.trace`` / ``compile.lower`` / ``compile.backend``) with a start ``ts``
on ``time.time()``, a ``dur_ms``, its thread and the span that enclosed it
(``parent``), into ``<MAGGY_TPU_LOG_ROOT>/<app>/<run>/telemetry/worker_<n>.jsonl``,
flushed before ``lagom`` returns. ``Cell.marks`` are on the same clock, so
set-up is the records with ``marks["process"] <= ts < marks["window"]``.
Against a program that records none of these spans (``MARKERS``) every reader
returns ``None``, as ``spans.py`` does for the trace.

The reductions are plain functions over record lists
(``checks/test_setup_span_metrics.py``); ``load`` reads the files once a process.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict

STAGES = ("compile.trace", "compile.lower", "compile.backend")
# a program with any of these records set-up from the inside
MARKERS = STAGES + ("train.make_state",)
# the executors' spans around the whole of the user's function: they cover
# set-up and window alike and say nothing of what happened inside
WRAPPERS = ("train_fn", "trial")


# ------------------------------------------------------------------ reductions


def union_s(intervals) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps counted once."""
    total, hi = 0.0, None
    for s, e in sorted(intervals):
        if hi is None or s > hi:
            total, hi = total + (e - s), e
        elif e > hi:
            total, hi = total + (e - hi), e
    return total


class Setup:
    """One run's span records and counters with its marks: ``lo`` (process
    start), ``devices`` (the backend is up), ``hi`` (window start) and ``end``
    (window end)."""

    def __init__(self, records, marks, window_end):
        self.lo, self.devices, self.hi = marks["process"], marks["devices"], marks["window"]
        self.end = window_end
        self.spans = [r for r in records if r.get("kind") == "span"]
        self.counters = defaultdict(int)
        for r in records:
            if r.get("kind") == "snapshot":
                for name, n in r.get("counters", {}).items():
                    self.counters[name] += n
        self.recorded = any(r["name"] in MARKERS for r in self.spans)

    def between(self, lo, hi, names=None):
        """The spans that started in ``[lo, hi)`` (of ``names``, if given)."""
        return [r for r in self.spans if lo <= r["ts"] < hi and (names is None or r["name"] in names)]

    def before_window(self, names=None):
        return self.between(self.lo, self.hi, names)

    def covered_s(self, spans, lo, hi) -> float:
        """Seconds of ``[lo, hi)`` inside any of ``spans``, whatever the thread."""
        clipped = [(max(r["ts"], lo), min(r["ts"] + r["dur_ms"] / 1e3, hi)) for r in spans]
        return union_s([(s, e) for s, e in clipped if e > s])

    def per_thread_s(self, spans, lo, hi) -> float:
        """The same, thread by thread and summed: an inner jit's trace lies
        inside its caller's on one thread and counts once; two threads that
        compile at once both count."""
        by_tid = defaultdict(list)
        for r in spans:
            by_tid[r.get("tid")].append(r)
        return sum(self.covered_s(rs, lo, hi) for rs in by_tid.values())


def make_state_s(s: Setup):
    return sum(r["dur_ms"] for r in s.before_window(("train.make_state",))) / 1e3


def trace_lower_s(s: Setup):
    return s.per_thread_s(s.before_window(STAGES[:2]), s.lo, s.hi)


def backend_compile_s(s: Setup):
    return sum(r["dur_ms"] for r in s.before_window(STAGES[2:]) if r["attrs"]["cache"] != "hit") / 1e3


def cache_load_s(s: Setup):
    return sum(r["attrs"].get("cache_load_ms", 0.0) for r in s.before_window(STAGES[2:])) / 1e3


def cache_misses(s: Setup):
    """The run's ``compile.cache_misses`` less the misses its backend spans
    show from the window's start on."""
    late = [r for r in s.spans if r["name"] == "compile.backend" and r["ts"] >= s.hi and r["attrs"]["cache"] == "miss"]
    return s.counters["compile.cache_misses"] - len(late)


def first_step_run_s(s: Setup):
    """The first ``train_step`` and the ``train.drain why=compile`` that
    follows it, less the compile stages under them: dispatch and the device's
    first run of the step."""
    steps = sorted(s.before_window(("train_step",)), key=lambda r: r["ts"])
    if not steps:
        return None
    own = [steps[0]] + [
        r for r in s.before_window(("train.drain",))
        if r.get("attrs", {}).get("why") == "compile" and r["ts"] >= steps[0]["ts"]
    ][:1]
    lo, hi = own[0]["ts"], max(r["ts"] + r["dur_ms"] / 1e3 for r in own)
    stages = [r for r in s.between(lo, hi, STAGES) if r.get("parent") in ("train_step", "train.drain")]
    return sum(r["dur_ms"] for r in own) / 1e3 - s.per_thread_s(stages, lo, hi)


def harness_compile_s(s: Setup):
    """Compile stages under no span of the program's own: programs that the
    code of the train function called itself."""
    stages = [r for r in s.before_window(STAGES) if r.get("parent") is None or r["parent"] in WRAPPERS]
    return s.per_thread_s(stages, s.lo, s.hi)


def named_s(s: Setup):
    """Seconds between ``devices`` and the window under any span of the program."""
    return s.covered_s([r for r in s.spans if r["name"] not in WRAPPERS], s.devices, s.hi)


def unnamed_s(s: Setup):
    return (s.hi - s.devices) - named_s(s)


def compile_ms_in_window(s: Setup):
    return sum(r["dur_ms"] for r in s.between(s.hi, s.end, STAGES))


# ------------------------------------------------------------------- the files


def worker_records(root: str, since: float) -> list:
    """The records of every worker file under ``root`` written to since
    ``since`` (earlier runs of the checkout leave theirs beside this run's)."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "**", "telemetry", "worker_*.jsonl"), recursive=True)):
        if os.path.getmtime(path) < since:
            continue
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass  # a torn line
    return out


_LOADED = {}  # id(cell) -> (cell, its Setup or None): holding the cell keeps its id its own


def load(obs):
    """This run's ``Setup``, or ``None`` without the marks, the files or the spans."""
    cell = obs.get("cell")
    root = os.environ.get("MAGGY_TPU_LOG_ROOT")
    if cell is None or not root or "window" not in cell.marks or cell.window[1] is None:
        return None
    if id(cell) not in _LOADED:
        setup = Setup(worker_records(root, cell.marks["process"]), cell.marks, cell.window[1])
        _LOADED[id(cell)] = (cell, setup if setup.recorded else None)
    return _LOADED[id(cell)][1]


def read(obs, reduction):
    setup = load(obs)
    return None if setup is None else reduction(setup)
