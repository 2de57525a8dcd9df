"""Trace attribution: where time went, per request and per training step.

This is the ONE implementation behind both consumers of a run's merged
telemetry JSONL — the human report (``tools/analyze_trace.py``) and the
autopilot Diagnoser (:mod:`maggy_tpu.autopilot.diagnose`) — so the numbers
an operator reads and the numbers the continuous-tuning loop acts on can
never drift apart.

Input: an experiment dir (or its ``telemetry/`` subdir) holding the
per-worker ``*.jsonl`` files (rotated ``*.jsonl.N`` segments are read too,
oldest first). The request-scoped tracing layer (docs/observability.md)
stamps every lifecycle event with a trace id, so one request's milestones —
``req.accepted`` on the router, ``req.queued``/``req.admitted``/
``req.first_token``/``req.finished`` on whichever replica served it,
``req.requeued`` hops in between — line up on the shared wall clock no
matter which worker wrote them.

Attribution is gap-labeling: consecutive milestone pairs within one trace
name the segment between them (accepted→dispatched = ``route``,
queued→admitted = ``queue``, admitted→first_token = ``prefill``,
first_token→finished = ``decode``, ...; unknown pairs land in ``other``).
Segments therefore sum to the measured e2e by construction — the report's
job is to show *which* bucket ate the time.

Per-step attribution reads the training gauges: ``step_time_ms`` (a step's
time on the device: the duration of its ``train.step_device`` span, which
``Trainer.fit``'s ``fit-steps`` thread measures from the later of the previous
step's end and the step's dispatch to the moment its output was ready: not the
dispatch of the jitted call, which returns in a few ms whatever the step
takes), ``input_wait_ms`` (blocked on the input pipeline), and
``metrics_drain_ms`` (lagged broadcast reads), with the remainder reported as
compute.

:func:`analyze` returns the machine-readable result (the exact object
``tools/analyze_trace.py --json`` prints); its layout is versioned under
``schema`` = :data:`SCHEMA` and treated as a stable contract:

* ``requests``: one row per trace — ``trace``, ``rid``, ``state``,
  ``start_ts``, ``e2e_ms``, ``hops``, ``components`` ({bucket: ms}).
* ``request_summary``: ``requests``, ``requeue_hops``, ``e2e_ms_mean``,
  ``components_ms_mean``, ``components_share``.
* ``step_summary``: ``steps``, ``step_ms_mean``, ``input_wait_ms_mean``,
  ``metrics_drain_ms_mean``, ``compute_ms_est``.

Keep this module stdlib-only: the CLI tool loads it without jax.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

# bump ONLY with an additive change note in docs/observability.md; external
# tooling keys on this.
# v2 (additive): rows carry the capacity attrs stamped on the lifecycle
# events — ``pages_held_peak`` (req.finished) and ``headroom_at_admit``
# (req.admitted / req.prefix_admitted). v1 JSONL without those attrs still
# reads fine: the fields are simply None.
SCHEMA = "maggy-tpu.trace-attribution.v2"

# (previous milestone, this milestone) -> attribution bucket; gaps between
# consecutive lifecycle events not named here land in "other"
GAP_LABELS: Dict[Tuple[str, str], str] = {
    ("req.accepted", "req.dispatched"): "route",
    ("req.requeued", "req.dispatched"): "route",
    ("req.accepted", "req.shed"): "route",
    ("req.dispatched", "req.queued"): "transit",
    ("req.accepted", "req.queued"): "transit",
    ("req.queued", "req.admitted"): "queue",
    ("req.queued", "req.prefix_admitted"): "queue",
    ("req.admitted", "req.first_token"): "prefill",
    ("req.prefix_admitted", "req.first_token"): "prefill",
    ("req.first_token", "req.finished"): "decode",
    ("req.finished", "req.completed"): "completion",
    ("req.queued", "req.requeued"): "lost",
    ("req.admitted", "req.requeued"): "lost",
    ("req.prefix_admitted", "req.requeued"): "lost",
    ("req.first_token", "req.requeued"): "lost",
    ("req.dispatched", "req.requeued"): "lost",
    ("req.finished", "req.requeued"): "lost",
    ("req.queued", "req.finished"): "queue",  # expired/cancelled in queue
}

COMPONENT_ORDER = (
    "route",
    "transit",
    "queue",
    "prefill",
    "decode",
    "lost",
    "completion",
    "other",
)

TERMINALS = ("req.completed", "req.finished", "req.shed")

# the per-step gauges the step attribution aggregates
STEP_GAUGES = ("step_time_ms", "input_wait_ms", "metrics_drain_ms")


def iter_jsonl_files(tdir: str) -> List[str]:
    """All JSONL files under ``tdir``, rotated segments ordered oldest
    first within each stem (``x.jsonl.3`` before ``x.jsonl.1`` before
    ``x.jsonl``)."""
    entries = []
    for path in glob.glob(os.path.join(tdir, "*.jsonl*")):
        base = os.path.basename(path)
        stem, _, suffix = base.partition(".jsonl")
        if suffix and not suffix[1:].isdigit():
            continue  # not a rotation segment (e.g. .jsonl.tmp)
        seg = int(suffix[1:]) if suffix else 0
        entries.append((stem, -seg, path))
    return [path for _, _, path in sorted(entries)]


def load_records(tdir: str) -> List[Dict[str, Any]]:
    import json

    records: List[Dict[str, Any]] = []
    for path in iter_jsonl_files(tdir):
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        records.append(json.loads(line))
                    except ValueError:
                        continue  # torn tail from a crashed worker
        except OSError:
            continue
    return records


# --------------------------------------------------------------- per request


def attribute_requests(records: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One attribution row per trace that carries request lifecycle events:
    ``{trace, rid, state, e2e_ms, components: {bucket: ms}}``. Components
    sum to e2e_ms by construction (every inter-milestone gap is labeled)."""
    by_trace: Dict[str, List[Dict[str, Any]]] = {}
    for rec in records:
        if rec.get("kind") != "event" or not rec.get("trace"):
            continue
        if not str(rec.get("name", "")).startswith("req."):
            continue
        by_trace.setdefault(rec["trace"], []).append(rec)
    out = []
    for trace, events in sorted(by_trace.items()):
        events.sort(key=lambda e: float(e.get("ts", 0.0)))
        # cut the timeline at the last terminal milestone: late duplicate
        # polls after completion must not stretch the request
        end_idx = max(
            (i for i, e in enumerate(events) if e.get("name") in TERMINALS),
            default=len(events) - 1,
        )
        events = events[: end_idx + 1]
        components: Dict[str, float] = {}
        for prev, cur in zip(events, events[1:]):
            gap_ms = (float(cur["ts"]) - float(prev["ts"])) * 1e3
            label = GAP_LABELS.get((prev["name"], cur["name"]), "other")
            components[label] = components.get(label, 0.0) + max(0.0, gap_ms)
        attrs = {}
        for e in events:
            attrs.update(e.get("attrs") or {})
        out.append(
            {
                "trace": trace,
                "rid": attrs.get("rid"),
                "state": attrs.get("state", "?"),
                "start_ts": float(events[0]["ts"]),
                "e2e_ms": (float(events[-1]["ts"]) - float(events[0]["ts"])) * 1e3,
                "hops": sum(1 for e in events if e["name"] == "req.requeued"),
                "components": components,
                # schema v2 capacity fields (None on v1 JSONL)
                "pages_held_peak": attrs.get("pages_held_peak"),
                "headroom_at_admit": attrs.get("headroom_at_admit"),
            }
        )
    return out


def summarize_requests(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    if not rows:
        return {"requests": 0}
    total = {k: 0.0 for k in COMPONENT_ORDER}
    for row in rows:
        for k, v in row["components"].items():
            total[k] = total.get(k, 0.0) + v
    e2e_sum = sum(r["e2e_ms"] for r in rows)
    return {
        "requests": len(rows),
        "requeue_hops": sum(r["hops"] for r in rows),
        "e2e_ms_mean": e2e_sum / len(rows),
        "components_ms_mean": {
            k: v / len(rows) for k, v in total.items() if v > 0
        },
        "components_share": {
            k: v / e2e_sum for k, v in total.items() if v > 0 and e2e_sum > 0
        },
    }


# ------------------------------------------------------------------ per step


def attribute_steps(records: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Training-loop attribution from the per-step gauges: where a mean
    step's wall clock went (input wait, metrics drain, compute residual)."""
    series: Dict[str, List[float]] = {}
    for rec in records:
        if rec.get("kind") != "gauge":
            continue
        name = rec.get("name")
        if name in STEP_GAUGES:
            try:
                series.setdefault(name, []).append(float(rec.get("value", 0.0)))
            except (TypeError, ValueError):
                continue

    def mean(name: str) -> Optional[float]:
        vals = series.get(name)
        return sum(vals) / len(vals) if vals else None

    step = mean("step_time_ms")
    wait = mean("input_wait_ms") or 0.0
    drain = mean("metrics_drain_ms") or 0.0
    out: Dict[str, Any] = {
        "steps": len(series.get("step_time_ms", [])),
        "step_ms_mean": step,
        "input_wait_ms_mean": mean("input_wait_ms"),
        "metrics_drain_ms_mean": mean("metrics_drain_ms"),
    }
    if step is not None:
        out["compute_ms_est"] = max(0.0, step - wait - drain)
    return out


# -------------------------------------------------------------------- entry


def analyze(path: str) -> Dict[str, Any]:
    """Full attribution for an experiment dir (or its ``telemetry/``
    subdir). The returned dict IS the ``--json`` output — see the module
    docstring for the schema contract."""
    tdir = path
    sub = os.path.join(path, "telemetry")
    if os.path.isdir(sub):
        tdir = sub
    records = load_records(tdir)
    rows = attribute_requests(records)
    return {
        "schema": SCHEMA,
        "telemetry_dir": tdir,
        "requests": rows,
        "request_summary": summarize_requests(rows),
        "step_summary": attribute_steps(records),
    }
