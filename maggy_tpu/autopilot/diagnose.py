"""Bottleneck diagnoser: telemetry window -> classified verdict + evidence.

The first stage of the telemetry→config loop (docs/autotune.md "Continuous
tuning"). Input is whatever the run already emits — aggregated gauge
windows from a live controller, a :func:`maggy_tpu.telemetry.attribution.
analyze` result (the SAME code path ``tools/analyze_trace.py`` renders),
or a raw merged-JSONL record list — and output is a :class:`Diagnosis`:
one dominant bottleneck per window plus an evidence struct naming exactly
the metrics (and the derived shares) behind the verdict, so every
``autopilot.diagnosis`` telemetry event is auditable after the fact.

Verdicts (per scope, in precedence order — the first matching rule wins):

* ``train``: ``memory_bound`` (HBM headroom below the floor) →
  ``input_bound`` (input-pipeline wait dominates the step wall) →
  ``drain_bound`` (lagged-broadcast host reads dominate) →
  ``compute_bound`` (the device is the bottleneck — the healthy state).
* ``serve``: ``memory_bound`` → ``queue_bound`` (slots saturated with a
  backlog at least one wave deep — admission/capacity limited) →
  ``drain_bound`` (host token-drain time dominates per-token decode) →
  ``idle`` (nothing queued or running) → ``compute_bound``.

Thresholds are explicit :class:`Thresholds` fields, not magic numbers, so
tests and operators can reason about (and tighten) the classifier.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Optional

BOTTLENECKS = (
    "input_bound",
    "compute_bound",
    "drain_bound",
    "queue_bound",
    "memory_bound",
    "idle",
)


@dataclasses.dataclass(frozen=True)
class Thresholds:
    """Classifier knobs: what 'dominates' means, per rule."""

    input_share: float = 0.25  # input wait / step wall
    drain_share: float = 0.20  # metrics drain / step wall (train)
    serve_drain_share: float = 0.25  # drain ms / per-token time (serve)
    queue_waves: float = 1.0  # backlog depth in units of num_slots
    slot_utilization: float = 0.85  # active/num_slots to call "saturated"
    min_headroom: float = 0.05  # HBM headroom fraction floor


@dataclasses.dataclass(frozen=True)
class Diagnosis:
    """One window's verdict. ``evidence`` holds the raw metric values the
    rule read; ``shares`` the derived fractions it compared; ``reason`` a
    one-line human account. All JSON-safe by construction."""

    bottleneck: str
    scope: str  # "train" | "serve"
    evidence: Dict[str, float]
    shares: Dict[str, float]
    reason: str

    def to_dict(self) -> Dict[str, Any]:
        return {
            "bottleneck": self.bottleneck,
            "scope": self.scope,
            "evidence": dict(self.evidence),
            "shares": {k: round(v, 4) for k, v in self.shares.items()},
            "reason": self.reason,
        }


def _f(window: Dict[str, Any], key: str, default: float = 0.0) -> float:
    v = window.get(key)
    try:
        return default if v is None else float(v)
    except (TypeError, ValueError):
        return default


# ------------------------------------------------------------------- train


def diagnose_train(
    window: Dict[str, Any], thresholds: Optional[Thresholds] = None
) -> Diagnosis:
    """Classify a training window. Expected keys (means over the window):
    ``step_time_ms``, ``input_wait_ms``, ``metrics_drain_ms``, optionally
    ``memory_headroom_frac`` — exactly the gauges ``Trainer.fit`` emits and
    ``attribution.attribute_steps`` aggregates."""
    th = thresholds or Thresholds()
    step = _f(window, "step_time_ms")
    wait = _f(window, "input_wait_ms")
    drain = _f(window, "metrics_drain_ms")
    headroom = window.get("memory_headroom_frac")
    evidence = {
        "step_time_ms": round(step, 3),
        "input_wait_ms": round(wait, 3),
        "metrics_drain_ms": round(drain, 3),
    }
    if headroom is not None:
        evidence["memory_headroom_frac"] = round(float(headroom), 4)
    shares = {}
    if step > 0:
        shares["input"] = wait / step
        shares["drain"] = drain / step
        shares["compute"] = max(0.0, 1.0 - shares["input"] - shares["drain"])

    if headroom is not None and float(headroom) < th.min_headroom:
        return Diagnosis(
            "memory_bound", "train", evidence, shares,
            f"HBM headroom {float(headroom):.1%} below the "
            f"{th.min_headroom:.0%} floor",
        )
    if step <= 0:
        return Diagnosis(
            "idle", "train", evidence, shares, "no measured steps in window"
        )
    if shares["input"] >= th.input_share and shares["input"] >= shares["drain"]:
        return Diagnosis(
            "input_bound", "train", evidence, shares,
            f"input_wait_ms is {shares['input']:.0%} of step_time_ms "
            f"(threshold {th.input_share:.0%})",
        )
    if shares["drain"] >= th.drain_share:
        return Diagnosis(
            "drain_bound", "train", evidence, shares,
            f"metrics_drain_ms is {shares['drain']:.0%} of step_time_ms "
            f"(threshold {th.drain_share:.0%})",
        )
    return Diagnosis(
        "compute_bound", "train", evidence, shares,
        f"device compute holds {shares['compute']:.0%} of the step wall",
    )


# ------------------------------------------------------------------- serve


def diagnose_serve(
    window: Dict[str, Any], thresholds: Optional[Thresholds] = None
) -> Diagnosis:
    """Classify a serving window from ``Scheduler.stats()``-shaped metrics
    (queue_depth, active_slots, num_slots, tpot_ms_p50, ...) plus the
    engine's ``drain_ms`` and an optional ``memory_headroom_frac``."""
    th = thresholds or Thresholds()
    queue = _f(window, "queue_depth")
    active = _f(window, "active_slots")
    slots = max(1.0, _f(window, "num_slots", 1.0))
    tpot = _f(window, "tpot_ms_p50")
    drain = _f(window, "drain_ms")
    headroom = window.get("memory_headroom_frac")
    evidence = {
        "queue_depth": round(queue, 2),
        "active_slots": round(active, 2),
        "num_slots": slots,
        "tpot_ms_p50": round(tpot, 3),
        "drain_ms": round(drain, 3),
    }
    shares = {
        "queue_waves": queue / slots,
        "slot_utilization": active / slots,
        "drain": (drain / tpot) if tpot > 0 else 0.0,
    }
    if headroom is not None:
        evidence["memory_headroom_frac"] = round(float(headroom), 4)
        if float(headroom) < th.min_headroom:
            return Diagnosis(
                "memory_bound", "serve", evidence, shares,
                f"HBM headroom {float(headroom):.1%} below the "
                f"{th.min_headroom:.0%} floor",
            )
    if (
        shares["queue_waves"] >= th.queue_waves
        and shares["slot_utilization"] >= th.slot_utilization
    ):
        return Diagnosis(
            "queue_bound", "serve", evidence, shares,
            f"backlog {queue:.0f} >= {th.queue_waves:.0%} of {slots:.0f} "
            f"slots with {shares['slot_utilization']:.0%} occupancy",
        )
    if shares["drain"] >= th.serve_drain_share:
        return Diagnosis(
            "drain_bound", "serve", evidence, shares,
            f"host drain is {shares['drain']:.0%} of per-token time "
            f"(threshold {th.serve_drain_share:.0%})",
        )
    if active == 0 and queue == 0:
        return Diagnosis(
            "idle", "serve", evidence, shares, "no queued or active requests"
        )
    return Diagnosis(
        "compute_bound", "serve", evidence, shares,
        "device decode holds the per-token time",
    )


# --------------------------------------------- attribution-backed diagnosis


def diagnose_steps(
    step_summary: Dict[str, Any], thresholds: Optional[Thresholds] = None
) -> Diagnosis:
    """Training diagnosis straight from an ``attribution.analyze`` result's
    ``step_summary`` — the offline twin of the live window path, reading
    the exact numbers ``tools/analyze_trace.py`` prints."""
    return diagnose_train(
        {
            "step_time_ms": step_summary.get("step_ms_mean"),
            "input_wait_ms": step_summary.get("input_wait_ms_mean"),
            "metrics_drain_ms": step_summary.get("metrics_drain_ms_mean"),
        },
        thresholds,
    )


def diagnose_requests(
    request_summary: Dict[str, Any], thresholds: Optional[Thresholds] = None
) -> Diagnosis:
    """Serving diagnosis from an ``attribution.analyze`` result's
    ``request_summary``: the component *shares* (queue/prefill/decode/...)
    name the dominant per-request cost directly."""
    th = thresholds or Thresholds()
    shares = dict(request_summary.get("components_share") or {})
    evidence = {
        k: round(v, 3)
        for k, v in (request_summary.get("components_ms_mean") or {}).items()
    }
    evidence["requests"] = request_summary.get("requests", 0)
    if not shares:
        return Diagnosis(
            "idle", "serve", evidence, shares, "no attributed requests"
        )
    queue_share = shares.get("queue", 0.0) + shares.get("route", 0.0)
    if queue_share >= max(th.queue_waves * 0.25, 0.25):
        return Diagnosis(
            "queue_bound", "serve", evidence, shares,
            f"queue+route hold {queue_share:.0%} of mean request e2e",
        )
    return Diagnosis(
        "compute_bound", "serve", evidence, shares,
        "prefill/decode dominate mean request e2e",
    )


def diagnose_records(
    records: Iterable[Dict[str, Any]],
    scope: str = "train",
    thresholds: Optional[Thresholds] = None,
) -> Diagnosis:
    """Diagnose directly from raw merged-JSONL records (the sink format),
    routing through the shared attribution module."""
    from maggy_tpu.telemetry import attribution

    if scope == "serve":
        rows = attribution.attribute_requests(records)
        return diagnose_requests(attribution.summarize_requests(rows), thresholds)
    return diagnose_steps(attribution.attribute_steps(records), thresholds)
