"""Planner: diagnosis -> candidate config moves over the knob registry.

Second stage of the telemetry→config loop. A :class:`Move` names one knob
from the checked-in registry (:mod:`maggy_tpu.autopilot.knobs`) and a
target value; the Planner's playbook maps each bottleneck class to the
moves that historically relieve it, clamped into the knob's declared
bounds and filtered three ways:

* ``live_only`` keeps only ``safe_live`` knobs — what the online
  controller may touch mid-run. Startup-only recommendations (batch size,
  remat policy, flash tiles) still come back from :meth:`Planner.plan_all`
  and land in the decision cache for the next launch.
* a caller-supplied ``feasible(move)`` hook prunes moves the same way the
  startup tuner prunes candidates — :func:`aot_memory_check` adapts
  ``tune``'s AOT ``memory_analysis`` pruning for batch/remat moves, so an
  autopilot recommendation can never be one the static stage would reject.
* no-op moves (target equals current) are dropped.

Decisions persist in the tune cache keyed by a **workload fingerprint**
(:func:`workload_fingerprint` = model fingerprint × topology × bucketed
traffic shape), so a fleet of identical workers shares learned configs:
:class:`DecisionStore` is the read/write seam, and a fresh controller seeds
its knobs from whatever the fleet already committed for this workload.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Any, Callable, Dict, List, Optional

from maggy_tpu.autopilot.knobs import FLASH_TILE_CHOICES, KNOBS, Knob

# decision-cache records are versioned alongside the attribution schema
DECISION_SCHEMA = "maggy-tpu.autopilot-decisions.v1"


@dataclasses.dataclass(frozen=True)
class Move:
    """One planned config change: a registered knob and its target value."""

    knob: str
    value: Any
    reason: str = ""

    def __post_init__(self):
        if self.knob not in KNOBS:
            raise ValueError(
                f"move targets unregistered knob {self.knob!r} "
                f"(declare it in maggy_tpu/autopilot/knobs.py)"
            )

    @property
    def spec(self) -> Knob:
        return KNOBS[self.knob]

    def to_dict(self) -> Dict[str, Any]:
        return {"knob": self.knob, "value": self.value, "reason": self.reason}


# ------------------------------------------------------------ fingerprints


def bucket_pow2(v: float) -> int:
    """Smallest power of two >= v (1 for v <= 1): traffic features are
    bucketed so near-identical workloads share a fingerprint instead of
    fragmenting the fleet cache per exact batch/prompt length."""
    v = max(1, int(v))
    b = 1
    while b < v:
        b *= 2
    return b


def traffic_shape(kind: str, **features: Any) -> Dict[str, Any]:
    """Canonical traffic-shape dict: ``kind`` ("train"/"serve") plus
    numeric features bucketed to powers of two."""
    out: Dict[str, Any] = {"kind": str(kind)}
    for key in sorted(features):
        v = features[key]
        out[key] = bucket_pow2(v) if isinstance(v, (int, float)) else str(v)
    return out


def workload_fingerprint(
    model: Any, topology: Dict[str, Any], traffic: Dict[str, Any]
) -> str:
    """Stable id of (what runs, where it runs, what hits it): model
    fingerprint/config identity × device topology × bucketed traffic
    shape. This is the key the fleet shares learned configs under."""
    payload = json.dumps(
        {"model": model, "topology": topology, "traffic": traffic},
        sort_keys=True,
        default=str,
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


# -------------------------------------------------------------- feasibility


def aot_memory_check(
    model: Any,
    batch_fn: Callable[[int], Dict[str, Any]],
    *,
    optimizer: Any = None,
    budget_bytes: Optional[int] = None,
    devices: Optional[list] = None,
) -> Callable[[Move], bool]:
    """A ``feasible(move)`` hook backed by the startup tuner's AOT memory
    analysis: a ``train.batch_size``/``train.remat_policy`` move survives
    only if the candidate it implies compiles under the HBM budget —
    nothing executes. Non-memory moves pass through."""
    from maggy_tpu.tune import static as static_mod
    from maggy_tpu.tune.candidates import Candidate

    def feasible(move: Move) -> bool:
        if move.knob not in ("train.batch_size", "train.remat_policy"):
            return True
        if move.knob == "train.batch_size":
            bs, remat = int(move.value), None
        else:
            bs, remat = len(batch_fn(1)["tokens"]), move.value
            bs = max(1, bs)
        report = static_mod.analyze_candidate(
            model,
            Candidate(preset="dp", batch_size=bs, remat_policy=remat),
            batch_fn(bs),
            optimizer=optimizer,
            budget_bytes=budget_bytes,
            devices=devices,
        )
        return report.ok

    return feasible


# ----------------------------------------------------------------- planner


def _grow(knob: Knob, current: Any) -> Any:
    """Next value up for a numeric knob: double (min 2), clamped."""
    cur = int(current or 0)
    return knob.clamp(max(2, cur * 2))


def _shrink(knob: Knob, current: Any) -> Any:
    cur = int(current or 0)
    return knob.clamp(cur // 2)


class Planner:
    """Maps a :class:`Diagnosis` plus the target's current knob values to
    an ordered list of candidate :class:`Move`\\ s (best first)."""

    def __init__(self, feasible: Optional[Callable[[Move], bool]] = None):
        self.feasible = feasible

    # playbook: one method per (scope, bottleneck) worth acting on
    def _train_moves(self, diag, current) -> List[Move]:
        moves: List[Move] = []
        if diag.bottleneck == "input_bound":
            knob = KNOBS["train.prefetch_depth"]
            cur = current.get(knob.name)
            if cur is not None:
                moves.append(
                    Move(knob.name, _grow(knob, cur), diag.reason)
                )
        elif diag.bottleneck == "drain_bound":
            knob = KNOBS["train.metrics_window"]
            cur = current.get(knob.name)
            if cur is not None:
                moves.append(Move(knob.name, _grow(knob, cur), diag.reason))
        elif diag.bottleneck == "memory_bound":
            # ZeRO first (docs/distributed.md "Gradient overlap & ZeRO"):
            # sharding optimizer states over the data axis recovers
            # ~2x param bytes per device WITHOUT touching the batch —
            # shrink batch only when zero_stage is already raised (or the
            # caller doesn't report it)
            if current.get("train.zero_stage") == 0:
                moves.append(Move("train.zero_stage", 1, diag.reason))
            bs = current.get("train.batch_size")
            if bs and int(bs) > 1:
                moves.append(
                    Move(
                        "train.batch_size",
                        _shrink(KNOBS["train.batch_size"], bs),
                        diag.reason,
                    )
                )
            if current.get("train.remat_policy") is None:
                moves.append(
                    Move("train.remat_policy", "nothing", diag.reason)
                )
        elif diag.bottleneck == "compute_bound":
            # promoted tune_flash sweep: recommend the measured-best tiles
            # when none are pinned yet (offline; racing the full grid is
            # the startup tuner's job)
            if current.get("train.flash_bwd_block_q") is None:
                best = FLASH_TILE_CHOICES[2]  # 512: the round-2 sweep's winner (one v5e)
                moves.append(
                    Move("train.flash_bwd_block_q", best, diag.reason)
                )
                moves.append(
                    Move("train.flash_bwd_block_k", best, diag.reason)
                )
        return moves

    def _serve_moves(self, diag, current) -> List[Move]:
        moves: List[Move] = []
        if diag.bottleneck == "queue_bound":
            knob = KNOBS["serve.num_slots"]
            cur = current.get(knob.name)
            if cur is not None and _grow(knob, cur) != cur:
                moves.append(Move(knob.name, _grow(knob, cur), diag.reason))
            elif current.get("fleet.admission") == "queue":
                # slot geometry already at its bound: shed instead of
                # queueing past the SLO
                moves.append(Move("fleet.admission", "shed", diag.reason))
        elif diag.bottleneck == "drain_bound":
            if current.get("serve.async_decode") is False:
                moves.append(Move("serve.async_decode", True, diag.reason))
        elif diag.bottleneck == "memory_bound":
            # spill before preempt: growing the host-DRAM tier turns the
            # next preemption's re-prefill into a cheap swap-in without
            # giving up any HBM, so it leads the shrink ladder
            # (docs/serving.md "Host-DRAM page tier")
            tier = current.get("serve.tier_host_pages")
            if tier is not None and _grow(KNOBS["serve.tier_host_pages"], tier) != tier:
                moves.append(
                    Move(
                        "serve.tier_host_pages",
                        _grow(KNOBS["serve.tier_host_pages"], tier),
                        diag.reason,
                    )
                )
            # paged engines shrink the per-request page cap FIRST: it
            # bounds worst-case footprint without sacrificing concurrency;
            # cutting num_slots is the blunt fallback (docs/serving.md
            # "Paged KV cache")
            cap = current.get("serve.max_pages_per_req")
            if cap and int(cap) > 1:
                moves.append(
                    Move(
                        "serve.max_pages_per_req",
                        _shrink(KNOBS["serve.max_pages_per_req"], cap),
                        diag.reason,
                    )
                )
            cur = current.get("serve.num_slots")
            if cur and int(cur) > 1:
                moves.append(
                    Move(
                        "serve.num_slots",
                        _shrink(KNOBS["serve.num_slots"], cur),
                        diag.reason,
                    )
                )
        return moves

    def plan_all(self, diag, current: Dict[str, Any]) -> List[Move]:
        """Every candidate move for this diagnosis — live and startup-only
        alike — deduped, feasibility-filtered, no-ops dropped."""
        raw = (
            self._train_moves(diag, current)
            if diag.scope == "train"
            else self._serve_moves(diag, current)
        )
        out: List[Move] = []
        seen = set()
        for move in raw:
            if move.knob in seen:
                continue
            seen.add(move.knob)
            if current.get(move.knob) == move.value:
                continue  # no-op
            if not move.spec.valid(move.value):
                continue
            if self.feasible is not None and not self.feasible(move):
                continue
            out.append(move)
        return out

    def plan(
        self, diag, current: Dict[str, Any], live_only: bool = True
    ) -> List[Move]:
        moves = self.plan_all(diag, current)
        if live_only:
            moves = [m for m in moves if m.spec.safe_live]
        return moves


# ----------------------------------------------------------- decision cache


class DecisionStore:
    """Autopilot decisions in the persistent tune cache, keyed per
    workload fingerprint — the fleet-shared artifact: any worker running
    the same (model × topology × traffic shape) reads the knobs its peers
    already proved out, and commits its own wins back."""

    def __init__(self, env=None):
        from maggy_tpu.tune.cache import TuneCache

        self.cache = TuneCache(env)

    @staticmethod
    def key(workload: str) -> str:
        return f"autopilot-{workload}"

    def load(self, workload: str) -> Dict[str, Any]:
        """Committed knob values for this workload ({} when none). A
        record stamped with a different workload fingerprint (a clobber)
        reads as empty, never as someone else's config."""
        record = self.cache.get_record(self.key(workload))
        if not record or record.get("workload") != workload:
            return {}
        return dict(record.get("knobs") or {})

    def record(
        self,
        workload: str,
        move: Move,
        *,
        outcome: str,
        before: Optional[float] = None,
        after: Optional[float] = None,
    ) -> None:
        """Append one guarded decision; committed moves update the shared
        knob table, rollbacks only append to the history."""
        key = self.key(workload)
        record = self.cache.get_record(key)
        if not record or record.get("workload") != workload:
            record = {
                "schema": DECISION_SCHEMA,
                "workload": workload,
                "knobs": {},
                "history": [],
            }
        if outcome == "committed":
            record["knobs"][move.knob] = move.value
        history = record.setdefault("history", [])
        history.append(
            {
                "ts": time.time(),
                "move": move.to_dict(),
                "outcome": outcome,
                "guard_before": before,
                "guard_after": after,
            }
        )
        del history[:-50]  # bounded
        self.cache.put(key, record)
