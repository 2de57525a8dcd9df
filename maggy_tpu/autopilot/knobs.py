"""Checked-in autopilot knob registry.

Every configuration knob the Planner (:mod:`maggy_tpu.autopilot.plan`) may
move must be declared here with a type, bounds and a ``safe_live`` flag —
``tools/check_knob_registry.py`` (wired into tier-1, mirroring the
telemetry-name lint) fails on any knob reference in ``maggy_tpu/`` that is
missing from this table, and on any registry entry whose declaration is
structurally incomplete. The failure mode this kills: the controller
"re-tunes" a knob nothing applies (a typo'd name silently becomes a no-op
move that still burns a guard window), or live-applies a knob that is only
safe at startup.

``safe_live`` semantics (docs/autotune.md "Rollback semantics"): a
safe-live knob can be changed on a RUNNING job — either instantly
(prefetch depth, metrics window, admission policy) or via the
drain-and-reconfigure seam between serving waves (slot geometry). Knobs
with ``safe_live=False`` are *startup* knobs: the Planner may still
recommend them (recorded into the workload-fingerprint decision cache for
the next launch, AOT-feasibility-checked through ``tune``'s memory
analysis) but the online controller never applies them mid-run.

Keep this module import-light (stdlib only): the lint loads it by file
path without importing the package.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

SCOPES = ("train", "serve", "fleet")
KINDS = ("int", "float", "bool", "choice")

# flash-attention tile candidates, promoted from the manual
# tools/tune_flash.py sweep grid — the sweep tool and the Planner's
# compute-bound recommendations now draw from this one table
FLASH_TILE_CHOICES = (128, 256, 512, 1024)

# remat policy names mirrored from models/transformer.py REMAT_POLICIES
# (kept literal here so the registry stays stdlib-importable)
REMAT_POLICY_CHOICES = (None, "nothing", "dots")

# paged KV cache page sizes (tokens): powers of two that divide every
# supported max_seq_len; the engine snaps incompatible values down
PAGE_SIZE_CHOICES = (8, 16, 32, 64, 128)

# gradient-reduction bucket sizes in MiB (parallel/overlap.py): powers of
# two spanning tiny test models up to production param trees; None =
# unbucketed (one collective per dtype)
BUCKET_MB_CHOICES = (None, 1, 4, 16, 32, 64, 128, 256)

# ZeRO optimizer-state sharding stages supported by the trainer (0 = dense
# replicated states, 1 = states sharded over the data axis)
ZERO_STAGE_CHOICES = (0, 1)


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable: identity, type, bounds, and liveness contract."""

    name: str  # "<scope>.<knob>", e.g. "train.prefetch_depth"
    kind: str  # "int" | "float" | "bool" | "choice"
    scope: str  # "train" | "serve" | "fleet"
    safe_live: bool  # applicable to a running job (see module docstring)
    description: str
    lo: Optional[float] = None  # int/float bounds, inclusive
    hi: Optional[float] = None
    choices: Optional[Tuple[Any, ...]] = None  # for kind == "choice"

    def clamp(self, value: Any) -> Any:
        """``value`` coerced into this knob's domain (bounds/choices)."""
        if self.kind == "int":
            return int(min(self.hi, max(self.lo, int(value))))
        if self.kind == "float":
            return float(min(self.hi, max(self.lo, float(value))))
        if self.kind == "bool":
            return bool(value)
        return value if value in self.choices else self.choices[0]

    def valid(self, value: Any) -> bool:
        if self.kind == "int":
            return isinstance(value, int) and self.lo <= value <= self.hi
        if self.kind == "float":
            return (
                isinstance(value, (int, float)) and self.lo <= value <= self.hi
            )
        if self.kind == "bool":
            return isinstance(value, bool)
        return value in self.choices


KNOBS = {
    k.name: k
    for k in (
        # ---- training loop (applied inside Trainer.fit)
        Knob(
            "train.prefetch_depth", "int", "train", True,
            "DevicePrefetcher lookahead; raised when input-bound",
            lo=1, hi=16,
        ),
        Knob(
            "train.metrics_window", "int", "train", True,
            "lagged metrics drain window; raised when drain-bound",
            lo=0, hi=8,
        ),
        Knob(
            "train.batch_size", "int", "train", False,
            "global batch size (startup-only; AOT memory-checked)",
            lo=1, hi=65536,
        ),
        Knob(
            "train.remat_policy", "choice", "train", False,
            "activation remat policy (startup-only)",
            choices=REMAT_POLICY_CHOICES,
        ),
        Knob(
            "train.zero_stage", "choice", "train", False,
            "ZeRO optimizer-state sharding stage (startup-only: changes the "
            "optax state layout; memory-bound playbook raises it before "
            "shrinking batch)",
            choices=ZERO_STAGE_CHOICES,
        ),
        Knob(
            "train.bucket_mb", "choice", "train", False,
            "gradient-reduction bucket size in MiB (startup-only: None = "
            "unbucketed; smaller buckets overlap more comm with backward)",
            choices=BUCKET_MB_CHOICES,
        ),
        Knob(
            "train.flash_bwd_block_q", "choice", "train", False,
            "flash-attention backward q tile (tools/tune_flash.py grid)",
            choices=FLASH_TILE_CHOICES,
        ),
        Knob(
            "train.flash_bwd_block_k", "choice", "train", False,
            "flash-attention backward k tile (tools/tune_flash.py grid)",
            choices=FLASH_TILE_CHOICES,
        ),
        # ---- serving engine/scheduler (applied by the Scheduler)
        Knob(
            "serve.num_slots", "int", "serve", True,
            "decode slot count; drain-and-reconfigure between waves",
            lo=1, hi=256,
        ),
        Knob(
            "serve.max_queue", "int", "serve", True,
            "scheduler admission queue bound",
            lo=1, hi=65536,
        ),
        Knob(
            "serve.async_decode", "bool", "serve", True,
            "async decode double buffer (flushed before flipping)",
        ),
        Knob(
            "serve.prefix_min", "int", "serve", True,
            "minimum shared-prefix length for KV reuse",
            lo=1, hi=65536,
        ),
        Knob(
            "serve.page_size", "choice", "serve", False,
            "paged KV cache page size in tokens (startup-only: the page "
            "pool layout is baked into the compiled decode program)",
            choices=PAGE_SIZE_CHOICES,
        ),
        Knob(
            "serve.max_pages_per_req", "int", "serve", True,
            "cap on KV pages one request may hold; shrunk FIRST when "
            "memory-bound (before sacrificing num_slots concurrency)",
            lo=1, hi=65536,
        ),
        Knob(
            "serve.tier_host_pages", "int", "serve", True,
            "host-DRAM KV tier capacity in pages; grown when memory-bound "
            "so spill replaces preemption re-prefill (0 disables spills)",
            lo=0, hi=1_048_576,
        ),
        Knob(
            "serve.tier_low_water_pct", "float", "serve", True,
            "HBM headroom fraction below which the scheduler spills the "
            "coldest stream to the host tier each metrics tick",
            lo=0.0, hi=0.9,
        ),
        # ---- fleet router (applied by the Router)
        Knob(
            "fleet.admission", "choice", "fleet", True,
            "over-SLO behavior: park in router queue or shed BUSY",
            choices=("queue", "shed"),
        ),
        Knob(
            "fleet.slo_ttft_ms", "float", "fleet", True,
            "TTFT budget driving projected-TTFT admission",
            lo=1.0, hi=600_000.0,
        ),
        Knob(
            "fleet.affinity_weight", "float", "fleet", True,
            "prefix-affinity bonus in ms subtracted from projected TTFT "
            "for replicas holding the prompt's prefix resident (0 = "
            "affinity-blind routing; brownout level >= 2 zeroes it)",
            lo=0.0, hi=10_000.0,
        ),
        # ---- fleet autoscaler (docs/fleet.md "Autoscaling")
        Knob(
            "fleet.min_replicas", "int", "fleet", True,
            "autoscaler floor: scale-in never drains below this count",
            lo=1, hi=64,
        ),
        Knob(
            "fleet.max_replicas", "int", "fleet", True,
            "autoscaler ceiling: scale-out pressure past it raises the "
            "fleet.at_capacity gauge instead of spawning",
            lo=1, hi=64,
        ),
        Knob(
            "fleet.scale_cooldown_s", "float", "fleet", True,
            "minimum seconds between scale events (flap prevention: a "
            "burst's edge must not thrash the fleet)",
            lo=0.0, hi=3600.0,
        ),
        Knob(
            "fleet.target_util", "float", "fleet", True,
            "fleet slot-utilization ceiling the autoscaler holds: "
            "sustained util above it scales out, scale-in only when the "
            "survivors would stay below it",
            lo=0.05, hi=0.95,
        ),
    )
}


def validate_registry(knobs=None):
    """Structural check of the registry itself (run by the lint): every
    entry has a coherent kind/bounds/choices declaration, a scope-prefixed
    name, and an explicit safe-live flag. Returns a list of error strings."""
    errors = []
    for name, knob in (knobs if knobs is not None else KNOBS).items():
        where = f"knob {name!r}"
        if name != knob.name:
            errors.append(f"{where}: registered under a different key")
        if knob.scope not in SCOPES:
            errors.append(f"{where}: unknown scope {knob.scope!r}")
        elif not name.startswith(knob.scope + "."):
            errors.append(f"{where}: name must be prefixed '{knob.scope}.'")
        if knob.kind not in KINDS:
            errors.append(f"{where}: unknown kind {knob.kind!r}")
        if knob.kind in ("int", "float"):
            if knob.lo is None or knob.hi is None or knob.lo > knob.hi:
                errors.append(f"{where}: {knob.kind} knob needs lo <= hi bounds")
        if knob.kind == "choice" and not knob.choices:
            errors.append(f"{where}: choice knob needs a non-empty choices tuple")
        if not isinstance(knob.safe_live, bool):
            errors.append(f"{where}: safe_live must be an explicit bool")
        if not knob.description:
            errors.append(f"{where}: description required")
    return errors
