"""Candidate system configurations and the tuned-winner record.

A :class:`Candidate` is one point in the system-configuration grid —
(mesh shape, global batch, microbatches, remat policy). The
static stage AOT-compiles each one; the measured stage races the survivors.
The winner is frozen into a :class:`TunedConfig`, the JSON-round-trippable
record the tuning cache stores and that builds a ready-to-``fit`` Trainer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Tuple

from maggy_tpu.parallel.spec import ShardingSpec


def resolve_spec(preset: Any, num_devices: int) -> ShardingSpec:
    """A preset name or ShardingSpec resolved against the live device count."""
    if isinstance(preset, ShardingSpec):
        if preset.num_devices == num_devices:
            return preset
        return preset.scaled_to(num_devices)
    return ShardingSpec.preset(str(preset), num_devices)


def apply_remat(model: Any, remat_policy: Optional[str]) -> Any:
    """Return ``model`` with the candidate's remat policy applied, when its
    config carries ``remat``/``remat_policy`` fields (the flagship Decoder
    family does); other models pass through unchanged — the knob is then a
    no-op, not an error, so generic flax models still tune over mesh/batch."""
    if remat_policy is None:
        return model
    cfg = getattr(model, "cfg", None)
    if cfg is None or not hasattr(cfg, "remat_policy"):
        return model
    new_cfg = dataclasses.replace(cfg, remat=True, remat_policy=remat_policy)
    return model.clone(cfg=new_cfg)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One system configuration under consideration."""

    preset: Any  # preset name (str) or ShardingSpec
    batch_size: int
    n_microbatches: Optional[int] = None
    remat_policy: Optional[str] = None

    @property
    def label(self) -> str:
        parts = [str(self.preset), f"bs{self.batch_size}"]
        if self.n_microbatches:
            parts.append(f"mb{self.n_microbatches}")
        if self.remat_policy:
            parts.append(f"remat:{self.remat_policy}")
        return "/".join(parts)

    def spec_for(self, num_devices: int) -> ShardingSpec:
        return resolve_spec(self.preset, num_devices)

    def to_dict(self) -> Dict[str, Any]:
        preset = (
            dataclasses.asdict(self.preset)
            if isinstance(self.preset, ShardingSpec)
            else self.preset
        )
        return {
            "preset": preset,
            "batch_size": self.batch_size,
            "n_microbatches": self.n_microbatches,
            "remat_policy": self.remat_policy,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Candidate":
        preset = d["preset"]
        if isinstance(preset, dict):
            preset = ShardingSpec(**preset)
        return cls(
            preset=preset,
            batch_size=int(d["batch_size"]),
            n_microbatches=d.get("n_microbatches"),
            remat_policy=d.get("remat_policy"),
        )


def enumerate_candidates(tune_cfg, num_devices: int) -> List[Candidate]:
    """The candidate grid, with obviously-infeasible combinations dropped
    before anything is compiled: batch not divisible by the mesh's
    data×fsdp extent, microbatch counts that don't divide the batch, the
    known-invalid pp×sp composition, and microbatch settings on meshes
    without a pipeline axis (collapsed to ``None`` to avoid duplicates)."""
    seen = set()
    out: List[Candidate] = []
    for preset in tune_cfg.presets:
        try:
            spec = resolve_spec(preset, num_devices)
        except ValueError:
            continue  # preset can't cover this device count
        if spec.pp > 1 and spec.sp > 1:
            continue  # Trainer rejects this composition outright
        dpf = spec.dp * spec.fsdp
        for bs in tune_cfg.batch_sizes:
            if bs % dpf:
                continue
            micro_opts: Iterable[Optional[int]] = (
                tune_cfg.microbatches if spec.pp > 1 else (None,)
            )
            for mb in micro_opts:
                if mb is not None and (bs % mb or (bs // mb) % dpf):
                    continue
                for remat in tune_cfg.remat_policies:
                    cand = Candidate(
                        preset=preset,
                        batch_size=int(bs),
                        n_microbatches=mb,
                        remat_policy=remat,
                    )
                    key = repr(cand.to_dict())
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(cand)
                    if len(out) >= tune_cfg.max_candidates:
                        return out
    return out


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """A tuning winner: everything needed to reproduce the chosen system
    configuration. ``Trainer.fit`` accepts it directly via :meth:`trainer`."""

    spec: ShardingSpec
    batch_size: int
    n_microbatches: Optional[int] = None
    remat_policy: Optional[str] = None
    source: str = "static"  # "static" | "measured" | "cache"
    steps_per_sec: Optional[float] = None
    step_time_ms: Optional[float] = None

    def mesh(self, devices: Optional[list] = None):
        from maggy_tpu.parallel.mesh import make_mesh

        import jax

        devs = devices if devices is not None else jax.devices()
        spec = (
            self.spec
            if self.spec.num_devices == len(devs)
            else self.spec.scaled_to(len(devs))
        )
        return make_mesh(spec, devs)

    def trainer(self, model: Any, optimizer: Any, devices: Optional[list] = None, **kw):
        """Build a ready Trainer on this config's mesh, with the remat policy
        applied to the model. The returned trainer's
        ``fit``/``step`` run the tuned configuration directly."""
        from maggy_tpu.train.trainer import Trainer

        return Trainer(
            apply_remat(model, self.remat_policy),
            optimizer,
            self.mesh(devices),
            n_microbatches=self.n_microbatches,
            **kw,
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "spec": dataclasses.asdict(self.spec),
            "batch_size": self.batch_size,
            "n_microbatches": self.n_microbatches,
            "remat_policy": self.remat_policy,
            "source": self.source,
            "steps_per_sec": self.steps_per_sec,
            "step_time_ms": self.step_time_ms,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TunedConfig":
        return cls(
            spec=ShardingSpec(**d["spec"]),
            batch_size=int(d["batch_size"]),
            n_microbatches=d.get("n_microbatches"),
            remat_policy=d.get("remat_policy"),
            source=d.get("source", "cache"),
            steps_per_sec=d.get("steps_per_sec"),
            step_time_ms=d.get("step_time_ms"),
        )

    @classmethod
    def from_candidate(cls, cand: Candidate, num_devices: int, **kw) -> "TunedConfig":
        return cls(
            spec=cand.spec_for(num_devices),
            batch_size=cand.batch_size,
            n_microbatches=cand.n_microbatches,
            remat_policy=cand.remat_policy,
            **kw,
        )
