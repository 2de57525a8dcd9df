"""AOT-guided system-configuration autotuner.

Maggy's core trick is the oblivious training function — the same ``train_fn``
runs as a local run, an HPO trial, or a distributed rank. This package
applies that idea to the *system* axis: mesh shape, global batch size,
microbatch count and remat policy are searched like
hyperparameters, in two stages:

**Stage 1 — static (no execution).** Every candidate's train step is
AOT-compiled (``jit → lower → compile``) against abstract arguments and
interrogated: ``memory_analysis()`` prunes configurations whose per-device
estimate exceeds the HBM budget *before anything runs*, and
``cost_analysis()`` provides a flops/bytes ranking. Works identically on the
CPU tier-1 mesh.

**Stage 2 — measured.** The survivors race through the *existing* HPO driver
with the stock ASHA optimizer — candidate index as a CATEGORICAL
searchspace, the trial fn a thin wrapper over ``Trainer.fit`` — so the tuner
adds zero distributed machinery.

Winners persist in a tuning cache on the env seam (local or ``gs://``
identically), keyed by (model fingerprint, topology, dtype, search grid);
the serve CLI consults it before falling back to defaults.

    from maggy_tpu.tune import tune, TuneConfig
    result = tune(Decoder(cfg), TuneConfig(presets=("dp", "fsdp", "2d")))
    trainer = result.best.trainer(Decoder(cfg), optax.adamw(1e-3))
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

from maggy_tpu.config.tune import TuneConfig
from maggy_tpu.tune import static as static_mod
from maggy_tpu.tune.cache import (
    TuneCache,
    alias_cache_key,
    alias_workload,
    cache_key,
    model_fingerprint,
    topology_key,
)
from maggy_tpu.tune.candidates import Candidate, TunedConfig, enumerate_candidates
from maggy_tpu.tune.static import StaticReport, static_stage

__all__ = [
    "TuneConfig",
    "TuneResult",
    "TunedConfig",
    "Candidate",
    "StaticReport",
    "tune",
]


@dataclasses.dataclass
class TuneResult:
    """Outcome of one :func:`tune` invocation."""

    best: TunedConfig
    key: str
    cache_hit: bool = False
    candidates: int = 0
    pruned_oom: int = 0
    pruned_infeasible: int = 0
    compiled: int = 0
    measured: Optional[Dict[str, Any]] = None
    reports: List[StaticReport] = dataclasses.field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "best": self.best.to_dict(),
            "key": self.key,
            "cache_hit": self.cache_hit,
            "candidates": self.candidates,
            "pruned_oom": self.pruned_oom,
            "pruned_infeasible": self.pruned_infeasible,
            "compiled": self.compiled,
            "measured": self.measured,
            "reports": [r.to_dict() for r in self.reports],
        }


def default_batch_fn(model: Any, seq_len: int) -> Callable[[int], Dict[str, Any]]:
    """Synthetic LM batches for models with a ``cfg.vocab_size`` (the
    flagship Decoder family). Other models must pass an explicit
    ``batch_fn(batch_size) -> batch`` matching their input contract."""
    import numpy as np

    vocab = getattr(getattr(model, "cfg", None), "vocab_size", None)
    if vocab is None:
        raise ValueError(
            "model has no cfg.vocab_size; pass batch_fn=... to tune() "
            "(a callable batch_size -> batch dict)"
        )
    rng = np.random.default_rng(0)

    def batch_fn(batch_size: int) -> Dict[str, Any]:
        return {
            "tokens": rng.integers(
                0, vocab, size=(batch_size, seq_len), dtype=np.int32
            )
        }

    return batch_fn


def tune(
    model: Any,
    config: Optional[TuneConfig] = None,
    *,
    optimizer: Any = None,
    loss_fn: Optional[Callable] = None,
    batch_fn: Optional[Callable[[int], Dict[str, Any]]] = None,
    env=None,
    devices: Optional[list] = None,
) -> TuneResult:
    """Find the best system configuration for training ``model``.

    Consults the persistent tuning cache first (a hit returns immediately —
    no compiles); otherwise runs the static AOT stage over the candidate
    grid, prunes on memory, races the survivors through the HPO driver with
    ASHA (unless ``config.measure`` is off, in which case the static
    flops/bytes ranking decides), persists the winner, and returns a
    :class:`TuneResult` whose ``best.trainer(model, optax_tx)`` is ready for
    ``fit``. Runs one lagom experiment internally, so it cannot be called
    from inside a running experiment's train_fn.
    """
    import jax
    import optax

    from maggy_tpu import telemetry

    cfg = config or TuneConfig()
    tel = telemetry.get()
    devs = devices if devices is not None else jax.devices()
    tx = optimizer if optimizer is not None else optax.adamw(cfg.learning_rate)
    get_batch = batch_fn or default_batch_fn(model, cfg.seq_len)

    fingerprint = model_fingerprint(model, get_batch(min(cfg.batch_sizes)))
    dtype = str(getattr(getattr(model, "cfg", None), "dtype", "na"))
    key = cache_key(fingerprint, topology_key(devs), dtype, cfg.grid_fingerprint())
    cache = TuneCache(env)

    if cfg.cache:
        record = cache.get(key)
        if record is not None:
            best = TunedConfig.from_dict(record["best"])
            if best.step_time_ms is not None:
                tel.gauge("tune.best_step_time", best.step_time_ms)
            tel.count("tune.cache_hits")
            return TuneResult(
                best=best,
                key=key,
                cache_hit=True,
                candidates=int(record.get("candidates", 0)),
                pruned_oom=int(record.get("pruned_oom", 0)),
                pruned_infeasible=int(record.get("pruned_infeasible", 0)),
                compiled=0,
                measured=record.get("measured"),
            )

    candidates = enumerate_candidates(cfg, len(devs))
    if not candidates:
        raise ValueError(
            f"TuneConfig enumerates no feasible candidates for "
            f"{len(devs)} devices (presets={cfg.presets!r}, "
            f"batch_sizes={cfg.batch_sizes!r})"
        )
    budget = (
        cfg.hbm_budget_bytes
        if cfg.hbm_budget_bytes is not None
        else static_mod.device_memory_budget()
    )

    compiled_before = static_mod.COMPILE_COUNT
    with tel.span("tune.static", candidates=len(candidates)):
        reports = static_stage(
            model,
            candidates,
            get_batch,
            optimizer=tx,
            loss_fn=loss_fn,
            budget_bytes=budget,
            devices=devs,
        )
    compiled = static_mod.COMPILE_COUNT - compiled_before
    survivors = [r.candidate for r in reports if r.ok]
    pruned_oom = sum(1 for r in reports if r.status == "oom")
    pruned_infeasible = sum(1 for r in reports if r.status == "infeasible")
    tel.gauge("tune.candidates", len(candidates))
    tel.gauge("tune.pruned_oom", pruned_oom)
    if not survivors:
        raise RuntimeError(
            f"all {len(candidates)} candidates pruned "
            f"({pruned_oom} over the {budget} B budget, "
            f"{pruned_infeasible} infeasible) — widen the grid or the budget"
        )

    measured_summary = None
    if cfg.measure and len(survivors) > 1:
        from maggy_tpu.tune.measure import measured_stage

        with tel.span("tune.measure", survivors=len(survivors)):
            best_idx, measured_summary = measured_stage(
                model,
                survivors,
                get_batch,
                cfg,
                make_optimizer=lambda: tx,
                loss_fn=loss_fn,
                devices=devs,
            )
        best_cand = survivors[best_idx]
        sps = measured_summary.get("best_steps_per_sec") or 0.0
        best = TunedConfig.from_candidate(
            best_cand,
            len(devs),
            source="measured",
            steps_per_sec=sps or None,
            step_time_ms=(1e3 / sps) if sps else None,
        )
    else:
        ok_reports = [r for r in reports if r.ok]
        ok_reports.sort(key=lambda r: r.cost_per_token(cfg.seq_len))
        best = TunedConfig.from_candidate(
            ok_reports[0].candidate, len(devs), source="static"
        )

    if best.step_time_ms is not None:
        tel.gauge("tune.best_step_time", best.step_time_ms)
    result = TuneResult(
        best=best,
        key=key,
        cache_hit=False,
        candidates=len(candidates),
        pruned_oom=pruned_oom,
        pruned_infeasible=pruned_infeasible,
        compiled=compiled,
        measured=measured_summary,
        reports=reports,
    )
    if cfg.cache:
        tel.count("tune.cache_misses")
        record = {
            "best": best.to_dict(),
            "key": key,
            "candidates": len(candidates),
            "pruned_oom": pruned_oom,
            "pruned_infeasible": pruned_infeasible,
            "measured": measured_summary,
            "reports": [r.to_dict() for r in reports],
            "created": time.time(),
        }
        cache.put(key, record)
        # grid-independent "latest winner" alias for consumers that never
        # tuned themselves (serve --mesh auto) — scoped per workload
        # fingerprint: the record is stamped so a read for a different
        # (model, topology, dtype) can never resolve to this winner
        topo = topology_key(devs)
        cache.put(
            alias_cache_key(fingerprint, topo, dtype),
            {**record, "workload": alias_workload(fingerprint, topo, dtype)},
        )
    return result


def cached_best(
    model: Any,
    config: Optional[TuneConfig] = None,
    *,
    batch_fn: Optional[Callable[[int], Dict[str, Any]]] = None,
    env=None,
    devices: Optional[list] = None,
) -> Optional[TunedConfig]:
    """Cache-only lookup: the tuned winner for this model on this topology,
    if one was ever persisted, else None. Never compiles, never executes —
    the cheap probe the serve CLI uses before falling back to defaults.
    With ``config`` the lookup is bound to that exact search grid; without
    it the grid-independent "latest winner" alias is consulted."""
    import jax

    devs = devices if devices is not None else jax.devices()
    seq_len = config.seq_len if config is not None else 16
    get_batch = batch_fn or default_batch_fn(model, seq_len)
    fingerprint = model_fingerprint(model, get_batch(1))
    dtype = str(getattr(getattr(model, "cfg", None), "dtype", "na"))
    topo = topology_key(devs)
    if config is not None:
        record = TuneCache(env).get(
            cache_key(fingerprint, topo, dtype, config.grid_fingerprint())
        )
    else:
        # workload-verified alias read: a clobbered/foreign record is a
        # miss, never another workload's winner
        record = TuneCache(env).get_alias(
            alias_cache_key(fingerprint, topo, dtype),
            alias_workload(fingerprint, topo, dtype),
        )
    return TunedConfig.from_dict(record["best"]) if record else None
