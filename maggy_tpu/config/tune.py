"""Autotuner configuration.

``TuneConfig`` declares the system-configuration search the autotuner
(:mod:`maggy_tpu.tune`) explores: candidate mesh shapes (``ShardingSpec``
presets or instances), global batch sizes, microbatch counts and remat
policies — plus the two-stage budget controls: the static stage's
HBM budget for AOT pruning and the measured stage's ASHA step schedule.

This is deliberately NOT a :class:`~maggy_tpu.config.base.LagomConfig`: the
autotuner is not an experiment kind of its own — its measured stage *builds*
a ``HyperparameterOptConfig`` over the surviving candidates and runs it
through the ordinary HPO driver, so system tuning reuses the exact trial
machinery hyperparameter tuning does.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union


class TuneConfig:
    """Search space + budgets for :func:`maggy_tpu.tune.tune`.

    :param presets: candidate mesh shapes — preset names (``"dp"``,
        ``"fsdp"``, ``"2d"``, ...) or :class:`ShardingSpec` instances
        (rescaled to the live device count via ``scaled_to``).
    :param batch_sizes: candidate *global* batch sizes.
    :param microbatches: candidate ``Trainer.n_microbatches`` values. Only
        meaningful for presets with a pipeline (``pp``) axis; ``None`` keeps
        the trainer default. Non-pp candidates collapse to ``None``.
    :param remat_policies: candidate remat policies by name (see
        ``maggy_tpu.models.transformer.REMAT_POLICIES``). ``None`` leaves the
        model exactly as configured; a name forces ``remat=True`` with that
        policy (only for models whose config carries those fields).
    :param seq_len: sequence length of the synthetic tuning batches.
    :param hbm_budget_bytes: per-device memory budget for the static stage's
        AOT prune. ``None`` asks the device (``memory_stats()["bytes_limit"]``
        where available — TPU/GPU); if the backend reports nothing (CPU),
        no candidate is memory-pruned.
    :param measure: run the measured stage (short trials through the HPO
        driver + ASHA). ``False`` picks the winner from the static
        flops/bytes ranking alone (the cheap mode).
    :param steps_per_unit: train steps per unit of ASHA budget; a trial at
        rung budget ``b`` runs ``b * steps_per_unit`` measured steps.
    :param asha_reduction_factor / asha_resource_min / asha_resource_max:
        the ASHA schedule over those step budgets.
    :param num_measure_trials: base-rung trial count for the measured stage;
        defaults to the number of static-stage survivors.
    :param cache: consult/persist the tuning cache on the env seam
        (``<root>/tune_cache/`` — local or ``gs://`` identically).
    :param max_candidates: hard cap on the enumerated candidate grid.
    :param learning_rate: optimizer LR for the tuning trials (adamw).
    """

    def __init__(
        self,
        presets: Sequence[Union[str, Any]] = ("dp", "fsdp", "2d"),
        batch_sizes: Sequence[int] = (8, 16, 32),
        microbatches: Sequence[Optional[int]] = (None,),
        remat_policies: Sequence[Optional[str]] = (None,),
        seq_len: int = 128,
        hbm_budget_bytes: Optional[int] = None,
        measure: bool = True,
        steps_per_unit: int = 4,
        asha_reduction_factor: int = 2,
        asha_resource_min: float = 1,
        asha_resource_max: float = 4,
        num_measure_trials: Optional[int] = None,
        cache: bool = True,
        max_candidates: int = 64,
        learning_rate: float = 1e-3,
        name: str = "autotune",
        seed: Optional[int] = 0,
    ):
        if not presets:
            raise ValueError("TuneConfig needs at least one mesh preset")
        if not batch_sizes or any(int(b) < 1 for b in batch_sizes):
            raise ValueError("batch_sizes must be positive ints")
        if seq_len < 2:
            raise ValueError("seq_len must be >= 2 (LM loss needs a target)")
        if steps_per_unit < 1:
            raise ValueError("steps_per_unit must be >= 1")
        if max_candidates < 1:
            raise ValueError("max_candidates must be >= 1")
        self.presets = tuple(presets)
        self.batch_sizes = tuple(int(b) for b in batch_sizes)
        self.microbatches = tuple(microbatches)
        self.remat_policies = tuple(remat_policies)
        self.seq_len = int(seq_len)
        self.hbm_budget_bytes = (
            None if hbm_budget_bytes is None else int(hbm_budget_bytes)
        )
        self.measure = bool(measure)
        self.steps_per_unit = int(steps_per_unit)
        self.asha_reduction_factor = int(asha_reduction_factor)
        self.asha_resource_min = asha_resource_min
        self.asha_resource_max = asha_resource_max
        self.num_measure_trials = num_measure_trials
        self.cache = bool(cache)
        self.max_candidates = int(max_candidates)
        self.learning_rate = float(learning_rate)
        self.name = name
        self.seed = seed

    def grid_fingerprint(self) -> dict:
        """The search-grid identity folded into the cache key: a cached
        winner is only valid for the grid it was chosen from."""
        def spec_key(p):
            return p if isinstance(p, str) else repr(p)

        return {
            "presets": [spec_key(p) for p in self.presets],
            "batch_sizes": list(self.batch_sizes),
            "microbatches": list(self.microbatches),
            "remat_policies": list(self.remat_policies),
            "seq_len": self.seq_len,
            "measure": self.measure,
        }
