"""Sharded trainer: the TPU-native distributed-training engine.

This replaces the reference's entire L1/L2 distributed-training machinery — DDP
/ FairScale / DeepSpeed wrapping (core/patching/modules.py:38-139), the 11 ZeRO
optimizer monkey-patches (core/patching/optim.py:28-117) and the NCCL bootstrap
(core/executors/torch_dist_executor.py:121-285) — with one functional pipeline:

    mesh = make_mesh(spec)                  # ShardingSpec: dp/fsdp/tp/sp/ep
    trainer = Trainer(model, optax.adamw(...), mesh)
    state  = trainer.make_state(rng, sample_batch)   # params born sharded
    state, metrics = trainer.step(state, batch)      # pjit'd, donated, bf16

Parameter/optimizer-state sharding (ZeRO-1/2/3 ≈ fsdp axis) is purely a
placement decision: optax state mirrors the param tree, so the same logical
axis rules shard both, and XLA inserts the all-gathers/reduce-scatters that
DeepSpeed implements by hand. There is nothing to monkey-patch — distribution
transparency comes from what we inject (a mesh-aware context), not from
patching engine classes (SURVEY.md §7 design stance).
"""

from __future__ import annotations

import dataclasses
import math
import os
import queue
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from maggy_tpu.models import head, sown
from maggy_tpu.parallel import sharding as shd
from maggy_tpu.parallel.spec import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_SLICE,
    AXIS_STAGE,
    AXIS_TENSOR,
    ShardingSpec,
)


class TrainState(train_state.TrainState):
    """flax TrainState; params may carry nn.Partitioned boxes (flax unboxes on
    apply, optax maps through them), so sharding metadata survives the whole
    update loop."""


def _lm_loss_parts(
    logits: jax.Array, batch: Dict[str, jax.Array], ahead: int = 1
) -> Tuple[jax.Array, jax.Array]:
    """``(masked log-likelihood sum, mask weight)`` for the LM objective —
    the sufficient statistics :func:`lm_loss_fn` normalizes. Split out so the
    bucketed-overlap step can psum the two parts across batch shards and
    reproduce the dense masked mean exactly (sum-of-sums / sum-of-weights),
    instead of averaging per-shard means whose denominators differ.
    ``ahead``: position ``i``'s logits predict token ``i + ahead`` (2 for a
    multi-token-prediction head); segments are runs, so a target in position
    ``i``'s segment has every token between them in it too."""
    tokens = batch["tokens"]
    targets = tokens[:, ahead:]
    logits = logits[:, :-ahead]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = head.kept_targets(batch, ahead)
    if mask is None:
        return ll.sum(), jnp.float32(ll.size)
    return (ll * mask).sum(), mask.sum()


def lm_loss_fn(
    logits: jax.Array, batch: Dict[str, jax.Array], ahead: int = 1
) -> jax.Array:
    """Next-token cross entropy over ``batch["tokens"]`` with optional
    ``batch["loss_mask"]``. With ``batch["segment_ids"]`` (packed sequences)
    the boundary positions — where the target token belongs to a different
    segment than its predictor — are masked out automatically."""
    ll_sum, weight = _lm_loss_parts(logits, batch, ahead)
    return -ll_sum / jnp.maximum(weight, 1.0)


def target_weighted_loss(logits: jax.Array, batch: Dict[str, jax.Array], weights: jax.Array) -> jax.Array:
    """The objective of a model that weighs its own targets
    (``sown.target_weights``): position ``i``'s logits predict token ``i``
    itself, ``-(sum_i w_i log softmax(logits_i)[x_i]) / N`` with ``N`` the
    batch's real tokens (``loss_mask``, else the segment ids above 0, else
    every position), float32. Weights on masked-out positions do not count."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    real = head.real_tokens(batch)
    if real is None:
        return -(ll * weights).sum() / jnp.float32(ll.size)
    real = real.astype(jnp.float32)
    return -(ll * weights * real).sum() / jnp.maximum(real.sum(), 1.0)


def model_loss(loss_fn: Callable, logits: jax.Array, mods, batch: Dict[str, jax.Array]) -> jax.Array:
    """The data loss of a dense step: the trainer's ``loss_fn``, or, where the
    model sowed its targets' weights, its own objective."""
    weights = sown.target_weights(mods)
    return loss_fn(logits, batch) if weights is None else target_weighted_loss(logits, batch, weights)


def classification_loss_fn(logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def mtp_loss(mods, batch: Dict[str, jax.Array]) -> Optional[jax.Array]:
    """The loss of a model's further heads (``sown.mtp_logits``): cross entropy
    of the token two ahead, counted where it lies in the predictor's document.
    Several heads (head ``i`` predicts the token ``i + 2`` ahead) give the mean
    of their losses, each over the positions whose target lies in the
    predictor's document. ``None`` for a model with one head."""
    logits = sown.mtp_logits(mods)
    if logits is None:
        return None
    if logits.ndim == 4:
        heads = logits.shape[2]
        return sum(lm_loss_fn(logits[:, :, i], batch, ahead=i + 2) for i in range(heads)) / heads
    return lm_loss_fn(logits, batch, ahead=2)


def data_losses(loss_fn: Callable, out: jax.Array, mods, batch, in_head: bool) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``(data loss, further heads' loss or None)`` of a dense step from what
    the model returned: its heads' losses where the head ran inside the loss
    (``models/head.py``: the first is the main head's, the mean of the others
    what :func:`mtp_loss` gives), else logits, from which the losses are taken
    here."""
    if in_head:
        return out[0], (out[1:].mean() if out.shape[0] > 1 else None)
    return model_loss(loss_fn, out, mods, batch), mtp_loss(mods, batch)


def _prefetch_depth(prefetch: Optional[int]) -> int:
    """Resolve an input-prefetch depth: an explicit argument wins, else the
    ``MAGGY_TPU_PREFETCH`` env knob, else 2 (double-buffered). 0 disables."""
    if prefetch is not None:
        return max(0, int(prefetch))
    try:
        return max(0, int(os.environ.get("MAGGY_TPU_PREFETCH", "2")))
    except ValueError:
        return 2


def _model_inputs(batch: Dict[str, jax.Array]) -> Tuple:
    if "tokens" in batch:
        args = [batch["tokens"]]
        # packed sequences: optional positions (restarting per segment) and
        # segment_ids ride through to the model's extra positional args
        if "positions" in batch or "segment_ids" in batch:
            args.append(batch.get("positions"))
            if "segment_ids" in batch:
                args.append(batch["segment_ids"])
        return tuple(args)
    if "inputs" in batch:
        return (batch["inputs"],)
    raise KeyError("Batch must contain 'tokens' (LM) or 'inputs' (generic)")


class _FitAutopilotTarget:
    """In-loop knob holder for ``fit``'s autopilot controller (push-mode
    target: the loop feeds per-step samples, and safe-live moves land on
    the live prefetcher / metrics window immediately)."""

    scope = "train"
    guard_metric = "steps_per_sec"

    def __init__(self, prefetcher, metrics_window: int, trainer=None):
        self.prefetcher = prefetcher
        self.metrics_window = int(metrics_window)
        self.trainer = trainer

    def sample(self):  # push-mode: the loop observes directly
        return {}

    def pending(self) -> bool:
        return False

    def current(self):
        cur = {"train.metrics_window": self.metrics_window}
        if self.prefetcher is not None:
            cur["train.prefetch_depth"] = self.prefetcher.depth
        if self.trainer is not None:
            # startup knobs: the planner proposes them for the NEXT run
            # (memory-bound playbook raises zero_stage before shrinking
            # batch); apply() rightly has no live handler for them
            cur["train.zero_stage"] = int(self.trainer.zero_stage)
            if self.trainer.bucket_mb is not None:
                cur["train.bucket_mb"] = float(self.trainer.bucket_mb)
        return cur

    def apply(self, knob, value) -> bool:
        if knob == "train.prefetch_depth" and self.prefetcher is not None:
            self.prefetcher.set_depth(int(value))
            return True
        if knob == "train.metrics_window":
            self.metrics_window = max(0, int(value))
            return True
        return False


class _StepWatcher:
    """What ``fit``'s loop cannot see without draining the pipeline: each
    step's end. One daemon thread (``fit-steps``) a ``fit`` call takes the
    steps in the order the loop dispatched them, waits for each one's output
    inside the live span ``train.step_wait``, brings it to the host (a dozen
    scalars) and journals ``train.step_device`` from the later of the previous
    step's end and this one's dispatch to its end, with the step's loss and
    every counter a row of ``sown.COUNTERS`` names as attributes, and the
    gauges from the same values. All state the two threads share rides the
    queue; the loop thread's part is one ``put`` a step."""

    def __init__(self, tel, trace, step0: int):
        self._tel, self._trace, self._step0 = tel, trace, step0
        self._gauges = {key: name for row in sown.COUNTERS for key, name in row.gauges.items()}
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._closed = False
        self._thread = threading.Thread(target=self._run, name="fit-steps", daemon=True)
        self._thread.start()

    def put(self, i: int, start: float, compiled: bool, tokens: int, metrics) -> None:
        """Step ``i`` of the call, dispatched at ``start`` on ``time.time()``."""
        self._queue.put((i, start, compiled, tokens, metrics))

    def close(self) -> None:
        """Wait until every step handed over is journaled, then end the
        thread. Idempotent."""
        if not self._closed:
            self._closed = True
            self._queue.put(None)  # no step follows
        self._thread.join()

    def _run(self) -> None:
        from maggy_tpu.telemetry import tracing as _tracing

        tel = self._tel
        _tracing.set_current(self._trace)
        prev_end = 0.0
        while True:
            item = self._queue.get()
            if item is None:
                return
            i, start, compiled, tokens, metrics = item
            try:
                with tel.span("train.step_wait", step=i):
                    jax.block_until_ready(metrics)
                    end = time.time()
                values = {k: float(v) for k, v in jax.device_get(metrics).items()}
            except Exception:  # noqa: BLE001 — a failed step is the loop thread's to raise, when it reads the same output
                continue
            start = max(prev_end, start)
            prev_end = end
            counters = {k: v for k, v in values.items() if k in self._gauges}
            attrs = {k: values[k] for k in ("loss", "mtp_loss") if k in values}
            tel.record_span(
                "train.step_device", start, end, step=i, global_step=self._step0 + i,
                compiled=compiled, tokens=tokens, **attrs, **counters,
            )
            if compiled:
                tel.gauge("compile_time_ms", (end - start) * 1e3)
            else:
                tel.gauge("step_time_ms", (end - start) * 1e3)
            for key, value in counters.items():
                tel.gauge(self._gauges[key], value)


@dataclasses.dataclass
class Trainer:
    """Builds sharded state + compiled train/eval steps for a flax model."""

    model: Any
    optimizer: optax.GradientTransformation
    mesh: Any
    loss_fn: Callable = lm_loss_fn
    rules: Tuple = shd.DEFAULT_RULES
    # pipeline parallelism: microbatches per step when the mesh has a stage
    # axis > 1 (defaults to 2*pp — enough to amortize the 1F1B bubble while
    # staying valid for small test batches); must divide the batch size
    n_microbatches: Optional[int] = None
    # elastic membership (docs/resilience.md): a MembershipMonitor injected
    # by the distributed executor. fit polls it at step boundaries — a
    # pending epoch (or a chaos slice_drop/slice_rejoin) interrupts the loop
    # with a membership exception the executor's reshape loop catches
    membership: Optional[Any] = None
    # device-side comm/compute overlap (docs/distributed.md "Gradient
    # overlap & ZeRO"): zero_stage=1 shards optimizer state over the data
    # axis (each rank updates its shard, then all-gathers params);
    # bucket_mb bounds the gradient-reduction bucket size in MiB so
    # per-bucket collectives overlap the remaining backward. Defaults keep
    # the dense step bit-for-bit. Only pure data/slice meshes are eligible —
    # anything else warns once and stays dense (see _overlap_mode)
    zero_stage: int = 0
    bucket_mb: Optional[float] = None

    def __post_init__(self):
        self._train_step = None
        # trace-time compile counter: the jitted step bodies bump this as a
        # Python side effect, so it counts XLA traces, not calls (the same
        # contract as Engine._decode_traces). _expect_recompile marks a
        # deliberate (re)build so the recompile sentinel stays quiet for it.
        self._step_traces = 0
        self._expect_recompile = False
        self._eval_step = None
        self._eval_loss_step = None
        self.state_shardings = None
        self._pp_parts = None
        self._pp_built_micro = None
        # (shape key, shardings) memo so the per-step hot path never
        # recomputes the batch sharding tree — the spec plumbing runs once
        self._batch_shardings_memo = None
        self._overlap_memo = None  # resolved (mode, manual axes, zero shards)
        if self.zero_stage not in (0, 1):
            raise ValueError(
                f"Trainer.zero_stage must be 0 or 1, got {self.zero_stage!r}"
            )
        if self.bucket_mb is not None and not float(self.bucket_mb) > 0:
            raise ValueError(
                f"Trainer.bucket_mb must be positive (or None), got "
                f"{self.bucket_mb!r}"
            )

    # ---------------------------------------------------------------- pipeline

    @property
    def pp(self) -> int:
        """Pipeline stages = the mesh's ``stage`` axis extent (1 = off)."""
        return dict(self.mesh.shape).get(AXIS_STAGE, 1)

    def _pipeline_parts(self):
        if self._pp_parts is None:
            from maggy_tpu.train.pipeline_adapter import decoder_pipeline_parts

            shape = dict(self.mesh.shape)
            if shape.get(AXIS_SEQ, 1) > 1:
                raise ValueError(
                    "pp>1 does not compose with sp>1: the 1F1B schedule runs "
                    "each stage op under a lax.cond whose predicate varies "
                    "per stage, and a seq-ring collective inside a "
                    "non-uniform cond deadlocks (verified on the CPU mesh). "
                    "Use pp x tp / pp x ep / pp x dp/fsdp, or sp without pp."
                )
            # pp composes with dp/fsdp (manual in the pipeline shard_maps)
            # and with tp/ep: tensor/expert dims of the stage params stay
            # GSPMD-managed, resolved from the model's own logical axes in
            # state_shardings_for
            self._pp_parts = decoder_pipeline_parts(
                self.model, self.pp, tp=shape.get(AXIS_TENSOR, 1),
                mesh=self.mesh, ep=shape.get(AXIS_EXPERT, 1),
            )
        return self._pp_parts

    # ---------------------------------------------------------------- overlap

    def _bucket_mb_eff(self) -> Optional[float]:
        """bucket_mb normalized: None/inf (one bucket per dtype) -> None."""
        if self.bucket_mb is None or not math.isfinite(float(self.bucket_mb)):
            return None
        return float(self.bucket_mb)

    def _overlap_mode(self) -> Tuple[str, Tuple[str, ...], int]:
        """Resolve (once per trainer) which step the config gets:
        ``("off"|"bucket"|"zero", manual batch axes, zero shard count)``.

        ``zero_stage``/``bucket_mb`` request the bucketed-overlap step
        (parallel/overlap.py), which runs the model under a manual
        shard_map over (slice, data). Ineligible configurations — pipeline
        meshes, meshes with non-trivial GSPMD-auto axes (this XLA's SPMD
        partitioner aborts on manual subgroups mixed with auto param
        sharding; under fsdp the optimizer state is sharded by the rule
        table already), or no batch axis to reduce over — warn once and
        fall back to the dense path, so a knob sweep never hard-fails on
        geometry."""
        if self._overlap_memo is not None:
            return self._overlap_memo
        off = ("off", (), 1)
        requested = self.zero_stage > 0 or self._bucket_mb_eff() is not None
        if not requested:
            self._overlap_memo = off
            return off
        from maggy_tpu.train.pipeline_adapter import warn_overlap_unbucketed

        shape = dict(self.mesh.shape)
        manual = tuple(
            a for a in (AXIS_SLICE, AXIS_DATA) if shape.get(a, 1) > 1
        )
        blockers = sorted(
            a
            for a in (AXIS_FSDP, AXIS_TENSOR, AXIS_SEQ, AXIS_EXPERT)
            if shape.get(a, 1) > 1
        )
        mode = off
        if self.pp > 1:
            warn_overlap_unbucketed(
                f"pipeline mesh (stage={self.pp}): per-stage bucketing is "
                "not implemented, the 1F1B schedule keeps its own collectives"
            )
        elif blockers:
            warn_overlap_unbucketed(
                f"mesh axes {blockers} are GSPMD-auto; the overlap step "
                "needs a pure data/slice mesh (fsdp already shards "
                "optimizer state by the rule table)"
            )
        elif not manual:
            warn_overlap_unbucketed("no data/slice mesh axis > 1 to reduce over")
        else:
            dz = shape.get(AXIS_DATA, 1) if self.zero_stage > 0 else 1
            if self.zero_stage > 0 and dz == 1:
                warnings.warn(
                    "zero_stage=1 needs a data-axis extent > 1; optimizer "
                    "states stay replicated (effective zero_stage=0)",
                    stacklevel=3,
                )
                dz = 1
            mode = ("zero" if dz > 1 else "bucket", manual, dz)
        self._overlap_memo = mode
        return mode

    def _build_overlap_train_step(
        self, mode: str, manual: Tuple[str, ...], dz: int
    ):
        """The bucketed-collective train step (docs/distributed.md "Gradient
        overlap & ZeRO").

        The whole step runs under a *manual* shard_map over the batch axes,
        so the gradient reduction is spelled per bucket, per mesh axis —
        intra-slice ``data`` (ICI) first, cross-slice ``slice`` (DCN)
        second — in reverse-topological bucket order. Each bucket's
        collective depends only on its own grads, which is what lets XLA's
        latency-hiding scheduler start it while the rest of backward runs
        (``overlap.latency_hiding_flags`` on real TPU backends). Under
        ``mode="zero"`` the data-axis reduction is a reduce-scatter, the
        optimizer update touches only the local shard (the optax state IS
        the flat shard layout — see ``_init_fn``), and an all-gather
        rebuilds the params; optimizer memory per device drops ~1/dz.
        """
        from jax.sharding import PartitionSpec as P

        from maggy_tpu import telemetry
        from maggy_tpu.parallel import overlap
        from jax import shard_map as _shard_map

        assert mode in ("bucket", "zero") and (mode != "zero" or dz > 1)
        mesh_shape = dict(self.mesh.shape)
        n_manual = 1
        for a in manual:
            n_manual *= mesh_shape[a]
        is_lm = self.loss_fn is lm_loss_fn
        bucket_mb = self._bucket_mb_eff()
        tel = telemetry.get()

        def local_objective(params, batch):
            # per-device objective chosen so psum over the manual axes
            # reproduces the dense objective exactly: LM losses contribute
            # sum/weight parts (global masked mean), generic losses the
            # mean-of-shards (exact for uniform means), aux terms the
            # mean-of-shards (router losses are per-token means)
            logits, mods = self.model.apply(
                {"params": params}, *_model_inputs(batch),
                mutable=["intermediates"],
            )
            if sown.mtp_logits(mods) is not None:
                raise NotImplementedError(
                    "the bucketed/ZeRO overlap step has no multi-token-"
                    "prediction loss: train this model with overlap off"
                )
            if sown.target_weights(mods) is not None:
                raise NotImplementedError(
                    "the bucketed/ZeRO overlap step has no objective that weighs "
                    "its own targets (block diffusion: a noise key a step, no next-"
                    "token shift): train this model with overlap off"
                )
            aux_dev = sown.collect_aux_losses(mods) / n_manual
            with jax.named_scope("loss"):
                if is_lm:
                    ll_sum, weight = _lm_loss_parts(logits, batch)
                    w_global = jax.lax.psum(weight, manual)
                    data_dev = -ll_sum / jnp.maximum(w_global, 1.0)
                else:
                    data_dev = self.loss_fn(logits, batch) / n_manual
            return data_dev + aux_dev, (data_dev, aux_dev)

        def reduce_bucket(vec, scatter: bool):
            # ICI before DCN: the fast intra-slice hop issues first so the
            # slow cross-slice all-reduce overlaps it (and later buckets'
            # backward) independently
            with jax.named_scope("grad_sync"):
                if AXIS_DATA in manual:
                    if scatter:
                        vec = jax.lax.psum_scatter(vec, AXIS_DATA, tiled=True)
                    else:
                        vec = jax.lax.psum(vec, AXIS_DATA)
                if AXIS_SLICE in manual:
                    vec = jax.lax.psum(vec, AXIS_SLICE)
            return vec

        def train_step(state: TrainState, batch):
            self._step_traces += 1  # trace-time: counts compiles, not calls
            # plan from traced shapes: static at trace time, rebuilt free on
            # recompile, never stored host-side
            plan = overlap.plan_buckets(state.params, bucket_mb, pad_to=dz)
            tel.gauge("train.bucket_count", len(plan.buckets))

            def body_bucket(params, batch):
                (_, (data_dev, aux_dev)), grads = jax.value_and_grad(
                    local_objective, has_aux=True
                )(params, batch)
                flats = overlap.flatten_buckets(grads, plan)
                flats = {
                    name: reduce_bucket(vec, scatter=False)
                    for name, vec in flats.items()
                }
                grads = overlap.unflatten_buckets(flats, plan, grads)
                loss = jax.lax.psum(data_dev, manual)
                aux = jax.lax.psum(aux_dev, manual)
                return grads, (loss, aux)

            def body_zero(params, opt_state, batch):
                (_, (data_dev, aux_dev)), grads = jax.value_and_grad(
                    local_objective, has_aux=True
                )(params, batch)
                gflats = overlap.flatten_buckets(grads, plan)
                gshards = {
                    name: reduce_bucket(vec, scatter=True)
                    for name, vec in gflats.items()
                }
                # each rank owns one 1/dz shard of every flat bucket; the
                # optimizer update below runs on shards only
                idx = jax.lax.axis_index(AXIS_DATA)
                pflats = overlap.flatten_buckets(params, plan)
                pshards = {
                    name: jax.lax.dynamic_slice_in_dim(
                        vec, idx * (vec.shape[0] // dz), vec.shape[0] // dz
                    )
                    for name, vec in pflats.items()
                }
                with jax.named_scope("optimizer"):
                    updates, new_opt = self.optimizer.update(
                        gshards, opt_state, pshards
                    )
                    new_shards = optax.apply_updates(pshards, updates)
                with jax.named_scope("grad_sync"):
                    new_flats = {
                        name: jax.lax.all_gather(v, AXIS_DATA, tiled=True)
                        for name, v in new_shards.items()
                    }
                new_params = overlap.unflatten_buckets(new_flats, plan, params)
                # shards partition the full (slice-reduced) gradient over
                # data, so the global sq-norm is the data-psum of local ones
                gsq = sum(
                    jnp.sum(jnp.square(v.astype(jnp.float32)))
                    for v in gshards.values()
                )
                gnorm = jnp.sqrt(jax.lax.psum(gsq, AXIS_DATA))
                loss = jax.lax.psum(data_dev, manual)
                aux = jax.lax.psum(aux_dev, manual)
                return new_params, new_opt, (loss, aux, gnorm)

            batch_spec = P(manual)
            if mode == "zero":
                padded = plan.padded_sizes
                opt_spec = jax.tree.map(
                    lambda l: P(AXIS_DATA)
                    if getattr(l, "ndim", 0) == 1 and l.shape[0] in padded
                    else P(),
                    state.opt_state,
                )
                fn = _shard_map(
                    body_zero,
                    mesh=self.mesh,
                    in_specs=(P(), opt_spec, batch_spec),
                    out_specs=(P(), opt_spec, P()),
                    check_vma=False,
                    axis_names=frozenset(manual),
                )
                new_params, new_opt, (loss, aux, gnorm) = fn(
                    state.params, state.opt_state, batch
                )
                new_state = state.replace(
                    step=state.step + 1, params=new_params, opt_state=new_opt
                )
            else:
                fn = _shard_map(
                    body_bucket,
                    mesh=self.mesh,
                    in_specs=(P(), batch_spec),
                    out_specs=(P(), P()),
                    check_vma=False,
                    axis_names=frozenset(manual),
                )
                grads, (loss, aux) = fn(state.params, batch)
                with jax.named_scope("optimizer"):
                    gnorm = optax.global_norm(grads)
                    new_state = state.apply_gradients(grads=grads)
            return new_state, {
                "loss": loss,
                "aux_loss": aux,
                "total_loss": loss + aux,
                "grad_norm": gnorm,
                "step": state.step,
            }

        return jax.jit(train_step, donate_argnums=(0,))

    # ------------------------------------------------------------------ state

    def _init_fn(self) -> Callable:
        if self.pp > 1:
            parts = self._pipeline_parts()

            def init_fn(rng, *ins):
                variables = self.model.init(rng, *ins)
                stage_params = parts.restack(shd.unbox(variables["params"]))
                return TrainState.create(
                    apply_fn=self.model.apply, params=stage_params, tx=self.optimizer
                )
        elif self._overlap_mode()[0] == "zero":
            bucket_mb = self._bucket_mb_eff()
            dz = self._overlap_mode()[2]

            def init_fn(rng, *ins):
                from maggy_tpu.parallel import overlap

                variables = self.model.init(rng, *ins)
                st = TrainState.create(
                    apply_fn=self.model.apply, params=variables["params"],
                    tx=self.optimizer,
                )
                # ZeRO-1: the optax state mirrors the FLAT bucket vectors
                # (the layout the sharded update consumes), not the param
                # tree — state_shardings_for places them P(data)
                plan = overlap.plan_buckets(st.params, bucket_mb, pad_to=dz)
                return st.replace(
                    opt_state=self.optimizer.init(
                        overlap.flatten_buckets(st.params, plan)
                    )
                )
        else:
            def init_fn(rng, *ins):
                variables = self.model.init(rng, *ins)
                return TrainState.create(
                    apply_fn=self.model.apply, params=variables["params"],
                    tx=self.optimizer,
                )

        return init_fn

    def state_shardings_for(self, sample_batch: Dict[str, Any], rng=None):
        """Compute (and cache) every TrainState leaf's NamedSharding from
        shapes alone — no allocation, no compile. ``make_state`` routes
        through this; it also serves placing foreign states (restored
        checkpoints, possibly re-staged across pp degrees) without a
        throwaway init — see :meth:`adopt_state`."""
        if rng is None:
            rng = jax.random.key(0)  # shapes only; the key value is irrelevant
        inputs = _model_inputs(sample_batch)
        abstract = jax.eval_shape(self._init_fn(), rng, *inputs)
        if self.pp > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            parts = self._pipeline_parts()
            n_stages = parts.n_stages
            mesh_shape = dict(self.mesh.shape)
            # the pipeline shard_maps leave tensor AND expert in GSPMD-auto
            # mode (parallel/pipeline.py _manual_axes), so those two — and
            # only those — may shard stage-param dims (pp x tp, pp x ep)
            auto_axes = {
                a: mesh_shape.get(a, 1) for a in (AXIS_TENSOR, AXIS_EXPERT)
            }

            def tensor_dims(names, shape):
                """Mesh axes for a stage leaf's trailing dims: only the
                GSPMD-auto axes are applied — an fsdp/seq rule resolution
                would contradict the pipeline shard_map's manual in_specs
                (params replicated over data/fsdp) and reshard every step."""
                table = dict(self.rules)
                out = []
                for name, dim in zip(names, shape):
                    ax = table.get(name) if name else None
                    if isinstance(ax, (tuple, list)):
                        # multi-axis rules (e.g. (data, fsdp)) are never
                        # auto axes here; also keeps lists unhashed
                        ax = ax[0] if len(ax) == 1 else None
                    ext = auto_axes.get(ax, 0)
                    out.append(ax if ext > 1 and dim % ext == 0 else None)
                return out

            def shard_of(leaf):
                # every stage-stacked leaf (params and the optax state
                # mirroring them) leads with [n_stages]; the rest (step /
                # adam count) are scalars — leading-dim == pp is exact here
                if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == n_stages:
                    return NamedSharding(self.mesh, P(AXIS_STAGE))
                return NamedSharding(self.mesh, P())

            if parts.stage_names is not None:
                spec_params = jax.tree.map(
                    lambda names, leaf: NamedSharding(
                        self.mesh,
                        P(AXIS_STAGE, *tensor_dims(names[1:], leaf.shape[1:])),
                    ),
                    parts.stage_names,
                    abstract.params,
                    is_leaf=lambda x: isinstance(x, tuple),
                )
                pstruct = jax.tree_util.tree_structure(abstract.params)

                def is_ptree(x):
                    try:
                        return jax.tree_util.tree_structure(x) == pstruct
                    except Exception:
                        return False

                # params and every optax mirror of them (adam mu/nu, ...)
                # get the tensor-resolved specs; loose leaves (step, adam
                # count) fall back to the stage/replicated rule
                self.state_shardings = jax.tree.map(
                    lambda x: spec_params
                    if is_ptree(x)
                    else jax.tree.map(shard_of, x),
                    abstract,
                    is_leaf=is_ptree,
                )
            else:
                self.state_shardings = jax.tree.map(shard_of, abstract)
        else:
            self.state_shardings = shd.params_shardings(
                self.mesh, abstract, self.rules
            )
            mode, _, dz = self._overlap_mode()
            if mode == "zero":
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                from maggy_tpu.parallel import overlap

                # the flat ZeRO bucket vectors (built by _init_fn) live
                # sharded over data; loose leaves (adam count) replicate
                plan = overlap.plan_buckets(
                    abstract.params, self._bucket_mb_eff(), pad_to=dz
                )
                padded = plan.padded_sizes
                self.state_shardings = self.state_shardings.replace(
                    opt_state=jax.tree.map(
                        lambda leaf, cur: NamedSharding(self.mesh, P(AXIS_DATA))
                        if getattr(leaf, "ndim", 0) == 1
                        and leaf.shape[0] in padded
                        else cur,
                        abstract.opt_state,
                        self.state_shardings.opt_state,
                    )
                )
        return self.state_shardings

    def make_state(self, rng: jax.Array, sample_batch: Dict[str, Any]) -> TrainState:
        """Initialize a TrainState with every leaf born on its target devices
        (jit + out_shardings — no host-side full materialization). Under a
        ``stage`` mesh axis > 1 the params are born in the stage-stacked
        pipeline layout (see :mod:`maggy_tpu.train.pipeline_adapter`)."""
        from maggy_tpu import telemetry

        inputs = _model_inputs(sample_batch)
        # the host's time only: the init is dispatched, not waited for
        with telemetry.get().span("train.make_state"):
            init = jax.jit(
                self._init_fn(),
                out_shardings=self.state_shardings_for(sample_batch, rng),
            )
            # np (not jnp): host values enter a multi-process jit as replicated
            # inputs instead of arrays committed to one process's local device
            with self.mesh:
                return init(rng, *jax.tree.map(np.asarray, inputs))

    def adopt_state(self, state: TrainState, sample_batch: Dict[str, Any]) -> TrainState:
        """Place a foreign/host TrainState onto THIS trainer's mesh layout —
        e.g. a checkpoint restored elsewhere or re-staged across pp degrees
        via :func:`maggy_tpu.train.pipeline_adapter.convert_pipeline_state`.
        Rebinds apply_fn/optimizer to this trainer's (required for the
        sharding tree's static fields to match) and shards every leaf."""
        shardings = self.state_shardings_for(sample_batch)
        state = state.replace(apply_fn=self.model.apply, tx=self.optimizer)
        with self.mesh:
            return jax.device_put(state, shardings)

    def batch_shardings(self, batch):
        default = shd.batch_sharding(self.mesh, self.rules)
        if not isinstance(batch, dict):
            return jax.tree.map(lambda _: default, batch)
        # packed-sequence side inputs are consumed seq-sharded by the SP
        # attention shard_maps; placing them (batch, seq) up front avoids an
        # XLA full-rematerialization reshard per step. Like params_shardings,
        # degrade to the batch-only placement when the length doesn't divide
        # the seq axis (non-SP attention paths have no divisibility demand).
        # Multi-process meshes place these too: shard_batch slices each
        # process's seq chunk from the sharding's own index map (r5; was a
        # per-step all-gather on the flagship long-context path before).
        seq_keys = ("segment_ids", "positions")
        seq_ext = shd.mesh_extent(
            self.mesh, shd.logical_to_mesh_axes(("activation_seq",), self.rules)[0]
        )
        seq_sharding = shd.named_sharding(
            self.mesh, ("batch", "activation_seq"), self.rules
        )

        def pick(key, leaf):
            if (
                key in seq_keys
                and seq_ext > 1
                and getattr(leaf, "ndim", 0) >= 2
                and leaf.shape[1] % seq_ext == 0
            ):
                return seq_sharding
            return default

        return {
            k: jax.tree.map(lambda leaf, k=k: pick(k, leaf), v)
            for k, v in batch.items()
        }

    def _cached_batch_shardings(self, batch):
        """``batch_shardings`` memoized on the batch's (key, shape, dtype)
        signature — every step of a training run sees the same signature, so
        the sharding tree is computed once instead of per step (the
        shard-spec plumbing the prefetcher keeps off the hot path)."""
        key = None
        if isinstance(batch, dict):
            try:
                key = tuple(
                    sorted(
                        (k, tuple(v.shape), str(v.dtype))
                        for k, v in batch.items()
                    )
                )
            except (AttributeError, TypeError):  # nested/objects: no memo
                key = None
        memo = self._batch_shardings_memo
        if key is not None and memo is not None and memo[0] == key:
            return memo[1]
        shardings = self.batch_shardings(batch)
        if key is not None:
            self._batch_shardings_memo = (key, shardings)
        return shardings

    def _place_packed(self, tel):
        """``shard_batch`` for ``fit``'s prefetcher thread, which has the host
        batch in hand: a packed one also records the share of the attention
        layers' flash tiles its segment ids leave to visit
        (``attention.tiles_visited_share``), as the model's config counts them
        for its own layers (``DecoderConfig.tiles_visited_share``), off the
        loop thread and with nothing read back from the device."""
        share_of = getattr(getattr(self.model, "cfg", None), "tiles_visited_share", None)

        def put(batch):
            seg = batch.get("segment_ids") if isinstance(batch, dict) else None
            if share_of is not None and isinstance(seg, np.ndarray) and seg.ndim == 2:
                share = share_of(seg)
                if share is not None:
                    tel.gauge("attention.tiles_visited_share", share)
            return self.shard_batch(batch)

        return put

    def shard_batch(self, batch, *, local: bool = False):
        """Place a host batch onto the mesh, batch axis over (data, fsdp).

        Single-process: a plain sharded device_put. Multi-process (global
        mesh formed via ``initialize_data_plane``): every process passes the
        same *global* batch and this slices out its own rows before assembly
        — so train_fns stay oblivious to the process topology. A loader that
        already rank-shards its stream (petastorm semantics — reference
        dataloader.py:116-131) passes ``local=True`` to skip the slicing.
        """
        shardings = self._cached_batch_shardings(batch)
        if jax.process_count() == 1:
            return jax.device_put(batch, shardings)
        import numpy as np

        default = shd.batch_sharding(self.mesh, self.rules)

        def process_block(s, shape):
            """This process's contiguous [start, stop) block per array dim,
            straight from the sharding's own index map — correct for any
            mesh/process layout, including a seq axis that spans processes."""
            idx_map = s.addressable_devices_indices_map(shape)
            block = []
            for d in range(len(shape)):
                starts = [sl[d].start or 0 for sl in idx_map.values()]
                stops = [
                    shape[d] if sl[d].stop is None else sl[d].stop
                    for sl in idx_map.values()
                ]
                block.append(slice(min(starts), max(stops)))
            return tuple(block)

        def put(x, s):
            x = np.asarray(x)
            if local:
                # a rank-sharding loader pre-slices ROWS only; it cannot also
                # slice a process-spanning seq chunk — keep batch placement
                # for inner-sharded leaves
                spec = getattr(s, "spec", ())
                if len(spec) > 1 and any(a is not None for a in spec[1:]):
                    s = default
                return jax.make_array_from_process_local_data(s, x)
            # every process passes the same GLOBAL array; carve out exactly
            # this process's block per the sharding's own index map. This is
            # the general rule the old rows/process_count slicing was a
            # special case of — and unlike it, stays correct when the batch
            # axis does NOT span processes (e.g. an sp-only mesh, where every
            # process must supply the full replicated batch) and when the
            # seq axis DOES (each process carves its seq chunk).
            return jax.make_array_from_process_local_data(
                s, np.ascontiguousarray(x[process_block(s, x.shape)]), x.shape
            )

        return jax.tree.map(put, batch, shardings)

    # ------------------------------------------------------------------ steps

    def _pp_batch_parts(self, batch, parts, n_micro: int, dpf: int):
        """Shared pipeline plumbing for the 1F1B train step AND the
        forward-only eval sweep: microbatch the batch, build the raw channel
        stream (packed side inputs ride as int channels so every stage can
        mask/position its attention), and close over the last-stage loss —
        including the packed/masked rescale that keeps per-microbatch masked
        means equal to the dense global mask-weighted mean.
        Returns ``(raw_microbatches, targets, loss_pp)``."""
        tokens = _model_inputs(batch)[0]
        bsz = tokens.shape[0]
        if bsz % n_micro:
            raise ValueError(
                f"batch size {bsz} not divisible by n_microbatches "
                f"{n_micro}; set Trainer(n_microbatches=...) to a divisor"
            )
        if (bsz // n_micro) % dpf:
            raise ValueError(
                f"each of the {n_micro} microbatches has {bsz // n_micro} "
                f"rows, which must divide the mesh's data x fsdp extent "
                f"({dpf}); grow the batch or lower n_microbatches"
            )

        def split(a):
            return a.reshape((n_micro, bsz // n_micro) + a.shape[1:])

        def eff_mask(b):
            """lm_loss_fn's effective target mask for a (sub)batch:
            loss_mask AND same-segment — must mirror lm_loss_fn exactly
            so the rescale below cancels its local denominator."""
            m = None
            lm = b.get("loss_mask")
            if lm is not None:
                m = lm[:, 1:].astype(jnp.float32)
            sg = b.get("segment_ids")
            if sg is not None:
                same = (sg[:, 1:] == sg[:, :-1]).astype(jnp.float32)
                m = same if m is None else m * same
            return m

        tgts = jax.tree.map(split, batch)
        mask_norm = None
        if self.loss_fn is lm_loss_fn and isinstance(batch, dict):
            m = eff_mask(batch)
            if m is not None:
                # global effective-mask sum, for rescaling per-microbatch
                # masked means back to the dense objective — segment
                # boundaries count too, or microbatches with uneven packing
                # would be mis-weighted
                mask_norm = jnp.maximum(m.sum(), 1.0)

        def loss_pp(stage_params, y, tgt):
            loss = self.loss_fn(parts.head_fn(stage_params, y), tgt)
            if mask_norm is not None:
                local = jnp.maximum(eff_mask(tgt).sum(), 1.0)
                # the schedule divides the psum of these by dpf*n_micro;
                # this rescale makes the total sum(ll*mask)/global_sum
                loss = loss * local * (dpf * n_micro) / mask_norm
            return loss

        if isinstance(batch, dict) and (
            "segment_ids" in batch or "positions" in batch
        ):
            # positions-only batches stack 2 channels — a zeros segment-id
            # channel would needlessly disable the flash kernel's
            # segment_ids-is-None fast path
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(tokens.shape[1], dtype=tokens.dtype),
                    tokens.shape,
                )
            channels = [tokens, positions.astype(tokens.dtype)]
            seg = batch.get("segment_ids")
            if seg is not None:
                channels.append(seg.astype(tokens.dtype))
            raw = jnp.stack(channels, axis=-1)
        else:
            raw = tokens
        return split(raw), tgts, loss_pp

    def _build_pp_train_step(self):
        """1F1B pipeline training step (mesh has stage>1): microbatch the
        batch axis, run :func:`pipeline_grads_1f1b` with the Decoder stage
        adapter, apply gradients in the stage-stacked layout.

        Loss semantics: the schedule averages per-microbatch losses. For the
        built-in :func:`lm_loss_fn` with a ``loss_mask`` that would differ
        from the dense path's global mask-weighted mean (sparse microbatches
        would be up-weighted), so that case is rescaled to the exact global
        mean. Custom loss_fns keep plain microbatch-mean averaging.
        """
        from maggy_tpu.parallel.pipeline import pipeline_grads_1f1b

        parts = self._pipeline_parts()
        n_micro = self.n_microbatches or 2 * parts.n_stages
        self._pp_built_micro = n_micro
        shape = dict(self.mesh.shape)
        dpf = shape.get(shd.AXIS_DATA, 1) * shape.get(shd.AXIS_FSDP, 1)

        def train_step(state: TrainState, batch):
            self._step_traces += 1  # trace-time: counts compiles, not calls
            split_raw, tgts, loss_pp = self._pp_batch_parts(
                batch, parts, n_micro, dpf
            )
            out = pipeline_grads_1f1b(
                parts.stage_fn,
                loss_pp,
                state.params,
                split_raw,
                tgts,
                mesh=self.mesh,
                first_fn=parts.first_fn,
                stage_takes_raw=True,
                stage_has_aux=parts.stage_has_aux,
            )
            if parts.stage_has_aux:
                loss, grads, aux = out
            else:
                (loss, grads), aux = out, jnp.zeros((), jnp.float32)
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=grads)
                gnorm = optax.global_norm(grads)
            # same metric semantics as the dense path: loss = data only,
            # aux_loss = router terms, total = optimized objective
            return new_state, {
                "loss": loss,
                "aux_loss": aux,
                "total_loss": loss + aux,
                "grad_norm": gnorm,
                "step": state.step,
            }

        return jax.jit(train_step, donate_argnums=(0,))

    def _head_targets(self, batch) -> Dict[str, Any]:
        """What the model's ``apply`` takes for its head to run inside the
        loss, in blocks of the sequence (``models/head.py`` ``step_targets``):
        the built-in language-model loss has that form, a user's ``loss_fn``
        takes logits."""
        return head.step_targets(self.model, batch, self.mesh) if self.loss_fn is lm_loss_fn else {}

    def _build_train_step(self):
        if self.pp > 1:
            self._overlap_mode()  # zero/bucket on a pp mesh: one-time warning
            return self._build_pp_train_step()
        mode, manual, dz = self._overlap_mode()
        if mode != "off":
            return self._build_overlap_train_step(mode, manual, dz)

        def train_step(state: TrainState, batch):
            self._step_traces += 1  # trace-time: counts compiles, not calls

            def loss_of(params):
                # mutable intermediates, or flax `sow` is a silent no-op: what the
                # model's layers sow is read through models/sown.py
                targets = self._head_targets(batch)
                out, mods = state.apply_fn(
                    {"params": params}, *_model_inputs(batch), mutable=["intermediates"],
                    **sown.step_inputs(self.model, state.step), **targets,
                )
                with jax.named_scope("loss"):
                    loss, mtp = data_losses(self.loss_fn, out, mods, batch, bool(targets))
                aux = sown.collect_aux_losses(mods)
                extra = sown.step_counters(mods)
                total = loss + aux
                if mtp is not None:
                    total = total + self.model.cfg.mtp_weight * mtp
                    extra["mtp_loss"] = mtp
                return total, (loss, aux, extra)

            (total, (loss, aux, extra)), grads = jax.value_and_grad(loss_of, has_aux=True)(
                state.params
            )
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=grads)
                gnorm = optax.global_norm(grads)
            return new_state, {
                "loss": loss,
                "aux_loss": aux,
                "total_loss": total,
                "grad_norm": gnorm,
                "step": state.step,
                **extra,
            }

        return jax.jit(train_step, donate_argnums=(0,))

    def checkpoint_meta(self) -> Dict[str, Any]:
        """The active system configuration, recorded by ``Checkpointer.save``
        alongside every state this trainer checkpoints: non-trivial mesh
        axes, microbatch setting, and the model's compute dtype. Restores
        compare it against the live trainer's and warn on mismatch."""
        mesh_axes = {k: v for k, v in dict(self.mesh.shape).items() if v > 1}
        cfg = getattr(self.model, "cfg", None)
        mode, _, dz = self._overlap_mode()
        return {
            "mesh_axes": mesh_axes,
            "num_devices": int(self.mesh.size),
            # world-size provenance: restores compare these against the live
            # topology and warn-and-reshard instead of silently mis-sharding
            # when a checkpoint crosses mesh widths (elastic reshape,
            # pod-size changes)
            "n_processes": int(jax.process_count()),
            "n_microbatches": self.n_microbatches,
            "dtype": str(getattr(cfg, "dtype", None)) if cfg is not None else None,
            # EFFECTIVE ZeRO layout (not the requested knobs): what
            # restore_zero_compat needs to rebuild the saved optimizer-state
            # layout when zero_stage / bucket_mb / data width change between
            # save and restore
            "zero": {
                "stage": 1 if mode == "zero" else 0,
                "bucket_mb": self._bucket_mb_eff() if mode == "zero" else None,
                "shards": dz,
            },
        }

    def _membership_check(self, state, step: int, checkpointer, chaos, tel) -> None:
        """Elastic-membership step-boundary seam (docs/resilience.md).

        Raises one of the membership control-flow exceptions when the mesh
        must reshape; the distributed executor's elastic loop catches them,
        negotiates the new view with the driver, and re-enters the train_fn
        (which resumes from the latest complete checkpoint).

        * A **pending epoch** (another member's event, delivered via the
          heartbeat RESHAPE reply) and a chaos **slice_rejoin** are
          graceful: the current step is checkpointed synchronously first,
          so all members converge on a checkpoint that includes every step
          taken here and nothing re-runs.
        * A chaos **slice_drop** is abrupt — the slice's devices (and any
          state since the last retained checkpoint) are gone, exactly like
          a real preemption, so nothing is saved: the reshaped run falls
          back to the last periodic checkpoint.
        """
        from maggy_tpu.resilience.membership import (
            MembershipChanged,
            SliceLost,
            SliceRejoin,
        )

        mem = self.membership
        event: Optional[BaseException] = None
        pending = mem.pending_epoch()
        if pending is not None:
            event = MembershipChanged(pending)
        elif chaos is not None:
            # sim mode hosts every active slice, so any of them may drop
            # here; a worker-mode process IS one slice and only its own
            # loss can originate locally
            self_slice = getattr(mem, "self_slice", None)
            candidates = mem.active if self_slice is None else (self_slice,)
            dropped = chaos.slice_drop(candidates, step=step)
            if dropped is not None:
                raise SliceLost(dropped, step=step)
            if self_slice is None:
                joined = chaos.slice_rejoin(mem.inactive, step=step)
                if joined is not None:
                    event = SliceRejoin(joined, step=step)
        if event is None:
            return
        if checkpointer is not None:
            # the reshape barrier's convergence point: one synchronous save
            # at the current step (same discipline as the preemption hook)
            checkpointer.save(step, state, meta=self.checkpoint_meta())
            checkpointer.wait()
            tel.count("resilience.reshape_checkpoints")
        raise event

    def step(self, state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        if (
            self._train_step is not None
            and self.pp > 1
            and (self.n_microbatches or 2 * self.pp) != self._pp_built_micro
        ):
            self._train_step = None  # n_microbatches changed: recompile
        if self._train_step is None:
            self._expect_recompile = True  # deliberate build: sentinel-sanctioned
            self._train_step = self._build_train_step()
        with self.mesh:
            return self._train_step(state, batch)

    @property
    def compile_counts(self) -> Dict[str, int]:
        """Compile count per jitted program (recompile-sentinel input): a
        bump without a preceding deliberate rebuild means XLA silently
        retraced — usually a drifting batch shape."""
        return {"train_step": self._step_traces}

    def eval_logits(self, state: TrainState, batch):
        """Full logits for one batch.

        MEMORY CAVEAT under pp>1: the stage-stacked params are unstacked and
        the whole model runs replicated per device — fine for tests/small
        models, an HBM spike at the scale pipeline parallelism exists for.
        Prefer :meth:`evaluate` there (forward-only pipelined loss, live
        bytes bounded by ~1 stage); full-logit extraction at scale should go
        through a checkpoint into a non-pp serving mesh."""
        if self._eval_step is None:
            if self.pp > 1:
                parts = self._pipeline_parts()

                def eval_step(state, batch):
                    params = parts.unstack(state.params)
                    return self.model.apply({"params": params}, *_model_inputs(batch))
            else:
                def eval_step(state, batch):
                    return state.apply_fn(
                        {"params": state.params}, *_model_inputs(batch), **sown.step_inputs(self.model, state.step)
                    )

            self._eval_step = jax.jit(eval_step)
        with self.mesh:
            return self._eval_step(state, batch)

    def evaluate(
        self,
        state: TrainState,
        data_iter,
        num_batches: int,
        prefetch: Optional[int] = None,
    ) -> Dict[str, float]:
        """Mean loss over ``num_batches`` held-out batches (no state update).
        The loss is computed inside jit so full logits never leave the
        device. Under pp>1 the loss flows through the pipeline stages
        (forward-only GPipe sweep) — per-device live
        bytes stay bounded by one stage's params + a microbatch activation,
        never the unstacked full model.

        Host overlap (docs/performance.md): input batches flow through a
        :class:`~maggy_tpu.train.prefetch.DevicePrefetcher` (``prefetch``
        batches ahead; ``MAGGY_TPU_PREFETCH`` sets the default, 0 disables)
        capped at ``num_batches`` so the iterator is never over-consumed,
        and the per-batch losses accumulate ON DEVICE — one host sync at
        the end instead of a pipeline drain per batch."""
        if num_batches < 1:
            raise ValueError("evaluate needs num_batches >= 1")
        if self._eval_loss_step is None:
            if self.pp > 1:
                from maggy_tpu.parallel.pipeline import pipeline_forward_loss

                parts = self._pipeline_parts()
                n_micro = self.n_microbatches or 2 * parts.n_stages
                shape = dict(self.mesh.shape)
                dpf = shape.get(shd.AXIS_DATA, 1) * shape.get(shd.AXIS_FSDP, 1)

                def eval_loss(state, batch):
                    split_raw, tgts, loss_pp = self._pp_batch_parts(
                        batch, parts, n_micro, dpf
                    )
                    loss, _aux = pipeline_forward_loss(
                        parts.stage_fn,
                        loss_pp,
                        state.params,
                        split_raw,
                        tgts,
                        mesh=self.mesh,
                        first_fn=parts.first_fn,
                        stage_takes_raw=True,
                        stage_has_aux=parts.stage_has_aux,
                    )
                    return loss
            else:
                def eval_loss(state, batch):
                    targets = self._head_targets(batch)
                    out, mods = state.apply_fn(
                        {"params": state.params}, *_model_inputs(batch), mutable=["intermediates"],
                        **sown.step_inputs(self.model, state.step), **targets,
                    )
                    return out[0] if targets else model_loss(self.loss_fn, out, mods, batch)

            self._eval_loss_step = jax.jit(eval_loss)
        from maggy_tpu import telemetry
        from maggy_tpu.train.prefetch import DevicePrefetcher

        depth = _prefetch_depth(prefetch)
        prefetcher = (
            DevicePrefetcher(
                data_iter,
                self.shard_batch,
                depth=depth,
                max_items=num_batches,
                telemetry_recorder=telemetry.get(),
            )
            if depth > 0
            else None
        )
        total = None
        try:
            with self.mesh:
                for _ in range(num_batches):
                    if prefetcher is not None:
                        batch = next(prefetcher)
                    else:
                        batch = self.shard_batch(next(data_iter))
                    loss = self._eval_loss_step(state, batch)
                    # accumulate on device: no per-batch float() pipeline
                    # drain — the single conversion below is the only sync
                    total = loss if total is None else total + loss
        finally:
            if prefetcher is not None:
                prefetcher.close()
        return {"loss": float(total) / num_batches}

    def fit(
        self,
        state: TrainState,
        data_iter,
        num_steps: int,
        reporter=None,
        report_every: int = 10,
        metric_key: str = "loss",
        metric_sign: float = 1.0,
        checkpointer=None,
        checkpoint_every: int = 0,
        profile_dir: Optional[str] = None,
        profile_steps: Tuple[int, int] = (3, 6),
        resume: Optional[Any] = None,
        prefetch: Optional[int] = None,
        metrics_window: int = 2,
        autopilot: Optional[Any] = None,
    ) -> Tuple[TrainState, Dict[str, float]]:
        """Simple host-side loop: shard batch → step → optional reporter
        broadcast at step boundaries (where EarlyStopException can interrupt —
        SURVEY.md §7 'host-callback polling at step boundaries').

        ``profile_dir`` captures a JAX/XLA profiler trace over
        ``profile_steps=(start, stop)`` (reference has no tracer, §5.1);
        ``checkpointer`` + ``checkpoint_every`` save the state periodically.

        Resilience (docs/resilience.md): ``resume="auto"`` restores the
        checkpointer's latest retained step over ``state`` (an explicit int
        restores that step) and fast-forwards ``data_iter`` by the number of
        steps already completed, so the loss trajectory continues exactly
        where the interrupted run left off; ``num_steps`` stays the TOTAL
        step budget for the run — only the remainder executes. With no
        checkpoint on disk, ``resume="auto"`` is a fresh run. When a
        checkpointer is present, fit also installs the SIGTERM/preemption
        hook (:mod:`maggy_tpu.resilience.preemption`): on notice it performs
        one final *synchronous* save at the current step and returns early
        with ``metrics["preempted"] = 1.0``.

        Reported values are ``metric_sign * metrics[metric_key]``. Broadcast
        values MUST be the same quantity and orientation as the train_fn's
        returned optimization metric — the driver's early stopping and trial
        ranking compare the two directly. When the experiment runs with
        ``direction="max"`` and the train_fn returns ``-loss``, pass
        ``metric_sign=-1.0`` so live broadcasts match; there is no implicit
        negation.

        Host overlap (docs/performance.md): with ``prefetch > 0`` (default
        2; ``MAGGY_TPU_PREFETCH`` overrides, 0 disables) batches flow
        through a :class:`~maggy_tpu.train.prefetch.DevicePrefetcher` — a
        background thread runs ``shard_batch`` (host gather + H2D transfer)
        ``prefetch`` batches ahead, so the device queue never waits on the
        host input pipeline. Consumption is capped at ``num_steps`` batches,
        so a shared iterator keeps its position across calls; only early
        exits (preemption/early stop) may leave up to ``prefetch`` extra
        batches consumed, and data-wait timing shifts accordingly (a
        preemption notice raised as a loader side effect fires when the
        PREFETCHER pulls that batch, up to ``prefetch`` steps early).

        Lagged metrics drain: reporter broadcasts read the metrics ref that
        just LEFT a ``metrics_window``-deep in-flight window (so the
        ``float()`` touches a value ``metrics_window`` steps old and never
        drains the XLA dispatch pipeline), stamped with the step it was
        measured at. Broadcast values are therefore up to ``metrics_window``
        steps stale and driver-side early stopping fires up to that many
        steps later; ``metrics_window=0`` restores synchronous broadcasts.
        The ``metrics_lag`` gauge records the realized lag.

        Autopilot (docs/autotune.md "Continuous tuning"): ``autopilot=True``
        (or an :class:`~maggy_tpu.autopilot.AutopilotConfig`) attaches an
        online controller that diagnoses each window of steps
        (input/drain/compute-bound), live-retunes the safe knobs — prefetch
        depth, metrics window — behind a measured before/after guard with
        automatic rollback, journals every decision as ``autopilot.*``
        telemetry, and shares committed knobs through the tune cache keyed
        by workload fingerprint.

        Telemetry: the loop records one span per phase into the ambient
        recorder (:func:`maggy_tpu.telemetry.get`; executors install a
        per-worker one) — ``train.fit_setup`` (entry to the first step),
        ``train.input_wait`` (the blocked pull of the next batch),
        ``train_step`` (dispatch), ``train.drain`` (every wait for the
        device), ``train.checkpoint`` — which inside a profiler session lie
        in its trace beside the device events, plus ``tokens_per_sec`` /
        ``mfu_est`` gauges. Under a live recorder a thread of the call's own
        (:class:`_StepWatcher`) waits for every step's output in turn and
        journals one ``train.step_device`` span a step, with the step's loss
        and counters, and its duration as ``step_time_ms``. A step whose call
        traced the program (the first of a cold trainer, a rebuild) is synced
        once on the loop thread to cover the XLA compile and lands in
        ``compile_time_ms`` instead of ``step_time_ms``; step 0 of a later
        ``fit`` on a warm trainer is an ordinary step. The prefetcher adds
        ``input_wait_ms`` (host time blocked waiting for an input batch) and
        ``prefetch_depth`` (queue occupancy) gauges, plus the ``shard_batch``
        spans the synchronous path records inline. The returned metrics dict
        always carries the measured ``steps_per_sec`` regardless of the
        telemetry flag. The loop thread itself syncs on nothing it does not
        read.
        """
        from maggy_tpu import telemetry
        from maggy_tpu.resilience import chaos as _chaos
        from maggy_tpu.resilience import preemption as _preemption
        from maggy_tpu.telemetry import flightrec as _flightrec
        from maggy_tpu.telemetry import tracing as _tracing

        tel = telemetry.get()
        # entry to the first step is one phase of the timeline: a device
        # that idles here waits on the ledger, the resume or the prefetcher
        with tel.span("train.fit_setup"):
            resumed_from = None
            skipped = 0
            if resume is not None:
                if checkpointer is None:
                    raise ValueError("fit(resume=...) requires a checkpointer")
                target = (
                    checkpointer.latest_step() if resume == "auto" else int(resume)
                )
                if target is not None and target > int(state.step):
                    from maggy_tpu.train.checkpoint import restore_zero_compat

                    start = int(state.step)
                    # zero-layout-aware restore: a checkpoint written under a
                    # different zero_stage/bucket/data-width gets its optimizer
                    # state converted (warn-and-reshard) instead of failing on
                    # the flat-vs-dense tree mismatch
                    state = restore_zero_compat(
                        checkpointer,
                        state,
                        step=None if resume == "auto" else target,
                        live_meta=self.checkpoint_meta(),
                    )
                    resumed_from = int(state.step)
                    skipped = resumed_from - start
                    # fast-forward: the interrupted run consumed one batch per
                    # completed step — skip them so the data stream (and the loss
                    # trajectory) continues where it left off. Loaders with a
                    # skip(n) fast path (batch_iterator, NativeBatchLoader)
                    # advance by index; plain generators drain next().
                    from maggy_tpu.train.prefetch import skip_batches

                    skip_batches(data_iter, skipped)
                    tel.count("resilience.auto_resumes")
            # num_steps is the TOTAL budget for this fit call; a resumed fit only
            # executes the remainder
            num_steps = max(0, num_steps - skipped)
            # preemption notice -> one final synchronous save + early return;
            # only armed when there is a checkpointer to save into
            hook = _preemption.install() if checkpointer is not None else None
            chaos = _chaos.get()
            # host-side step base: every in-loop "current step" below derives
            # from this + the loop index, so nothing int()s the device-resident
            # state.step (which would drain the dispatch pipeline)
            step0 = int(state.step)
            preempted = False
            metrics = {}
            profiling = False
            prof_start = min(profile_steps[0], max(0, num_steps - 2))
            prof_stop = min(profile_steps[1], num_steps - 1)
            # capacity ledger (docs/observability.md "Capacity"): the training
            # tier's HBM accounts — params, optimizer state (the ZeRO shards on
            # a sharded mesh), and the prefetcher's staged batches — reconciled
            # against reported device memory on the series-sample cadence
            from maggy_tpu.telemetry import memtrack as _memtrack

            ledger = _memtrack.MemoryLedger()
            ledger.register("params", _memtrack.array_bytes(state.params))
            ledger.register("optimizer", _memtrack.array_bytes(state.opt_state))
            depth = _prefetch_depth(prefetch)
            prefetcher = None
            if depth > 0 and num_steps > 0:
                from maggy_tpu.train.prefetch import DevicePrefetcher

                prefetcher = DevicePrefetcher(
                    data_iter,
                    self._place_packed(tel),
                    depth=depth,
                    max_items=num_steps,
                    telemetry_recorder=tel,
                    ledger=ledger,
                )
            window = max(0, int(metrics_window))
            # autopilot: an in-loop controller fed one sample per step; its
            # safe-live moves land on the prefetcher depth / metrics window of
            # THIS run (built lazily at step 0, once the batch signature that
            # names the workload fingerprint is known)
            ap = None
            ap_target = None
            ap_cfg = None
            if autopilot is not None and autopilot is not False:
                from maggy_tpu.autopilot import AutopilotConfig as _ApConfig

                ap_cfg = (
                    autopilot if isinstance(autopilot, _ApConfig) else _ApConfig()
                )
                ap_target = _FitAutopilotTarget(prefetcher, window, trainer=self)
            ap_wait_total = prefetcher.wait_ms_total if prefetcher is not None else 0.0
            pending: deque = deque()  # (loop index, in-flight device metrics)
            ready = None  # newest entry aged OUT of the window: safe to sync
            last_bcast = -1  # last loop index broadcast (monotonic step guard)
            fit_t0 = time.perf_counter()
            tokens_per_batch = 0
            # one trace per fit run: every span/gauge the loop records carries
            # it, and the run's start/end land as lifecycle events — the
            # training-side analogue of a serving request's lane
            run_trace = _tracing.new_trace_id()
            trace_prev = _tracing.current()
            _tracing.set_current(run_trace)
            tel.event(
                "train.run_start", trace=run_trace, num_steps=num_steps,
                resumed_from=resumed_from, step0=step0,
            )
            # stall watchdog: the loop beats per step; a wedged device/step
            # dumps the flight recorder (docs/observability.md). The threshold
            # is far above any healthy step — a long first-step compile only
            # risks a harmless diagnostic dump.
            wd = _flightrec.get()
            wd.begin("train.step", detail=step0)
            # recompile sentinel + time-series sampling (docs/observability.md):
            # the jitted step bumps a trace-time counter; a bump without a
            # deliberate rebuild means XLA silently retraced (usually a drifting
            # batch shape) and costs a full compile mid-run — alert, don't guess.
            # The store samples the recorder on its ~1 s tick (one clock compare
            # per step otherwise).
            from maggy_tpu.telemetry import timeseries as _timeseries
            from maggy_tpu.telemetry.alerts import RecompileSentinel as _Sentinel

            ts_store = _timeseries.SeriesStore()
            sentinel = _Sentinel(ts_store, tel, scope="worker", steady=("train_step",))
            # the steps' ends, seen from a thread of their own (a live recorder
            # only: with telemetry off no thread starts and the loop is as it
            # was). Last in the span: the thread's start is set-up's time, and
            # nothing stands between it and the try that ends it
            watcher = _StepWatcher(tel, run_trace, step0) if tel.active else None
        try:
            for i in range(num_steps):  # hot-loop (tools/check_host_sync.py)
                wd.beat("train.step", detail=step0 + i)
                if self.membership is not None:
                    # elastic membership (docs/resilience.md): a pending
                    # epoch or a chaos slice event interrupts the loop at
                    # this step boundary; graceful transitions checkpoint
                    # the current step first so no step re-runs
                    self._membership_check(
                        state, step0 + i, checkpointer, chaos, tel
                    )
                if chaos is not None:
                    # deterministic fault injection (chaos harness): a
                    # matching kill rule raises WorkerLost here
                    chaos.kill(tel.worker, step=step0 + i)
                if hook is not None and hook.requested():
                    with tel.span("train.checkpoint", step=i, why="preempt"):
                        checkpointer.save(
                            step0 + i, state, meta=self.checkpoint_meta()
                        )
                        checkpointer.wait()
                    tel.count("resilience.preempt_saves")
                    preempted = True
                    break
                if profile_dir is not None and not profiling and i == prof_start:
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                ap_drain_ms = 0.0  # this step's measured broadcast drain
                t_in0 = time.perf_counter() if ap_target is not None else 0.0
                if prefetcher is not None:
                    # sharded batches arrive pre-placed; H2D transfer of this
                    # batch overlapped compute of the previous step
                    with tel.span("train.input_wait", step=i):
                        sharded = next(prefetcher)
                else:
                    with tel.span("train.input_wait", step=i):
                        batch = next(data_iter)
                    with tel.span("shard_batch", step=i):
                        sharded = self.shard_batch(batch)
                if ap_target is not None:
                    if prefetcher is not None:
                        # queue-wait delta: the prefetcher already measures
                        # exactly the blocked portion of this pull
                        step_wait_ms = prefetcher.wait_ms_total - ap_wait_total
                        ap_wait_total = prefetcher.wait_ms_total
                    else:
                        step_wait_ms = (time.perf_counter() - t_in0) * 1e3
                if i == 0 and isinstance(sharded, dict) and "tokens" in sharded:
                    tokens_per_batch = int(  # sync: ok — shape metadata, not device data
                        getattr(sharded["tokens"], "size", 0)
                    )
                t0 = time.perf_counter()
                traces0 = self._step_traces
                with tel.span("train_step", step=i):
                    state, metrics = self.step(state, sharded)
                # the call traced the program (first step of a cold trainer,
                # a rebuild, a drifting shape): one deliberate sync so the
                # sample covers the XLA compile. Every other step, step 0 of
                # a warm trainer's fit included, stays fully async
                compiled = self._step_traces > traces0
                if compiled and tel.active:
                    with tel.span("train.drain", step=i, why="compile"):
                        jax.block_until_ready(metrics)  # sync: ok — compile timing
                dt_ms = (time.perf_counter() - t0) * 1e3
                if watcher is not None:  # dispatched dt_ms ago, on the records' clock
                    watcher.put(i, time.time() - dt_ms / 1e3, compiled, tokens_per_batch, metrics)
                if self._expect_recompile:
                    sentinel.expect("train_step")
                    self._expect_recompile = False
                sentinel.observe(self.compile_counts, watchdog=wd)
                if ts_store.maybe_sample(tel):
                    # same ~1 s cadence as the series sample: reconcile the
                    # HBM accounts and export headroom (params/optimizer
                    # re-read so adopted/replaced state stays honest)
                    ledger.register(
                        "params", _memtrack.array_bytes(state.params)
                    )
                    ledger.register(
                        "optimizer", _memtrack.array_bytes(state.opt_state)
                    )
                    ledger.tick(store=ts_store, telemetry=tel, now=time.time())
                # lagged metrics window: refs sit here `window` steps before
                # anything host-reads them, so broadcasts touch only results
                # the device has long finished — never the dispatch frontier
                pending.append((i, metrics))
                while len(pending) > max(1, window):
                    ready = pending.popleft()
                if profiling and i >= prof_stop:
                    with tel.span("train.drain", step=i, why="profile"):
                        jax.block_until_ready(metrics)  # sync: ok — trace boundary
                    jax.profiler.stop_trace()
                    profiling = False
                    profile_dir = None  # one capture per fit
                if reporter is not None and (i + 1) % report_every == 0:
                    # window 0 = synchronous broadcasts (fresh value, full
                    # pipeline drain); otherwise read the entry that aged
                    # out of the window
                    src = pending[-1] if window == 0 else ready
                    if (src is None or src[0] <= last_bcast) and i == num_steps - 1:
                        src = pending[0]  # final boundary: window not primed
                    if src is not None and src[0] > last_bcast:
                        j, lagged = src
                        last_bcast = j
                        tel.gauge("metrics_lag", i - j)
                        t_drain = time.perf_counter()
                        with tel.span("train.drain", step=i, why="report"):
                            value = metric_sign * float(lagged[metric_key])  # sync: ok — ref aged out of the window
                        # host time blocked in this read: the per-step
                        # drain cost analyze_trace attributes
                        ap_drain_ms = (time.perf_counter() - t_drain) * 1e3
                        tel.gauge("metrics_drain_ms", ap_drain_ms)
                        reporter.broadcast(value, step=step0 + j + 1)
                if checkpointer is not None and checkpoint_every and (
                    (i + 1) % checkpoint_every == 0
                ):
                    with tel.span("train.checkpoint", step=i):
                        checkpointer.save(
                            step0 + i + 1, state, meta=self.checkpoint_meta()
                        )
                if ap_target is not None:
                    if ap is None:
                        # the first batch names the workload: (model config
                        # + system config) x traffic shape -> the fleet-
                        # shared decision-cache key
                        from maggy_tpu.autopilot import (
                            Controller as _ApController,
                        )
                        from maggy_tpu.autopilot import plan as _ap_plan

                        bsz = seq = 0
                        if isinstance(sharded, dict) and "tokens" in sharded:
                            shape = getattr(sharded["tokens"], "shape", (0, 0))
                            bsz, seq = int(shape[0]), int(shape[-1])  # sync: ok — shape metadata, not device data
                        workload = _ap_plan.workload_fingerprint(
                            repr(getattr(self.model, "cfg", type(self.model).__name__)),
                            self.checkpoint_meta(),
                            _ap_plan.traffic_shape("train", batch=bsz, seq=seq),
                        )
                        ap = _ApController(
                            ap_target,
                            config=ap_cfg,
                            telemetry_recorder=tel,
                            workload=workload,
                        )
                    elif not compiled:  # a compile step would poison the window
                        # the guard is the TRUE per-step rate — compute plus
                        # the input wait and broadcast drain a move targets
                        wall_ms = dt_ms + step_wait_ms + ap_drain_ms
                        ap.observe(
                            {
                                "step_time_ms": dt_ms,
                                "input_wait_ms": step_wait_ms,
                                "metrics_drain_ms": ap_drain_ms,
                                "steps_per_sec": (
                                    1e3 / wall_ms if wall_ms > 0 else 0.0
                                ),
                            }
                        )
                        window = max(0, ap_target.metrics_window)
        except BaseException:
            if watcher is not None:
                watcher.close()  # a step that raises leaves no thread behind
            raise
        finally:
            wd.end("train.step")
            _tracing.set_current(trace_prev)
            if prefetcher is not None:
                prefetcher.close()
            if profiling:  # loop ended/raised while a trace was active
                jax.profiler.stop_trace()
        tel.event(
            "train.run_end", trace=run_trace, steps=num_steps,
            preempted=preempted,
        )
        with tel.span("train.drain", why="return"):
            if watcher is not None:
                watcher.close()  # the loop thread's wait for the device is booked here, as before
            out = {k: float(v) for k, v in metrics.items()}
        if resumed_from is not None:
            out["resumed_from"] = float(resumed_from)
        if preempted:
            out["preempted"] = 1.0
        # measured AFTER the float() conversions above — those force the
        # device->host sync that makes the wall time honest
        wall = time.perf_counter() - fit_t0
        if num_steps > 0 and wall > 0:
            out["steps_per_sec"] = num_steps / wall
            tel.gauge("steps_per_sec", out["steps_per_sec"])
            if tokens_per_batch and tel.active:
                tok_per_sec = tokens_per_batch * num_steps / wall
                tel.gauge("tokens_per_sec", tok_per_sec)
                from maggy_tpu.telemetry import flops as _flops

                mfu = _flops.estimate_mfu(
                    tok_per_sec,
                    _flops.param_count(state.params),
                    list(self.mesh.devices.flat),
                )
                if mfu is not None:
                    tel.gauge("mfu_est", mfu)
        return state, out


@dataclasses.dataclass
class TrainContext:
    """What the distributed executor injects into an oblivious train_fn.

    The train_fn can stay framework-high-level (use ``ctx.trainer(...)``) or go
    low-level (use ``ctx.mesh`` + ``ctx.shard`` directly with its own pjit).
    """

    mesh: Any
    spec: ShardingSpec
    process_index: int = 0
    num_processes: int = 1
    rules: Tuple = shd.DEFAULT_RULES
    # "chief" (worker 0) / "worker" / "evaluator" — the reference's TF role
    # assignment (tf_dist_executor.py:138-144); an evaluator is outside the
    # training group and should evaluate checkpoints instead of training
    role: str = "worker"
    # elastic membership (docs/resilience.md): the worker's MembershipMonitor
    # and, for multi-slice meshes, the SliceTopology the mesh was built for
    membership: Any = None
    topology: Any = None

    @classmethod
    def create(
        cls, spec_or_preset="fsdp", devices=None, role="worker", membership=None
    ) -> "TrainContext":
        import jax as _jax

        from maggy_tpu import util
        from maggy_tpu.parallel.mesh import mesh_for

        # one XLA compile per geometry across trials/instances/processes
        util.enable_compilation_cache()
        mesh, spec = mesh_for(sharding=spec_or_preset, devices=devices)
        return cls(
            mesh=mesh,
            spec=spec,
            process_index=_jax.process_index(),
            num_processes=_jax.process_count(),
            role=role,
            membership=membership,
        )

    @classmethod
    def create_sliced(
        cls,
        spec_or_preset="fsdp",
        total_slices: int = 1,
        active=None,
        devices=None,
        role="worker",
        membership=None,
    ) -> "TrainContext":
        """A context over a multi-slice mesh (docs/distributed.md "Slice
        topology"): the device lease splits into ``total_slices`` contiguous
        simulated slices, ``active`` (default: all) selects which are in the
        mesh, and each runs ``spec_or_preset`` internally under an outer
        ``slice`` data axis. Batch placement spans (slice, data, fsdp) via
        :func:`maggy_tpu.parallel.sharding.slice_rules`; params never shard
        over ``slice``, so the gradient sync decomposes into intra-slice
        reduce-scatter (ICI) + cross-slice all-reduce (DCN). Elastic
        membership rebuilds this context with the surviving ``active`` set
        on every epoch change."""
        import jax as _jax

        from maggy_tpu import util
        from maggy_tpu.parallel.mesh import make_slice_mesh, slice_device_groups
        from maggy_tpu.parallel.spec import SliceTopology

        util.enable_compilation_cache()
        devices = list(devices) if devices else list(_jax.devices())
        groups = slice_device_groups(total_slices, devices)
        active = tuple(sorted(active if active is not None else range(total_slices)))
        if not active:
            raise ValueError("create_sliced needs at least one active slice")
        mesh_devices = [d for s in active for d in groups[s]]
        per_slice = len(groups[0])
        if isinstance(spec_or_preset, ShardingSpec):
            spec = (
                spec_or_preset
                if spec_or_preset.num_devices == per_slice
                else spec_or_preset.scaled_to(per_slice)
            )
        else:
            spec = ShardingSpec.preset(spec_or_preset, per_slice)
        topology = SliceTopology(n_slices=len(active), slice_spec=spec)
        return cls(
            mesh=make_slice_mesh(topology, mesh_devices),
            spec=spec,
            process_index=_jax.process_index(),
            num_processes=_jax.process_count(),
            rules=shd.slice_rules(shd.DEFAULT_RULES),
            role=role,
            membership=membership,
            topology=topology,
        )

    def trainer(
        self,
        model,
        optimizer,
        loss_fn: Callable = lm_loss_fn,
        n_microbatches: Optional[int] = None,
        zero_stage: Optional[int] = None,
        bucket_mb: Optional[float] = None,
    ) -> Trainer:
        # overlap knobs default to the spec's (config/distributed.py plumbs
        # them there); explicit arguments win
        if zero_stage is None:
            zero_stage = getattr(self.spec, "zero_stage", 0)
        if bucket_mb is None:
            bucket_mb = getattr(self.spec, "bucket_mb", None)
        return Trainer(
            model,
            optimizer,
            self.mesh,
            loss_fn=loss_fn,
            rules=self.rules,
            n_microbatches=n_microbatches,
            membership=self.membership,
            zero_stage=int(zero_stage),
            bucket_mb=bucket_mb,
        )

    def shard(self, tree, logical_axes=("batch",)):
        target = shd.named_sharding(self.mesh, logical_axes, self.rules)
        return jax.device_put(tree, target)
