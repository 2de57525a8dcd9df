"""Decoder ⇄ 1F1B pipeline adapter: stage functions for the flagship model.

The reference explicitly rejects pipeline modules
(core/patching/modules.py:106-109); here pipeline parallelism is first-class:
this module maps the scanned :class:`~maggy_tpu.models.Decoder` parameter tree
onto the uniform per-stage layout :func:`maggy_tpu.parallel.pipeline.
pipeline_grads_1f1b` wants — embedding ingested on stage 0 (``first_fn``),
``n_layers/n_stages`` decoder layers per stage (``stage_fn``), final norm +
LM head folded into the last stage's loss (``head_fn``).

Layout: every leaf of the stage tree carries a leading ``[n_stages]`` axis
sharded over the ``stage`` mesh axis, so each device holds one layer chunk
plus ONE copy of the embedding and head (the same per-device memory as
replicating them; only stage 0's embedding slice and the last stage's head
slice receive gradients — the others stay at their initial values and are
never read).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map

from maggy_tpu.models import sown
from maggy_tpu.models.transformer import (
    REMAT_POLICIES,
    Decoder,
    RMSNorm,
    _dense,
    _ScannedLayer,
    default_attention,
    flash_tileable,
    record_attention_kernel,
)


# gradient-overlap seam (docs/distributed.md "Gradient overlap & ZeRO"):
# pp-composed configs do NOT get per-stage bucketing yet — the 1F1B schedule
# already interleaves its stage collectives, and re-bucketing inside the
# stage shard_maps is future work. A zero_stage/bucket_mb request on a pp
# mesh (or any other overlap-ineligible geometry) lands here: one explicit
# process-wide warning, then the dense/pipeline path runs unchanged.
_overlap_fallback_warned = False


def warn_overlap_unbucketed(reason: str) -> None:
    """Warn once per process that a requested gradient-overlap config falls
    back to the unbucketed path; training proceeds unchanged."""
    global _overlap_fallback_warned
    if _overlap_fallback_warned:
        return
    _overlap_fallback_warned = True
    import warnings

    warnings.warn(
        f"gradient overlap disabled: {reason}; training continues on the "
        "unbucketed path",
        stacklevel=3,
    )


def _pp_local_attention(q, k, v, *, causal: bool = True, segment_ids=None):
    """Attention inside the pipeline's shard_map must be device-local (the
    stage/data/fsdp axes are manual): the single-device Pallas flash kernel
    on TPU when the geometry tiles onto the MXU, the XLA dense path
    otherwise — the same dispatch as auto_attention minus the mesh logic,
    recorded the same way."""
    from maggy_tpu.ops.flash import flash_attention  # late: import cycle

    why = flash_tileable(q.shape[1], k.shape[1], q.shape[3])
    if why is None and segment_ids is not None:
        why = "packed segments take the XLA path inside a pipeline stage"
    if why is None:
        record_attention_kernel("flash", q, k, segment_ids)
        return flash_attention(q, k, v, causal=causal)
    record_attention_kernel("xla_dense", q, k, segment_ids, why)
    return default_attention(q, k, v, causal=causal, segment_ids=segment_ids)


def _make_pp_tp_attention(tp: int):
    """Stage-local attention for pp x tp: a NESTED shard_map manual over the
    `tensor` axis (legal inside the pipeline's partial-manual region, where
    `tensor` is GSPMD-auto) splits the head axis so each tensor shard runs
    the single-device kernel — the Pallas flash path on TPU — on its own
    H/tp heads. Attention is embarrassingly parallel over heads, so there is
    no collective to insert and nothing for GSPMD to partition through an
    opaque custom call. Head-count divisibility (q AND GQA kv) is enforced
    by decoder_pipeline_parts before this is ever installed."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from maggy_tpu.parallel.spec import AXIS_TENSOR

    def attn(q, k, v, *, causal: bool = True, segment_ids=None):
        head_spec = P(None, None, AXIS_TENSOR, None)
        segmented = segment_ids is not None

        def local(q, k, v, seg):
            return _pp_local_attention(
                q, k, v, causal=causal, segment_ids=seg if segmented else None
            )

        seg_in = (
            segment_ids
            if segmented
            else jnp.zeros(q.shape[:2], jnp.int32)  # placeholder, never read
        )
        # mesh=None: inherit the CONTEXT mesh — inside the pipeline's
        # partial-manual region that is the abstract mesh with
        # stage/data/fsdp already Manual; passing the concrete Mesh there
        # is rejected ("context mesh should match")
        return shard_map(
            local,
            in_specs=(head_spec, head_spec, head_spec, P()),
            out_specs=head_spec,
            axis_names=frozenset({AXIS_TENSOR}),
            check_vma=False,
        )(q, k, v, seg_in)

    return attn


@dataclasses.dataclass(frozen=True)
class DecoderPipelineParts:
    """Everything the Trainer needs to run a Decoder under 1F1B."""

    n_stages: int
    layers_per_stage: int
    first_fn: Callable  # (stage_params, raw [mb,S] | [mb,S,3]) -> x [mb,S,D]
    stage_fn: Callable  # (stage_params, x, raw) -> x  (or (x, aux))
    head_fn: Callable   # (stage_params, x) -> logits [mb,S,V] fp32
    restack: Callable   # canonical decoder params -> stage-stacked tree
    unstack: Callable   # stage-stacked tree -> canonical decoder params
    # stage_fn returns (y, aux_scalar): per-stage router losses (MoE) join
    # the objective at each stage's backward tick
    stage_has_aux: bool = False
    # logical-axis names per stage-tree leaf ((None, ...canonical names) —
    # leading dim is the stage axis). The Trainer resolves these against its
    # rules to place tensor-parallel dims (attn heads / mlp hidden / vocab)
    # over the mesh's `tensor` axis inside each stage (pp x tp; the pipeline
    # shard_map stays manual over stage/data/fsdp and leaves `tensor` to
    # GSPMD). None for non-Decoder flows that build parts by hand.
    stage_names: Any = None


def decoder_pipeline_parts(
    model: Any, n_stages: int, tp: int = 1, mesh=None, ep: int = 1
) -> DecoderPipelineParts:
    """Build the 1F1B parts for a :class:`Decoder`.

    Raises loudly for anything the pipeline path cannot honor — a silently
    replicated stage axis is the failure mode this replaces."""
    from maggy_tpu.models.moe import MoEDecoder, _ScannedMoELayer

    is_moe = isinstance(model, MoEDecoder)
    if not isinstance(model, Decoder) and not is_moe:
        raise ValueError(
            "Pipeline parallelism (pp>1) currently supports the Decoder/"
            f"MoEDecoder families only, got {type(model).__name__}. Drop pp "
            "from the ShardingSpec or use parallel.pipeline primitives "
            "directly."
        )
    cfg = model.cfg
    if not cfg.scan_layers:
        raise ValueError("pp>1 needs scan_layers=True (stage chunks slice the scanned stack)")
    if cfg.decode:
        raise ValueError("pp>1 is a training path; decode=True has no pipeline support")
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={n_stages} stages"
        )
    if getattr(cfg, "ablated", None):
        raise ValueError(
            "pp>1 with cfg.ablated is not supported: the stage chunks would "
            "silently ignore the LOCO gates. Ablate without pipeline stages."
        )
    if getattr(cfg, "n_dense_layers", 0) or getattr(cfg, "mtp_depth", 0) or getattr(cfg, "experts_held", 0):
        raise ValueError(
            "pp>1 takes one kind of layer under one scan: a stack with leading "
            "dense layers, the multi-token-prediction module or the expert "
            "share form has no stage chunks yet"
        )
    if getattr(cfg, "stream_block", 0):
        raise ValueError(
            "pp>1 has no two-stream step: the block-diffusion objective (a clean "
            "and a noised stream, a noise key a step, targets the model weighs) "
            "runs on the dense step only"
        )
    if cfg.tie_embeddings:
        raise ValueError(
            "tie_embeddings=True is not supported with pp>1: the input "
            "embedding lives on stage 0 and the head on the last stage, and "
            "each would only receive its own partial gradient — the copies "
            "would silently untie. Use tie_embeddings=False under pp."
        )
    l_per = cfg.n_layers // n_stages
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp):
        raise ValueError(
            f"n_heads={cfg.n_heads} / n_kv_heads={cfg.n_kv_heads} not "
            f"divisible by tp={tp}: the stage-local attention shards BOTH "
            "head axes over the tensor mesh axis (GQA kv heads included)"
        )
    if ep > 1 and not is_moe:
        raise ValueError(
            f"ep={ep} under pp>1 needs an MoE model (got "
            f"{type(model).__name__}): a dense model has no expert dims, so "
            "the expert axis would silently replicate every stage param and "
            "waste ep-1 of every ep devices"
        )
    if ep > 1 and getattr(cfg, "n_experts", 0) % ep:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by ep={ep}: the "
            "expert axis would silently replicate instead of sharding the "
            "expert FFNs (pp x ep)"
        )
    # under pp x tp the stage body runs with the tensor axis in GSPMD-auto
    # mode; the Pallas flash kernel is an opaque custom call XLA cannot
    # partition over the sharded head axis, so a nested tensor-manual
    # shard_map splits heads explicitly and runs the single-device kernel
    # per shard (falls back to the GSPMD einsum path without a mesh)
    if tp > 1:
        # the nested map inherits the context mesh, but only Trainer-driven
        # flows guarantee one — bare parts built without a mesh keep GSPMD
        local_attn = (
            _make_pp_tp_attention(tp) if mesh is not None else default_attention
        )
    else:
        local_attn = _pp_local_attention
    if len(set(cfg.layer_kinds())) > 1:
        raise NotImplementedError("a pipeline stage scans layers of one kind: layer_types mixes them")
    stage_cfg = dataclasses.replace(
        cfg,
        n_layers=l_per,
        layer_types=cfg.layer_kinds()[:l_per] if cfg.layer_types else (),
        attention_fn=cfg.attention_fn or local_attn,
        # no logical-axis boxes inside the shard_map: placement is manual
        # (P('stage') on the stacked tree), and flax would otherwise try to
        # resolve names like 'embed' against the physical mesh mid-region
        partition_params=False,
    )

    layer_cls = _ScannedMoELayer if is_moe else _ScannedLayer
    if cfg.remat:
        layer_cls = nn.remat(
            layer_cls, prevent_cse=False, policy=REMAT_POLICIES[cfg.remat_policy]
        )
    chunk = nn.scan(
        layer_cls,
        variable_axes=(
            {"params": 0, "intermediates": 0} if is_moe else {"params": 0}
        ),
        split_rngs={"params": True},
        in_axes=nn.broadcast,
        length=l_per,
        metadata_params={nn.PARTITION_NAME: None},
    )(stage_cfg, stacked=l_per > 1)

    # raw microbatch layouts (decided per-trace by ndim/width): [mb, S]
    # plain tokens; [mb, S, 2] (tokens, positions); [mb, S, 3] (tokens,
    # positions, segment_ids) — the 1F1B stream is stage-replicated, so
    # every stage derives its side inputs from `raw` without widening the
    # activation hand-offs

    def first_fn(params, raw):
        tokens = raw[..., 0] if raw.ndim == 3 else raw
        return jnp.asarray(params["embedding"], cfg.dtype)[tokens]

    def _side_inputs(x, raw):
        if raw.ndim == 3:
            positions = raw[..., 1]
            segment_ids = raw[..., 2] if raw.shape[-1] >= 3 else None
        else:
            positions = jnp.broadcast_to(
                jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2]
            )
            segment_ids = None
        return positions, segment_ids

    if is_moe:
        def stage_fn(params, x, raw):
            positions, segment_ids = _side_inputs(x, raw)
            (y, _), mods = chunk.apply(
                {"params": params["layers"]}, x, positions, {}, segment_ids,
                mutable=["intermediates"],
            )  # {}: nothing rides the scan per layer (no gates, no bias)
            # this stage's router balancing losses (shared collection rule)
            return y, sown.collect_aux_losses(mods)
    else:
        def stage_fn(params, x, raw):
            positions, segment_ids = _side_inputs(x, raw)
            y, _ = chunk.apply(
                {"params": params["layers"]}, x, positions, segment_ids
            )
            return y

    # the head reuses the SAME modules as Decoder (single source of truth):
    # final_norm RMSNorm and the lm_head DenseGeneral applied functionally on
    # the stage-local param subtrees
    final_norm = RMSNorm(stage_cfg, name="final_norm")
    lm_head = _dense(cfg.vocab_size, ("embed", "vocab"), stage_cfg, "lm_head")

    def head_fn(params, x):
        xn = final_norm.apply({"params": params["final_norm"]}, x)
        logits = lm_head.apply({"params": params["lm_head"]}, xn)
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        return logits.astype(jnp.float32)

    def _bcast(p):
        import numpy as np

        if isinstance(p, np.ndarray):
            # host path (checkpoint re-staging): zero-copy view, never
            # n_stages materialized copies on the default device
            return np.broadcast_to(p[None], (n_stages,) + p.shape)
        return jnp.broadcast_to(p[None], (n_stages,) + p.shape)

    def restack(params):
        """Canonical (unboxed) Decoder params -> uniform stage tree."""
        out = {
            "embedding": _bcast(params["embedding"]),
            "layers": jax.tree.map(
                lambda p: p.reshape((n_stages, l_per) + p.shape[1:]),
                params["layers"],
            ),
            "final_norm": jax.tree.map(_bcast, params["final_norm"]),
            "lm_head": jax.tree.map(_bcast, params["lm_head"]),
        }
        return out

    def unstack(stage_params):
        """Stage tree -> canonical Decoder params (each leaf from its owning
        stage: embedding from 0, norm/head from -1), e.g. for checkpoint
        export into generate()/eval."""
        out = {
            "embedding": stage_params["embedding"][0],
            "layers": jax.tree.map(
                lambda p: p.reshape((n_stages * l_per,) + p.shape[2:]),
                stage_params["layers"],
            ),
            "final_norm": jax.tree.map(lambda p: p[-1], stage_params["final_norm"]),
            "lm_head": jax.tree.map(lambda p: p[-1], stage_params["lm_head"]),
        }
        return out

    # logical axes per stage leaf, for pp x tp placement: the canonical
    # model's own nn.Partitioned names (same source params_shardings reads on
    # the dense path), pushed through restack's layout — every stage leaf
    # gains a leading stage axis, so names gain a leading None. Only built
    # when a tensor axis is real: at tp=1 the resolution could only ever
    # return the plain P('stage') placement, so skip the extra abstract init
    stage_names = None
    if tp > 1 or ep > 1:
        pmodel = type(model)(dataclasses.replace(cfg, partition_params=True))
        abstract = jax.eval_shape(
            pmodel.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
        canonical_names = jax.tree.map(
            lambda l: tuple(l.names)
            if isinstance(l, nn.Partitioned)
            else (None,) * getattr(l, "ndim", 0),
            abstract["params"],
            is_leaf=lambda x: isinstance(x, nn.Partitioned),
        )
        stage_names = jax.tree.map(
            lambda n: (None,) + n,
            canonical_names,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    return DecoderPipelineParts(
        n_stages=n_stages,
        layers_per_stage=l_per,
        first_fn=first_fn,
        stage_fn=stage_fn,
        head_fn=head_fn,
        restack=restack,
        unstack=unstack,
        stage_has_aux=is_moe,
        stage_names=stage_names,
    )


def convert_pipeline_state(state, old_parts, new_parts):
    """Re-stage a pipeline TrainState across pp degrees (checkpoint
    portability, SURVEY §5.4): every stage-stacked tree in the state —
    params and the optax mirrors (adam mu/nu, ...) — goes through
    ``old_parts.unstack`` → ``new_parts.restack``; scalars (step, adam
    count) pass through. Run the result through the NEW Trainer's
    ``make_state``-born shardings — ``Trainer.adopt_state`` does both —
    before stepping."""
    pstruct = jax.tree_util.tree_structure(state.params)

    def is_param_tree(x):
        try:
            return jax.tree_util.tree_structure(x) == pstruct
        except Exception:
            return False

    def convert(x):
        if is_param_tree(x):
            return new_parts.restack(old_parts.unstack(x))
        return x

    new_params = convert(state.params)
    new_opt = jax.tree.map(convert, state.opt_state, is_leaf=is_param_tree)
    return state.replace(params=new_params, opt_state=new_opt)
