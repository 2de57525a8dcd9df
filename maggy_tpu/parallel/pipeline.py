"""Pipeline parallelism over the ``stage`` mesh axis: GPipe + 1F1B.

The reference explicitly rejects pipeline modules (core/patching/modules.py:
106-109 asserts against DeepSpeed PipelineModule); SURVEY.md §2.10 marks PP a
stretch goal. This is the TPU-native version: layer stages live on different
devices along the ``stage`` mesh axis, activations flow stage→stage via
``ppermute`` (point-to-point — DCN-friendly, hence the axis sits outermost in
MESH_AXES), and microbatches keep every stage busy after the fill phase.

Two schedules:

* :func:`pipeline_apply` — classic GPipe: with S stages and M microbatches the
  loop runs M + S - 1 ticks; at tick t stage s processes microbatch t - s.
  Backward flows through the same schedule by autodiff (ppermute's transpose
  is the reverse permute), so one ``jax.grad`` trains the pipeline — but the
  scan's autodiff residuals grow with the tick count × carry size.
* :func:`pipeline_grads_1f1b` — an explicit one-forward-one-backward training
  schedule (PipeDream-flush order) with per-microbatch rematerialisation:
  each stage keeps only its in-flight stage *inputs* (an S+1-slot ring
  buffer) and re-linearises at backward time, so activation memory is O(S)
  per stage instead of O(M) — the long-context setting. Closed-form SPMD
  clock, derivable from the dependency chain: backward of microbatch m at
  stage s fires at tick ``2S-1-s+2m``; its forward at ``s+m`` during warmup
  (m ≤ S-1-s) and ``2m+s`` in steady state. Each stage performs at most one
  op per tick (fwd/bwd tick parities are opposite), activation hand-offs are
  buffered in the ring, and gradient hand-offs always arrive exactly one
  tick before their consumer — so a single carried buffer suffices.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from maggy_tpu.parallel.spec import AXIS_DATA, AXIS_FSDP, AXIS_STAGE


def _manual_axes(mesh, axis_name) -> frozenset:
    """The pipeline shard_maps are manual over stage (ppermute hand-offs) and
    data/fsdp (explicit grad/loss psums) ONLY; every other mesh axis —
    `tensor` being the live case (pp x tp) — stays in GSPMD-auto mode, so a
    stage body whose params carry tensor-sharded dims (attn heads / mlp
    hidden / vocab) is tensor-parallelized by XLA inside each stage.

    When every would-be-auto axis is trivial (extent 1) this returns ALL
    mesh axes (full-manual): jax 0.9's partial-manual mode rejects EAGER
    calls on any mesh that has non-manual axes, and full-manual is
    semantically identical there — so eager pipeline_apply keeps working on
    plain pp x dp meshes, and the partial-manual path (always reached
    through the Trainer's jit) engages only when tp/sp/ep is real."""
    manual = frozenset({axis_name, AXIS_DATA, AXIS_FSDP}) & frozenset(
        mesh.axis_names
    )
    shape = dict(mesh.shape)
    if all(shape[a] == 1 for a in mesh.axis_names if a not in manual):
        return frozenset(mesh.axis_names)
    return manual


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    *,
    mesh,
    axis_name: str = AXIS_STAGE,
    out_mode: str = "replicated",
):
    """Run a layer pipeline over the mesh's ``stage`` axis.

    :param stage_fn: ``fn(params_for_one_stage, x) -> y`` — one stage's compute
        (e.g. a scan over its layer chunk). Must keep the activation shape.
    :param stage_params: pytree whose leaves have a leading ``[n_stages]`` axis
        (sharded over ``stage``) — build with :func:`stack_stage_params`.
    :param microbatches: ``[n_micro, mb, ...]`` activations; the ``mb`` axis is
        sharded over (data, fsdp), so a pp x dp mesh pipelines AND
        data-parallelizes (each dp replica pipelines its batch slice).
    :param out_mode: ``"replicated"`` all-reduces the full output buffer so
        every stage holds it (API-compatible default); ``"scatter"`` instead
        reduce-scatters the ``n_micro`` axis over stages — ~2x less interconnect
        traffic, right when the consumer (a loss) reduces anyway. Requires
        ``n_micro % n_stages == 0``.
    :returns: ``[n_micro, mb, ...]`` outputs of the final stage
        (``[n_micro / n_stages, mb, ...]`` per stage for ``"scatter"``).
    """
    if out_mode not in ("replicated", "scatter"):
        raise ValueError(f"out_mode must be 'replicated' or 'scatter', got {out_mode!r}")
    n_stages = mesh.shape[axis_name]
    if n_stages == 1:
        return jax.vmap(lambda x: stage_fn(jax.tree.map(lambda p: p[0], stage_params), x))(
            microbatches
        )
    n_micro = microbatches.shape[0]
    if n_micro < n_stages:
        raise ValueError(
            f"Need at least as many microbatches ({n_micro}) as stages "
            f"({n_stages}) to fill the pipeline."
        )

    def local(params, mb):
        # params leaves: [1, ...] local stage shard; mb: [n_micro, mb, ...]
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis_name)
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            incoming, outputs = carry
            # stage 0 ingests microbatch t (clamped; masked out later)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x0 = jax.lax.dynamic_index_in_dim(mb, mb_idx, keepdims=False)
            x = jnp.where(stage == 0, x0, incoming)
            y = stage_fn(params, x)
            # last stage writes its result for microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, jax.lax.dynamic_index_in_dim(outputs, out_idx, keepdims=False)),
                out_idx, 0,
            )
            nxt = jax.lax.ppermute(y, axis_name, fwd)
            return (nxt, updated), None

        init = (jnp.zeros_like(mb[0]), jnp.zeros_like(mb))
        (_, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(n_micro + n_stages - 1)
        )
        # only the last stage holds real outputs
        outputs = jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))
        if out_mode == "scatter":
            # reduce-scatter the micro axis over stages: each stage keeps its
            # n_micro/S chunk instead of an all-reduced full buffer
            return jax.lax.psum_scatter(
                outputs, axis_name, scatter_dimension=0, tiled=True
            )
        return jax.lax.psum(outputs, axis_name)

    batch_spec = P(None, (AXIS_DATA, AXIS_FSDP))
    if out_mode == "scatter":
        if n_stages > 1 and n_micro % n_stages:
            raise ValueError(
                f"out_mode='scatter' needs n_micro ({n_micro}) divisible by "
                f"stages ({n_stages})"
            )
        out_spec = P(axis_name, (AXIS_DATA, AXIS_FSDP))
    else:
        out_spec = batch_spec
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), batch_spec),
        out_specs=out_spec,
        axis_names=_manual_axes(mesh, axis_name),
        check_vma=False,
    )(stage_params, microbatches)


def pipeline_grads_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    targets,
    *,
    mesh,
    axis_name: str = AXIS_STAGE,
    first_fn: Optional[Callable] = None,
    stage_takes_raw: bool = False,
    stage_has_aux: bool = False,
):
    """One training step with the 1F1B schedule: returns ``(loss, grads)``.

    :param stage_fn: ``fn(params_for_one_stage, x) -> y``, activation-shape
        and dtype preserving — or ``fn(params, x, raw)`` with
        ``stage_takes_raw=True``: every stage also receives the CURRENT
        microbatch's raw rows from the (stage-replicated) stream, so
        side-channel inputs every layer needs — packed-sequence segment ids,
        per-segment positions — reach all stages without flowing through the
        activation hand-offs.
    :param loss_fn: ``fn(params_for_one_stage, y_final, target) -> scalar`` —
        mean loss of ONE microbatch, computed on the last stage only (no
        output buffer ever forms, let alone gets broadcast). Taking the stage
        params lets a language-model head (final norm + lm_head) live inside
        the loss so its gradients flow on the last stage.
    :param stage_params: leaves ``[n_stages, ...]`` (see
        :func:`stack_stage_params`). The tree must be UNIFORM across stages;
        params used by one stage only (embedding on stage 0, head on the last)
        simply receive zero gradient contributions elsewhere.
    :param microbatches: ``[n_micro, mb, ...]``; ``targets`` any pytree of
        ``[n_micro, ...]`` leaves consumed by ``loss_fn``.
    :param first_fn: optional ``fn(params_for_one_stage, raw_microbatch) -> x``
        applied by stage 0 to turn a raw microbatch (e.g. int token ids) into
        the pipeline's activation dtype/shape — the embedding lookup of a
        language model. Differentiated together with stage 0's chunk, so
        embedding gradients come out in stage 0's param grads. When None the
        microbatches themselves must already be activations.
    :param stage_has_aux: the stage function returns ``(y, aux_scalar)`` —
        a per-stage auxiliary loss (MoE router balancing). Each stage's aux
        joins the objective at ITS OWN backward tick: the VJP is pulled with
        cotangent ``(g, 1.0)`` so aux gradients land in that stage's param
        grads. The return gains a third element: ``(loss, grads, aux)`` with
        ``loss`` the DATA loss and ``aux`` the summed auxiliary term (both
        microbatch means) — the optimized objective is their sum.
    :returns: ``loss`` — mean over all microbatches (replicated), and
        ``grads`` — same structure/sharding as ``stage_params``.

    Memory: each stage stores its in-flight stage inputs in an (S+1)-slot
    ring and re-linearises (recompute + VJP) at its backward tick — O(S)
    activations per stage versus GPipe-autodiff's O(ticks) scan residuals.
    With ``first_fn``, the ring stores raw-microbatch-derived activations for
    stage 0 implicitly: stage 0 re-reads the (cheap, int) microbatch stream at
    backward time and recomputes the embedding inside its VJP.
    """
    if first_fn is None:
        first_fn = lambda params, raw: raw  # noqa: E731 - identity ingest
    base_stage = (
        stage_fn if stage_takes_raw else (lambda p, x, raw: stage_fn(p, x))
    )
    if stage_has_aux:
        run_stage = base_stage  # already (y, aux)
    else:
        run_stage = lambda p, x, raw: (base_stage(p, x, raw), jnp.float32(0))  # noqa: E731
    S = mesh.shape[axis_name]
    M = microbatches.shape[0]
    if S == 1:
        def loss_all(params):
            p0 = jax.tree.map(lambda q: q[0], params)

            def one(x, t):
                y, aux = run_stage(p0, first_fn(p0, x), x)
                return loss_fn(p0, y, t), aux

            data, aux = jax.vmap(one)(microbatches, targets)
            return data.mean() + aux.mean(), (data.mean(), aux.mean())

        (_, (data, aux)), grads = jax.value_and_grad(loss_all, has_aux=True)(
            stage_params
        )
        if stage_has_aux:
            return data, grads, aux
        return data, grads
    if M < S:
        raise ValueError(
            f"Need at least as many microbatches ({M}) as stages ({S})."
        )
    RING = S + 1  # in-flight inputs per stage are bounded by S (see proof in tests)
    T = 2 * M + 2 * S - 2

    def local(params, mbs, tgts):
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis_name)
        is_last = stage == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]
        # activation shape/dtype comes from first_fn's output, not the raw
        # microbatch stream (they differ when first_fn embeds token ids)
        act = jax.eval_shape(
            first_fn, params, jax.ShapeDtypeStruct(mbs.shape[1:], mbs.dtype)
        )
        zeros_mb = jnp.zeros(act.shape, act.dtype)
        zero_dp = jax.tree.map(jnp.zeros_like, params)

        def ingest(p, raw, x_ring):
            """Stage 0 turns the raw microbatch into an activation; everyone
            else reads the ring. Both branches are computed and where-selected
            (first_fn is a cheap gather), which keeps the select differentiable
            so embedding grads appear exactly on stage 0."""
            return jnp.where(stage == 0, first_fn(p, raw), x_ring)

        def fwd_micro(t, s):
            """Which microbatch (if any) stage s forwards at tick t."""
            warm = t - s
            is_warm = (warm >= 0) & (warm <= S - 1 - s) & (warm < M)
            bey = (t - s) // 2
            is_bey = (
                ((t - s) >= 0)
                & ((t - s) % 2 == 0)
                & (bey > S - 1 - s)
                & (bey < M)
            )
            return jnp.where(is_warm, warm, bey), is_warm | is_bey

        def bwd_micro(t, s):
            tb = t - (2 * S - 1 - s)
            return tb // 2, (tb >= 0) & (tb % 2 == 0) & (tb // 2 < M)

        def tick(carry, t):
            xbuf, y_recv, g_recv, grad_acc, loss_acc, aux_acc = carry

            # 1. bank last tick's arriving activation into the ring
            m_arr, ok_arr = fwd_micro(t - 1, stage - 1)
            ok_arr = ok_arr & (stage > 0) & (t > 0)
            slot = jnp.clip(m_arr, 0, M - 1) % RING
            xbuf = jnp.where(
                ok_arr,
                jax.lax.dynamic_update_index_in_dim(xbuf, y_recv, slot, 0),
                xbuf,
            )

            # 2. forward op (at most one per tick); the last stage's forward
            # output has no consumer (no fwd_perm edge out, and its backward
            # recomputes inside the vjp), so skip it there
            m_f, do_f = fwd_micro(t, stage)
            do_f = do_f & ~is_last
            mf = jnp.clip(m_f, 0, M - 1)
            raw_f = jax.lax.dynamic_index_in_dim(mbs, mf, keepdims=False)
            ring_f = jax.lax.dynamic_index_in_dim(xbuf, mf % RING, keepdims=False)
            y = jax.lax.cond(
                do_f,
                lambda raw, xr: run_stage(params, ingest(params, raw, xr), raw)[0],
                lambda raw, xr: zeros_mb,
                raw_f, ring_f,
            )

            # 3. backward op: re-linearise from the saved stage input (stage 0
            # re-reads the raw microbatch stream and re-embeds inside its VJP)
            m_b, do_b = bwd_micro(t, stage)
            mb_ = jnp.clip(m_b, 0, M - 1)
            raw_b = jax.lax.dynamic_index_in_dim(mbs, mb_, keepdims=False)
            ring_b = jax.lax.dynamic_index_in_dim(xbuf, mb_ % RING, keepdims=False)
            tgt = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, mb_, keepdims=False),
                tgts,
            )

            def run_bwd(raw, xr, g):
                def last_fn(raw, xr, g):
                    def full(p, x):
                        y, aux = run_stage(p, ingest(p, raw, x), raw)
                        return loss_fn(p, y, tgt), aux

                    (lval, aux), pull = jax.vjp(full, params, xr)
                    # both outputs get cotangent 1: loss + aux is the
                    # optimized objective; they stay split for reporting
                    dp, dx = pull((jnp.ones_like(lval), jnp.ones_like(aux)))
                    return dp, dx, lval.astype(jnp.float32), aux.astype(jnp.float32)

                def mid_fn(raw, xr, g):
                    (yv, aux), pull = jax.vjp(
                        lambda p, x: run_stage(p, ingest(p, raw, x), raw),
                        params, xr,
                    )
                    # cotangent 1.0 on the aux output: this stage's router
                    # losses reach its param grads right here
                    dp, dx = pull((g.astype(yv.dtype), jnp.ones_like(aux)))
                    return dp, dx, jnp.float32(0), aux.astype(jnp.float32)

                return jax.lax.cond(is_last, last_fn, mid_fn, raw, xr, g)

            def skip_bwd(raw, xr, g):
                return zero_dp, zeros_mb, jnp.float32(0), jnp.float32(0)

            dp, dx, lval, aval = jax.lax.cond(
                do_b, run_bwd, skip_bwd, raw_b, ring_b, g_recv
            )
            grad_acc = jax.tree.map(lambda a, d: a + d, grad_acc, dp)
            loss_acc = loss_acc + lval
            aux_acc = aux_acc + aval

            # 4. hand off: activations forward, gradients backward
            y_next = jax.lax.ppermute(y, axis_name, fwd_perm)
            g_next = jax.lax.ppermute(dx, axis_name, bwd_perm)
            return (xbuf, y_next, g_next, grad_acc, loss_acc, aux_acc), None

        init = (
            jnp.zeros((RING,) + act.shape, act.dtype),
            zeros_mb,
            zeros_mb,
            zero_dp,
            jnp.float32(0),
            jnp.float32(0),
        )
        (_, _, _, grad_acc, loss_acc, aux_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(T)
        )

        # data-parallel mean over (data, fsdp) replicas, micro mean over M.
        # the stage psums are load-bearing SUMS, not broadcasts: the data
        # loss sits on the last stage, but every stage contributes its own
        # aux at its backward ticks
        dpf = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
        grads = jax.tree.map(
            lambda g: (
                jax.lax.psum(g, (AXIS_DATA, AXIS_FSDP)) / (dpf * M)
            )[None],
            grad_acc,
        )

        def reduce_scalar(v):
            v = jax.lax.psum(v, axis_name)
            return jax.lax.psum(v, (AXIS_DATA, AXIS_FSDP)) / (dpf * M)

        return reduce_scalar(loss_acc), grads, reduce_scalar(aux_acc)

    batch_spec = P(None, (AXIS_DATA, AXIS_FSDP))
    loss, grads, aux = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), batch_spec, batch_spec),
        out_specs=(P(), P(axis_name), P()),
        axis_names=_manual_axes(mesh, axis_name),
        check_vma=False,
    )(stage_params, microbatches, targets)
    if stage_has_aux:
        return loss, grads, aux
    return loss, grads


def pipeline_forward_loss(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    targets,
    *,
    mesh,
    axis_name: str = AXIS_STAGE,
    first_fn: Optional[Callable] = None,
    stage_takes_raw: bool = False,
    stage_has_aux: bool = False,
):
    """Forward-only GPipe sweep returning ``(loss, aux)`` microbatch means —
    the EVAL counterpart of :func:`pipeline_grads_1f1b`:
    per-device live state is one stage's params plus a single microbatch
    activation, instead of unstacking the whole model replicated on every
    device (which OOMs exactly in the regime pipeline parallelism exists
    for). Same stage_fn/loss_fn/first_fn contracts as the 1F1B schedule;
    no gradients, no activation ring — M + S - 1 ticks."""
    if first_fn is None:
        first_fn = lambda params, raw: raw  # noqa: E731 - identity ingest
    base_stage = (
        stage_fn if stage_takes_raw else (lambda p, x, raw: stage_fn(p, x))
    )
    if stage_has_aux:
        run_stage = base_stage
    else:
        run_stage = lambda p, x, raw: (base_stage(p, x, raw), jnp.float32(0))  # noqa: E731
    S = mesh.shape[axis_name]
    M = microbatches.shape[0]
    if S == 1:
        def one(params):
            p0 = jax.tree.map(lambda q: q[0], params)

            def per_micro(x, t):
                y, aux = run_stage(p0, first_fn(p0, x), x)
                return loss_fn(p0, y, t), aux

            data, aux = jax.vmap(per_micro)(microbatches, targets)
            return data.mean(), aux.mean()

        return one(stage_params)

    def local(params, mbs, tgts):
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis_name)
        is_last = stage == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        act = jax.eval_shape(
            first_fn, params, jax.ShapeDtypeStruct(mbs.shape[1:], mbs.dtype)
        )
        zeros_mb = jnp.zeros(act.shape, act.dtype)

        def tick(carry, t):
            y_recv, loss_acc, aux_acc = carry
            m = jnp.clip(t - stage, 0, M - 1)
            do = ((t - stage) >= 0) & ((t - stage) < M)
            raw = jax.lax.dynamic_index_in_dim(mbs, m, keepdims=False)
            x = jnp.where(stage == 0, first_fn(params, raw), y_recv)

            def run(raw, x):
                y, aux = run_stage(params, x, raw)
                tgt = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, m, keepdims=False),
                    tgts,
                )
                lval = jax.lax.cond(
                    is_last,
                    lambda: loss_fn(params, y, tgt).astype(jnp.float32),
                    lambda: jnp.float32(0),
                )
                return y, lval, aux.astype(jnp.float32)

            def skip(raw, x):
                return zeros_mb, jnp.float32(0), jnp.float32(0)

            y, lval, aval = jax.lax.cond(do, run, skip, raw, x)
            y_next = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (y_next, loss_acc + lval, aux_acc + aval), None

        init = (zeros_mb, jnp.float32(0), jnp.float32(0))
        (_, loss_acc, aux_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(M + S - 1)
        )
        dpf = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]

        def reduce_scalar(v):
            v = jax.lax.psum(v, axis_name)
            return jax.lax.psum(v, (AXIS_DATA, AXIS_FSDP)) / (dpf * M)

        return reduce_scalar(loss_acc), reduce_scalar(aux_acc)

    batch_spec = P(None, (AXIS_DATA, AXIS_FSDP))
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), batch_spec, batch_spec),
        out_specs=(P(), P()),
        axis_names=_manual_axes(mesh, axis_name),
        check_vma=False,
    )(stage_params, microbatches, targets)


def stack_stage_params(per_layer_params, n_stages: int):
    """Reshape layer-stacked params ``[L, ...]`` into ``[n_stages, L//n_stages,
    ...]`` for :func:`pipeline_apply` (shard the leading axis over 'stage')."""

    def reshape(p):
        L = p.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])

    return jax.tree.map(reshape, per_layer_params)
