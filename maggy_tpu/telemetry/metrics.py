"""Checked-in metric-name registry.

Every ``gauge``/``counter``/``histogram``/``event``/``span`` name the codebase
emits must appear here — ``tools/check_telemetry_names.py`` (wired into tier-1)
walks ``maggy_tpu/`` and fails on any telemetry call whose literal name is
missing. The failure mode this kills: a typo'd name (``serve.ttft_m``)
silently splits a series into two, and every dashboard/percentile downstream
quietly reads half the data.

Keep this module import-light (stdlib only): the lint loads it by file path
without importing the package, so it must not pull jax or anything heavy.

Adding a metric = add the name here (grouped by kind, with a one-line
meaning) + emit it. Names are grouped per kind because a name may legally
be both a gauge (latest value, monitor panel) and a histogram (full
distribution, SSTATS percentiles) — ``serve.ttft_ms`` is.
"""

from __future__ import annotations

# gauges: latest-value signals (monitor panels, heartbeat snapshots)
GAUGES = frozenset(
    {
        # training loop (train/trainer.py)
        # a step's time on the device: the duration of its ``train.step_device``
        # span, from the later of the previous step's end and its own dispatch to
        # the moment its output was ready (fit's ``fit-steps`` thread)
        "step_time_ms",
        "compile_time_ms",  # a step whose call traced the program, synced to cover the XLA compile
        "steps_per_sec",
        "tokens_per_sec",
        "mfu_est",  # 6*params FLOPs estimate vs detected chip peak
        "metrics_lag",  # steps between a broadcast and its metric
        "metrics_drain_ms",  # host time in the lagged broadcast read
        # input pipeline (train/prefetch.py)
        "input_wait_ms",
        "prefetch_depth",
        # of the flash forward grid's tiles for a packed host batch, the share
        # the kernel visits (ops/flash.py tiles_visited_share; recorded per
        # batch in the prefetcher's thread by Trainer.fit)
        "attention.tiles_visited_share",
        # a model with sliding-window attention layers (models/transformer.py
        # Attention of kind sliding_attention): pairs inside window, document and
        # causal order over the causal pairs inside documents, the sliding layers'
        "attention.window_pairs_share",
        # a model with layers of chunk summaries (Attention of kind
        # eva_attention): the summaries over all the entries the step's real
        # queries see, and the chunks a document's start cuts over all chunks
        "attention.eva_remote_share",
        "attention.eva_chunks_cut_share",
        # a model that trains by diffusion over blocks (models/moe.py MoEDecoder
        # under block_diffusion): of the step's real tokens those its noise masked
        # (about a half), and the pairs the two streams' block-wise mask keeps over
        # one causal stream's pairs inside documents (about 2)
        "diffusion.masked_share",
        "attention.blockdiff_pairs_share",
        # an expert share model's step counters (models/moe.py
        # ExpertShareBlock; like every step counter below, gauged by Trainer.fit
        # from each step's output and an attribute of its ``train.step_device`` span)
        "moe.slots",  # (token, choice) slots on the experts this chip holds, all layers
        "moe.slots_dropped",  # of them, cut by a buffer: the layer is dropless, so 0
        "moe.load_max_over_mean",  # busiest held expert's slots over the mean one's, a mean over layers
        # rows of the layers' buffers the chunks that ran visited over the rows
        # the buffers hold (T * top_k a layer); 1.0 = the mechanism does nothing
        "moe.rows_visited_share",
        # rows of the layers' buffers one token-side sum reads (the tiles that hold a slot of a
        # block of tokens) over the T * top_k rows a gather a choice fetches; 1.0 = the gathers' traffic
        "moe.combine_rows_share",
        # ReLU-gated experts (MoEConfig.expert_act "relu"): of the hidden activations
        # relu(x W_gate) of the slots on held experts, the share exactly zero, a mean over layers
        "moe.hidden_zero_share",
        # a model with short-convolution layers (models/transformer.py
        # ShortConv): taps zeroed at row and document starts over all taps of
        # the step's conv layers; above 0 the batch's packing reached the operator
        "conv.taps_masked_share",
        # a model with Kimi-delta-attention layers (models/transformer.py KDA): the chunks of
        # the delta rule in which a document starts over all chunks (above 0 the batch's
        # packing reached the recurrence), and the mean of the log decays over tokens,
        # channels and layers (between the layer's floor and 0: how long the layers remember)
        "kda.chunks_cut_share",
        "kda.log_decay_mean",
        # a model with selected-key attention layers (models/transformer.py
        # Attention with sparse_topk, ops/sparse_select.py)
        "sparse.selected_share",  # pairs the selections keep over the pairs visible inside documents
        "sparse.rows_off_k",  # queries whose set is not min(sparse_topk, visible) keys: exact, so 0
        "sparse.index_loss",  # the indexer's KL loss, summed over the layers
        # control plane (core/rpc.py)
        "heartbeat_rtt_ms",
        # serving engine + scheduler (serve/)
        "serve.ttft_ms",
        "serve.tokens_per_sec",
        "serve.queue_depth",
        "serve.active_slots",
        "serve.drain_ms",
        "serve.decode_retraces",
        # paged KV cache (serve/paging/, docs/serving.md "Paged KV cache")
        "serve.pages_free",  # allocatable pages left in the pool
        "serve.pages_shared",  # pages aliased by >1 request (prefix reuse)
        # KV page heat + fragmentation (serve/paging/allocator.py heat
        # stamps; docs/observability.md "Capacity")
        "serve.pages_hot",  # pages accessed within the hot generation window
        "serve.pages_warm",  # pages idle past hot but inside warm
        "serve.pages_cold",  # pages idle past the warm window (eviction candidates)
        "serve.fragmentation",  # free-pool frag ratio (0=one run, ->1 shattered)
        # prefix residency (serve/prefix.py residency_stats)
        "serve.prefix_resident_bytes",  # KV bytes pinned by resident prompts
        "serve.prefix_resident_count",  # resident prompts in the prefix index
        # host-DRAM KV tier (serve/tier/, docs/serving.md "Host-DRAM page tier")
        "tier.host_pages_free",  # unallocated host pool pages
        "tier.host_pages_total",  # host pool capacity in pages
        "tier.host_bytes",  # bytes held by resident host packs
        "tier.resident_packs",  # spilled KV packs resident in host DRAM
        # device memory ledger (telemetry/memtrack.py; per-account gauges
        # ride the mem.account. dynamic prefix)
        "mem.hbm_used",  # reported device bytes in use (sim on CPU)
        "mem.hbm_free",  # pool limit minus used
        "mem.headroom_pct",  # free/limit — the autoscaler's capacity signal
        "mem.unattributed",  # reported-used minus the account sum
        # serving fleet (serve/fleet/)
        "fleet.healthy_replicas",
        "fleet.breaker_open",  # circuit breakers currently open (gray replicas)
        "fleet.brownout_level",  # degradation ladder position (0=normal..3=shed)
        # capacity loop (serve/fleet/autoscale.py; docs/fleet.md "Autoscaling")
        "fleet.replicas",  # fleet size (non-dead replicas, any role)
        "fleet.draining",  # replicas mid-retirement (no dispatch, still polled)
        "fleet.at_capacity",  # 1 while scale-out pressure is pinned at max_replicas
        "serve.handoff_ms",  # prefill->decode KV handoff latency
        # autotuner (tune/)
        "tune.candidates",
        "tune.pruned_oom",
        "tune.best_step_time",
        # gradient overlap + ZeRO (parallel/overlap.py, train/trainer.py;
        # docs/distributed.md "Gradient overlap & ZeRO")
        "train.bucket_count",  # gradient-reduction buckets in the compiled step
        # autopilot online controller (autopilot/controller.py)
        "autopilot.tick_ms",  # per-sample controller cost (≤2% budget)
        # elastic membership (resilience/membership.py, core/driver/distributed.py)
        "resilience.membership_epoch",  # current membership epoch
        "resilience.active_slices",  # slices currently in the data mesh
        "resilience.reshape_ms",  # epoch bump -> reshape barrier complete
    }
)

# counters: monotonic totals
COUNTERS = frozenset(
    {
        "trials_done",
        "trials_errored",
        "checkpoint_fallback",
        "serve.prefix_hits",
        "serve.prefix_tokens_saved",
        "serve.preemptions",  # paged-pool preemptions (request requeued, not failed)
        "fleet.shed",
        "fleet.quarantined",
        "fleet.requeued",
        "fleet.routed",
        # overload robustness (docs/fleet.md "QoS classes", docs/resilience.md
        # "Gray failure & circuit breakers")
        "fleet.brownout_clamped",  # best-effort dispatches with max_new clamped
        "fleet.retry_deferred",  # requeues delayed by an exhausted retry budget
        "fleet.breaker_opened",  # breaker transitions into OPEN (incl. re-opens)
        "fleet.breaker_closed",  # half-open probes that verified recovery
        "fleet.scale_events",  # autoscaler decisions applied (up + down)
        # per-QoS-class scheduler accounting (serve/scheduler.py); the class
        # tail is the closed qos set, spelled out so the lint sees every name
        "serve.qos.admitted.premium",
        "serve.qos.admitted.standard",
        "serve.qos.admitted.best_effort",
        "serve.qos.preempted.premium",
        "serve.qos.preempted.standard",
        "serve.qos.preempted.best_effort",
        "serve.qos.quota_deferred.premium",
        "serve.qos.quota_deferred.standard",
        "serve.qos.quota_deferred.best_effort",
        "resilience.auto_resumes",
        "resilience.preempt_saves",
        "resilience.worker_deaths",
        "resilience.workers_quarantined",
        "resilience.trials_requeued",
        "resilience.trials_exhausted",
        "resilience.dist_restarts",
        # elastic membership (docs/resilience.md "Elastic membership")
        "resilience.slice_drops",  # slices that left the data mesh
        "resilience.slice_rejoins",  # dropped slices re-admitted
        "resilience.reshape_checkpoints",  # graceful-reshape convergence saves
        "resilience.ckpt_reshards",  # restores re-placed across mesh layouts
        "resilience.ckpt_zero_reshards",  # optimizer states converted across zero layouts
        "tune.cache_hits",
        "tune.cache_misses",
        # the persistent compile cache's verdict on each backend compile it was
        # asked about (telemetry/recorder.py, from jax.monitoring): loaded, or
        # compiled anew (written back or not)
        "compile.cache_hits",
        "compile.cache_misses",
        "flightrec.dumps",  # stall watchdog dumps written (telemetry/flightrec.py)
        # series-only SLO attainment counters (telemetry/timeseries.py):
        # ingested into the time-series store from scheduler/router SLO
        # accounting, never emitted via tel.count — registered so alert
        # rules and metrics_query resolve them with units
        "serve.slo_ok",  # requests that met the TTFT SLO
        "serve.slo_miss",  # requests that missed the TTFT SLO
        # series-only headroom low-water tick counters (telemetry/memtrack.py):
        # the counter pair alert.hbm_headroom's multi-window burn reads
        "mem.headroom_ok",  # ledger ticks with headroom above the low-water mark
        "mem.headroom_miss",  # ledger ticks under it (capacity budget burning)
        "profcap.captures",  # alert-triggered profile captures written (telemetry/profcap.py)
        # host-DRAM KV tier (serve/tier/) + prefix-affinity routing
        # (serve/fleet/router.py; docs/fleet.md "Fleet-global KV")
        "tier.spills",  # streams spilled to the host tier (any kind)
        "tier.fills",  # host packs swapped back onto the device
        "tier.spilled_pages",  # KV pages copied device -> host
        "tier.filled_pages",  # KV pages copied host -> device
        "tier.prefix_spills",  # released prefixes captured as host packs
        "tier.prefix_fills",  # admissions served from a host prefix pack
        "tier.host_evictions",  # LRU packs dropped to make host room
        "tier.pressure_spills",  # spills forced by a low-headroom tick
        "tier.affinity_hits",  # routed to a replica holding the prefix
        "tier.affinity_misses",  # no holder available; routed affinity-blind
        # autopilot online controller (autopilot/controller.py)
        "autopilot.diagnoses",  # windows classified
        "autopilot.retunes",  # guarded moves committed
        "autopilot.rollbacks",  # guarded moves reverted on regression
    }
)

# histograms: fixed-log-bucket latency distributions (telemetry/histogram.py)
HISTOGRAMS = frozenset(
    {
        "serve.ttft_ms",  # submit -> first token
        "serve.tpot_ms",  # per-token decode time after the first
        "serve.queue_wait_ms",  # submit -> admission
        "serve.e2e_ms",  # submit -> terminal state
        "serve.drain_ms",  # async decode host drain
        "serve.handoff_ms",  # disaggregated prefill->decode handoff
        "tier.swap_in_ms",  # host pack fetch + device scatter on admit
        "tier.spill_ms",  # device gather + host pack write on spill
        "fleet.drain_ms",  # scale-in drain: dispatch stop -> replica retired
        "serve.itl_ms",  # gap between a request's consecutive tokens (Request.token_ts)
    }
)

# spans: timed blocks. Each also opens a ``jax.profiler.TraceAnnotation`` of
# the same name (telemetry/recorder.py), so inside a profiler session it lies
# on the host plane of the trace, on the device events' clock.
SPANS = frozenset(
    {
        # the stages of a program's compilation, as jax.monitoring reports them
        # on the calling thread (telemetry/recorder.py): journaled after the
        # fact, ``parent`` the program span whose call compiled, ``fun_name``
        # the program; nothing on the profiler's trace
        "compile.trace",  # Python to jaxpr (an inner jit's trace lies inside its caller's)
        "compile.lower",  # jaxpr to MLIR
        "compile.backend",  # XLA compile or cache load: ``cache`` hit/miss/off, ``cache_load_ms``
        # Trainer.make_state (train/trainer.py): shape inference for the
        # shardings, then the jitted init's compile and dispatch
        "train.make_state",
        # Trainer.fit step loop (train/trainer.py), one per phase
        "train.fit_setup",  # fit entry -> first step (resume, ledger, prefetcher)
        "train.input_wait",  # the loop thread's blocked pull of the next batch
        "shard_batch",  # host gather + H2D of one batch (prefetcher thread, or inline)
        "train_step",  # dispatch of the jitted step
        "train.drain",  # the loop thread waits for the device (metric reads, syncs)
        # fit's ``fit-steps`` thread, one pair a step: the live wait for the
        # step's output (on the profiler's trace too), and the step's time on the
        # device journaled after it, with ``step``, ``global_step``, ``compiled``,
        # ``tokens``, ``loss``, ``mtp_loss`` and the step counters as attributes
        "train.step_wait",
        "train.step_device",
        "train.checkpoint",  # Checkpointer.save called from the loop
        # checkpointing (train/checkpoint.py)
        "checkpoint_save",
        "checkpoint_restore",
        "checkpoint_restore_params",
        # Scheduler._loop_body (serve/scheduler.py), one per phase of an iteration
        "serve.sweep",  # cancel/deadline eviction + pending reconfigure
        "serve.admit",  # _admit_ready: queue pop -> prefill -> first token
        "serve.preempt",  # page growth check, in-flight drain, victim preemption
        "serve.emit",  # the token loop after a decode step (under the lock)
        "serve.idle_wait",  # no active slot: flush + wait for a submit
        "serve.tick",  # per-iteration gauges, ~1 Hz metrics tick + flush
        # Engine (serve/engine.py)
        "serve.prefill",
        "serve.prefix_admit",
        "serve.kv_admit",
        "serve.decode_step",
        "serve.spill",  # device -> host page copy-out (release / preempt victim)
        "serve.reconfigure",
        # experiment runtime (core/executors/, core/pod.py)
        "trial",
        "train_fn",
        "await_reservations",
        "build_context",
        "data_plane_init",
        # autotuner (tune/)
        "tune.static",
        "tune.measure",
    }
)

# device-side scopes: ``jax.named_scope`` names put on what is not a flax
# module (a module's own name is its scope: ``attn``, ``mlp``, ``attn_norm``,
# ``mlp_norm``, ``final_norm``, ``lm_head``, ``moe``, ``layers``). They reach
# the compiled HLO's ``op_name`` metadata and the profiler's device events;
# the compiled computation is unchanged. Recomputed operations carry
# ``REMAT_MARKER`` in ``op_name`` (what ``jax.checkpoint`` names the forward
# it replays in the backward pass).
SCOPES = (
    "loss",  # float32 logits -> log-softmax -> masked mean (train/trainer.py)
    "optimizer",  # optax update + apply, global gradient norm
    "grad_sync",  # bucketed / ZeRO gradient collectives (train/trainer.py overlap step)
    "moe.route",  # router logits, top-k, capacity positions, aux losses
    "moe.dispatch",  # tokens to expert buffers (one-hot, or sorted slots in the share form)
    "moe.experts",  # the experts' feed-forward matmuls
    "moe.combine",  # weighted gather back to tokens
    "moe.shared",  # the shared expert beside the routed ones (ExpertShareBlock)
    "moe.preroute",  # around moe.route and moe.dispatch where the router reads the layer's input, ahead of attention (MoELayer, route_from "layer_input")
    "mla.q",  # latent attention: query down-projection, norm, up-projection
    "mla.kv",  # latent attention: key-value down-projection, norm, up-projection
    "mla.rope",  # latent attention: rope on the narrow part, heads put together
    "conv.in_proj",  # short convolution: the product that makes the two gates and the input
    "conv.mix",  # short convolution: the gates and the taps between the two products (no product)
    "conv.out_proj",  # short convolution: the product back to the model's width
    "attn.gate",  # the per-head output gate: its projection, the sigmoid and the product (Attention and LatentAttention, attn_gate)
    "kda.in_proj",  # Kimi delta attention: the six products of the normed input (q, k, v, the decay's f, beta, the gate)
    "kda.conv",  # Kimi delta attention: the three streams' taps, SiLU, the unit norms of q and k
    "kda.gate",  # Kimi delta attention: the log decays a channel and beta a head, float32
    "kda.scan",  # Kimi delta attention: everything of ops/kda.py, forward, replay and backward (the chunks' products, the triangular inverse, the kernels kda_fwd and kda_bwd)
    "kda.out",  # Kimi delta attention: the head's norm, the gate a head, the product back to the model's width
    "sparse.index",  # selected-key attention: the indexer's projections, the index scores, the mask from the thresholds
    "sparse.select",  # selected-key attention: each query's top-k threshold
    "sparse.index_loss",  # selected-key attention: the indexer's loss and its gradient, one pass
    "eva.prep",  # chunk summaries: a chunk's keys against phi, the softmax, the two weighted sums, mu (Attention, eva_attention)
    "eva.local",  # chunk summaries: the flash kernels on the windows folded into rows, and their visit table (ops/eva.py)
    "eva.remote",  # chunk summaries: the flash kernels on the summaries under their selection, and their visit table
    "eva.merge",  # chunk summaries: the two calls' outputs joined by their log-sum-exp; the two dq added
    "diffusion.noise",  # block diffusion: the step's noise and [MASK], the two streams' assembly (MoEDecoder), a layer's bounds and pair counts (Attention)
    "diffusion.merge",  # block diffusion: the noised queries' own block, two band kernels that continue the flash call on the clean keys (own_block_fwd, own_block_bwd), and the tiles with halos XLA builds for them (ops/blockdiff.py)
    # (flax module names are scopes too and need no entry: attn, mlp, moe,
    # and mtp, the multi-token-prediction module)
    "decode_attn",  # page/chunk gather + online softmax over the KV cache
    "kv_write",  # this step's K/V written into the cache
    "sample",  # logits -> token (serve/engine.py)
)
REMAT_MARKER = "rematted_computation"

# lifecycle events: trace-correlated milestones (telemetry/tracing.py)
EVENTS = frozenset(
    {
        # serving request lifecycle (scheduler-side)
        "req.queued",
        "req.admitted",
        "req.prefix_admitted",
        "req.first_token",
        "req.finished",
        "req.preempted",  # pages freed, requeued ahead of fresh arrivals
        "req.preempted_for_priority",  # victim lost its pages to a higher class
        # router-side hops (serve/fleet/router.py)
        "req.accepted",
        "req.dispatched",
        "req.requeued",
        "req.shed",
        "req.completed",
        # disaggregated prefill/decode (serve/fleet/prefill.py, docs/fleet.md)
        "req.prefilled",  # prompt ran on a prefill replica
        "req.handoff",  # KV pack accepted by a decode replica
        # training runs (train/trainer.py)
        "train.run_start",
        "train.run_end",
        # which attention kernel the automatic dispatch took for a traced
        # shape, and why not flash (models/transformer.py auto_attention);
        # for the flash kernels their tiles, ``lanes`` and ``backward``
        # (``fused`` or ``split``: ops/flash.py backward_form), ``selected``,
        # the keys a query keeps, where a selection masks the call, and for a
        # two-stream layer ``own_block`` (``kernel`` or ``xla``: ops/blockdiff.py)
        "attention.kernel",
        # what a Kimi-delta-attention layer's recurrence runs as for a traced shape
        # (models/transformer.py KDA): ``chunk``, ``head_dim`` and ``form`` (``pallas``: the
        # kernels kda_fwd / kda_bwd; ``xla``: lax.scan over the same steps)
        "kda.kernel",
        # whether a step's head and loss run over whole logits or in blocks of
        # the sequence, and the block (models/head.py step_targets)
        "loss.blocks",
        # autopilot decisions (autopilot/controller.py, serve/scheduler.py):
        # the auditable telemetry→config loop — diagnosis verdicts, applied
        # moves, guarded commits, automatic rollbacks
        "autopilot.diagnosis",
        "autopilot.applied",
        "autopilot.committed",
        "autopilot.rollback",
        "autopilot.reconfigure_failed",
        # fleet autoscaler decision journal (serve/fleet/autoscale.py;
        # docs/fleet.md "Autoscaling") — the auditable capacity loop:
        # decisions, safe-event milestones, guarded commits, auto-reverts
        "fleet.scale.up",  # scale-out decided; spawn + warm started
        "fleet.scale.down",  # scale-in decided; victim drain started
        "fleet.scale.admitted",  # warmed replica entered probation dispatch
        "fleet.scale.retired",  # drained (or kill-fallback) replica removed
        "fleet.scale.committed",  # post-scale guard window held
        "fleet.scale.rollback",  # guard regressed; event auto-reverted
        "fleet.scale.guard_extended",  # regression explained by ongoing storm
        "fleet.scale.blocked",  # decision suppressed (at max / warm failed)
        # alert rule transitions (telemetry/alerts.py; the rule name rides
        # in the ``alert=`` attr and must exist in alerts.RULES — linted)
        "alert.firing",
        "alert.resolved",
    }
)

# f-string names whose literal head is one of these prefixes are legal
# (the tail is a bounded enum resolved at runtime: request terminal states,
# RPC verbs)
DYNAMIC_PREFIXES = (
    "serve.requests_",  # scheduler terminal-state counters
    "rpc_errors.",  # per-verb client failures (recorder.rpc)
    "rpc_frame_errors.",  # server frame hygiene (core/rpc.py)
    "serve.qos.",  # per-class tails resolved from the closed qos set
    "mem.account.",  # per-account ledger gauges (telemetry/memtrack.py)
)

BY_KIND = {
    "gauge": GAUGES,
    "count": COUNTERS,
    "histogram": HISTOGRAMS,
    "event": EVENTS,
    "span": SPANS,
}

ALL = GAUGES | COUNTERS | HISTOGRAMS | EVENTS | SPANS

# ---------------------------------------------------------------- units
# Every registered name carries a unit so downstream consumers (monitor
# sparklines, tools/metrics_query.py, the docs signal table) can label and
# scale values without guessing. The lint fails on any registered name
# missing from UNITS or carrying an unknown unit.
VALID_UNITS = frozenset({"ms", "count", "bytes", "ratio", "per_s"})

# counters and events are dimensionally counts; histograms are all latency
# distributions in ms, spans durations in ms. Gauges are mixed, so each is mapped explicitly —
# adding a gauge means adding its unit here too.
GAUGE_UNITS = {
    "step_time_ms": "ms",
    "compile_time_ms": "ms",
    "steps_per_sec": "per_s",
    "tokens_per_sec": "per_s",
    "mfu_est": "ratio",
    "metrics_lag": "count",
    "metrics_drain_ms": "ms",
    "input_wait_ms": "ms",
    "prefetch_depth": "count",
    "attention.tiles_visited_share": "ratio",
    "attention.window_pairs_share": "ratio",
    "attention.eva_remote_share": "ratio",
    "attention.eva_chunks_cut_share": "ratio",
    "diffusion.masked_share": "ratio",
    "attention.blockdiff_pairs_share": "ratio",
    "moe.slots": "count",
    "moe.slots_dropped": "count",
    "moe.load_max_over_mean": "ratio",
    "moe.rows_visited_share": "ratio",
    "moe.combine_rows_share": "ratio",
    "moe.hidden_zero_share": "ratio",
    "conv.taps_masked_share": "ratio",
    "kda.chunks_cut_share": "ratio",
    "kda.log_decay_mean": "ratio",
    "sparse.selected_share": "ratio",
    "sparse.rows_off_k": "count",
    "sparse.index_loss": "ratio",  # nats, like a loss: no unit of its own in the vocabulary
    "heartbeat_rtt_ms": "ms",
    "serve.ttft_ms": "ms",
    "serve.tokens_per_sec": "per_s",
    "serve.queue_depth": "count",
    "serve.active_slots": "count",
    "serve.drain_ms": "ms",
    "serve.decode_retraces": "count",
    "serve.pages_free": "count",
    "serve.pages_shared": "count",
    "serve.pages_hot": "count",
    "serve.pages_warm": "count",
    "serve.pages_cold": "count",
    "serve.fragmentation": "ratio",
    "serve.prefix_resident_bytes": "bytes",
    "serve.prefix_resident_count": "count",
    "tier.host_pages_free": "count",
    "tier.host_pages_total": "count",
    "tier.host_bytes": "bytes",
    "tier.resident_packs": "count",
    "mem.hbm_used": "bytes",
    "mem.hbm_free": "bytes",
    "mem.headroom_pct": "ratio",
    "mem.unattributed": "bytes",
    "fleet.healthy_replicas": "count",
    "fleet.breaker_open": "count",
    "fleet.brownout_level": "count",
    "fleet.replicas": "count",
    "fleet.draining": "count",
    "fleet.at_capacity": "count",
    "serve.handoff_ms": "ms",
    "tune.candidates": "count",
    "tune.pruned_oom": "count",
    "tune.best_step_time": "ms",
    "train.bucket_count": "count",
    "autopilot.tick_ms": "ms",
    "resilience.membership_epoch": "count",
    "resilience.active_slices": "count",
    "resilience.reshape_ms": "ms",
}

UNITS = {name: "count" for name in COUNTERS | EVENTS}
UNITS.update({name: "ms" for name in HISTOGRAMS | SPANS})
UNITS.update(GAUGE_UNITS)
