"""What a model hands back from a step beside its logits: the names its layers
sow into ``intermediates`` and what each becomes. A train step applies the
model with ``mutable=["intermediates"]`` and reads the result through here and
nowhere else: the dense step, the bucketed/ZeRO step and the pipeline's stage
adapter (``train/trainer.py``, ``train/pipeline_adapter.py``) and the tests.

The same seam carries an objective that is the model's own: the weights of the
positions' targets (:func:`target_weights`) and what ``apply`` takes from the
step's count (:func:`step_inputs`).

A step counter is written in three places: the layer's ``sow``, its row in
:data:`COUNTERS`, and the gauge's name and unit in ``telemetry/metrics.py``
(``docs/observability.md`` "Adding a step counter"). ``Trainer.step`` returns
every key of :func:`step_counters` and ``Trainer.fit`` records each under its
row's gauge name; neither names a layer.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def _leaves(mods, match: str) -> list:
    """The intermediates whose path holds ``match``, in the tree's order."""
    flat = jax.tree_util.tree_flatten_with_path(mods.get("intermediates", {}))[0]
    return [leaf for path, leaf in flat if match in jax.tree_util.keystr(path)]


def sown(mods, name: str) -> list:
    """Every intermediate a model sowed under ``name``, wherever in it."""
    return _leaves(mods, f"'{name}'")


def collect_aux_losses(mods) -> jax.Array:
    """Sum every ``*aux_loss`` intermediate a model sowed (MoE router
    balancing, the indexer's loss). THE one matching rule — every train step
    and the tests collect through here, so models that sow and trainers that
    collect cannot silently desync."""
    aux = jnp.zeros((), jnp.float32)
    for leaf in _leaves(mods, "aux_loss"):
        aux = aux + jnp.sum(leaf).astype(jnp.float32)
    return aux


def mtp_logits(mods) -> Optional[jax.Array]:
    """The further heads' logits of a model that sowed ``mtp_logits``
    (``MoEDecoder``: ``[B, S, vocab]``, the token two ahead; ``Decoder`` with
    ``pred_heads``: ``[B, S, heads - 1, vocab]``, head ``i`` the token
    ``i + 2`` ahead). ``None`` for every other model."""
    logits = sown(mods, "mtp_logits")
    return logits[0] if logits else None


def target_weights(mods) -> Optional[jax.Array]:
    """The objective's weight of each position's own token, float32 ``[B, L]``,
    of a model that sowed ``target_weights`` (``MoEDecoder`` under
    ``block_diffusion``: ``1 / t`` on the tokens the step masked, 0 elsewhere;
    its logits ``[B, L, vocab]`` predict position ``i``'s own token, with no
    shift). The train step then takes the weighted loss in the place of its
    ``loss_fn``. ``None`` for every other model."""
    weights = sown(mods, "target_weights")
    return weights[0] if weights else None


def step_inputs(model, step) -> Dict[str, jax.Array]:
    """What a model's ``apply`` takes in a train step beside the batch, from
    the optimizer step's count: whatever its configuration's ``step_inputs``
    says (``MoEConfig``: the step's noise key under ``block_diffusion``). Empty
    for every other model, whose program is then as it was."""
    make = getattr(getattr(model, "cfg", None), "step_inputs", None)
    return make(step) if make is not None else {}


def _stacked(leaves: list, width: int) -> jax.Array:
    """A row of ``width`` numbers a layer (scanned layers sow them stacked), all layers' rows."""
    return jnp.concatenate([a.reshape(-1, width) for a in leaves])


def _expert_load(load, dropped):
    load = jnp.concatenate([a.reshape(-1, a.shape[-1]) for a in load]).astype(jnp.float32)
    return {
        "moe_slots": load.sum(),
        "moe_slots_dropped": sum(jnp.sum(a) for a in dropped).astype(jnp.float32),
        "moe_load_max_over_mean": jnp.mean(load.max(-1) / jnp.maximum(load.mean(-1), 1.0)),
    }


def _summed_share(key: str):
    """[part, of] a layer -> the layers' parts over the layers' wholes, under ``key``."""
    def reduce(rows):
        part, of = _stacked(rows, 2).sum(0)
        return {key: part / of}

    return reduce


def _hidden_zeros(zeros):
    per_layer = _stacked(zeros, 2).astype(jnp.float32)
    return {"moe_hidden_zero_share": jnp.mean(per_layer[:, 0] / jnp.maximum(per_layer[:, 1], 1.0))}


def _index_loss(loss):
    return {"index_loss": sum(jnp.sum(a) for a in loss).astype(jnp.float32)}


def _sparse_counts(counts):
    selected, visible, off = _stacked(counts, 3).astype(jnp.float32).sum(0)
    return {"sparse_selected_share": selected / jnp.maximum(visible, 1.0), "sparse_rows_off_k": off}


def _window_pairs(pairs):
    inside, causal = _stacked(pairs, 2).astype(jnp.float32).sum(0)
    return {"window_pairs_share": inside / jnp.maximum(causal, 1.0)}


def _eva_counts(counts):
    remote, seen, cut, chunks = _stacked(counts, 4).astype(jnp.float32).sum(0)
    return {
        "eva_remote_share": remote / jnp.maximum(seen, 1.0), "eva_chunks_cut_share": cut / jnp.maximum(chunks, 1.0),
    }


def _diffusion_counts(masked, pairs):
    masked, real = _stacked(masked, 2).sum(0)
    kept, causal = _stacked(pairs, 2).astype(jnp.float32).sum(0)
    return {
        "diffusion_masked_share": masked / jnp.maximum(real, 1.0),
        "blockdiff_pairs_share": kept / jnp.maximum(causal, 1.0),
    }


def _kda_counts(counts):
    cut, chunks, decay, of = _stacked(counts, 4).astype(jnp.float32).sum(0)
    return {"kda_chunks_cut_share": cut / jnp.maximum(chunks, 1.0), "kda_log_decay_mean": decay / jnp.maximum(of, 1.0)}


class Counter(NamedTuple):
    """One row: the names a layer sows, what the step makes of every layer's
    leaves under them (one list a name, in order), and for each key of that in
    the step's output the gauge ``Trainer.fit`` records it under. A row reads
    nothing for a model that did not sow its first name."""

    names: Tuple[str, ...]
    reduce: Callable[..., Dict[str, jax.Array]]
    gauges: Dict[str, str]


COUNTERS: Tuple[Counter, ...] = (
    # ``ExpertShareBlock`` (models/moe.py): the (token, choice) slots on held experts, those a
    # buffer cut (dropless: 0), the busiest held expert's load over the mean one's, a mean over
    # the layers
    Counter(("expert_load", "slots_dropped"), _expert_load, {
        "moe_slots": "moe.slots", "moe_slots_dropped": "moe.slots_dropped",
        "moe_load_max_over_mean": "moe.load_max_over_mean",
    }),
    # ``ExpertShareBlock``, [visited, of] a layer: the rows of the layers' buffers that the chunks
    # that ran visited over the rows they hold (1.0: every layer worked through its whole buffer)
    Counter(("rows_visited",), _summed_share("moe_rows_visited_share"), {"moe_rows_visited_share": "moe.rows_visited_share"}),
    # ``ExpertShareBlock``, [read, of] a layer: the rows of the layers' buffers that one token-side
    # sum reads, in the tiles that hold a slot of a block of tokens (``moe.token_tiles``), over the
    # ``T * top_k`` rows that a gather a choice fetches (1.0: the gathers' traffic)
    Counter(("combine_rows",), _summed_share("moe_combine_rows_share"), {"moe_combine_rows_share": "moe.combine_rows_share"}),
    # ``ExpertShareBlock`` under ``expert_act="relu"``, [zeros, of] a layer: of the hidden
    # activations ``relu(x W_gate)`` of the slots on held experts those that are exactly zero (the
    # forward chunks' count), a mean over the layers: what a down product that skips zeros would save
    Counter(("hidden_zeros",), _hidden_zeros, {"moe_hidden_zero_share": "moe.hidden_zero_share"}),
    # ``ShortConv``, [masked, of] a layer: the taps zeroed at row and document starts over all
    # taps, which says that the batch's packing reached the operator
    Counter(("taps_masked",), _summed_share("conv_taps_masked_share"), {"conv_taps_masked_share": "conv.taps_masked_share"}),
    # ``KDA`` (models/transformer.py), [chunks with a document start inside, chunks, the sum of the
    # log decays ``a``, their count] a layer: the chunks of the delta rule that a document's start
    # cuts over all chunks, which says that the packing reached the recurrence, and the mean log
    # decay over tokens, channels and layers (between ``kda_decay_floor`` and 0: how long the
    # layers remember)
    Counter(("kda_counts",), _kda_counts, {
        "kda_chunks_cut_share": "kda.chunks_cut_share", "kda_log_decay_mean": "kda.log_decay_mean",
    }),
    # ``Attention`` with ``sparse_topk``: the indexer's loss summed over the layers
    Counter(("index_aux_loss",), _index_loss, {"index_loss": "sparse.index_loss"}),
    # ``Attention`` where it selects, [pairs selected, pairs visible, queries off their count] a
    # layer: the first over the second, and the queries whose set is not
    # ``min(sparse_topk, visible)`` keys (an exact selection: 0)
    Counter(("sparse_counts",), _sparse_counts, {
        "sparse_selected_share": "sparse.selected_share", "sparse_rows_off_k": "sparse.rows_off_k",
    }),
    # ``Attention`` of kind ``sliding_attention``, [pairs inside window, document and causal
    # order, causal pairs inside documents] a layer: how much of a full layer's attention the
    # window keeps on this batch
    Counter(("window_pairs",), _window_pairs, {"window_pairs_share": "attention.window_pairs_share"}),
    # ``Attention`` of kind ``eva_attention``, [summaries seen, all entries seen, chunks in which
    # two documents meet, chunks] a layer: the summaries over all the entries the real queries'
    # softmax runs over, and the chunks a document's start cuts over all chunks, which says that
    # the packing reached the summaries
    Counter(("eva_counts",), _eva_counts, {
        "eva_remote_share": "attention.eva_remote_share", "eva_chunks_cut_share": "attention.eva_chunks_cut_share",
    }),
    # ``MoEDecoder`` under ``block_diffusion``, [tokens masked, real tokens] of the step, and its
    # ``Attention`` layers, [pairs the block-wise mask keeps (clean on clean, noised on clean,
    # noised on its own block), one causal stream's pairs inside documents] a layer: the share of
    # the real tokens the step's noise masked (about a half: the level is uniform), and what the
    # two streams' attention computes over what a causal step would (about 2)
    Counter(("diffusion_masked", "blockdiff_pairs"), _diffusion_counts, {
        "diffusion_masked_share": "diffusion.masked_share", "blockdiff_pairs_share": "attention.blockdiff_pairs_share",
    }),
)


def step_counters(mods) -> Dict[str, jax.Array]:
    """The step's counters of whatever layers the model has: every row of
    :data:`COUNTERS` whose names it sowed, reduced over its layers. Empty for
    a model that sows none."""
    out: Dict[str, jax.Array] = {}
    for row in COUNTERS:
        leaves = [sown(mods, name) for name in row.names]
        if leaves[0]:
            out.update(row.reduce(*leaves))
    return out
