"""The head's product and the loss as one operation over blocks of the sequence.

A language model's three objectives here, the next token (``ahead`` 1), a
further head's (``ahead`` 2 and more) and a model's own weighted targets (no
shift), are all ``-(sum_i w_i * log_softmax(h_i @ W)[t_i])`` with the targets
``t`` and the weights ``w`` (the mask over the count of what it keeps) known
before the head runs. So the targets and the mask are shifted, never the
logits (:func:`next_token`, :func:`own_token`), and :func:`loss` goes from
hidden states, the head's kernel, targets and weights to the loss: over whole
logits where they are small, and where they are not (:func:`block_rows`) in a
loop over blocks of positions, so that the float32 logits, their log-softmax
and their cotangent exist for one block at a time. A block's numbers are
rounded where whole logits' are: the product's result in the model's dtype,
then float32.

A train step asks :func:`step_targets` what the model's ``apply`` takes beside
the batch (as ``sown.step_inputs``); a model that is handed ``targets`` returns
its heads' losses ``[heads]`` in the place of logits. With nothing handed, and
for every other caller, a model returns logits as before.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu.parallel.sharding import DEFAULT_RULES, mesh_extent
from maggy_tpu.parallel.spec import AXIS_SEQ, AXIS_SLICE, AXIS_TENSOR

# Where the float32 logits of a step's heads together are at most this many
# bytes a device they are made whole, and the loss is autodiff's over them;
# larger ones are made in blocks of the sequence whose float32 logits are at
# most BLOCK_LOGITS_BYTES (one head's block exists at a time). Both from PR
# 47's sizing on a v5e (PERF.md section 6).
WHOLE_LOGITS_BYTES = 3 << 29
BLOCK_LOGITS_BYTES = WHOLE_LOGITS_BYTES // 2


class Targets(NamedTuple):
    """What a model's head takes to return losses in the place of logits: a
    row a head, ``ids`` int32 and ``weights`` float32 ``[heads, B, S]`` (each
    head's weights already over its count), and the rows of the sequence a
    block holds (static)."""

    ids: jax.Array
    weights: jax.Array
    rows: int


def kept_targets(batch: Dict[str, jax.Array], ahead: int) -> Optional[jax.Array]:
    """Which of the targets ``ahead`` of their predictors count, float32
    ``[B, S - ahead]``: those whose ``loss_mask`` is set and that lie in the
    predictor's segment (segments are runs, so every token between them does
    too). ``None``: the batch has neither key and every target counts."""
    kept = batch.get("loss_mask")
    kept = None if kept is None else kept[:, ahead:].astype(jnp.float32)
    seg = batch.get("segment_ids")
    if seg is not None:
        same = (seg[:, ahead:] == seg[:, :-ahead]).astype(jnp.float32)
        kept = same if kept is None else kept * same
    return kept


def real_tokens(batch: Dict[str, jax.Array]) -> Optional[jax.Array]:
    """The batch's real tokens ``[B, S]``: its ``loss_mask``, else the segment
    ids above 0. ``None``: the batch has neither key and every token is real."""
    real = batch.get("loss_mask")
    if real is None and batch.get("segment_ids") is not None:
        real = batch["segment_ids"] > 0
    return real


def next_token(batch: Dict[str, jax.Array], ahead: int = 1) -> Tuple[jax.Array, jax.Array]:
    """``(ids, weights)`` ``[B, S]`` of the objective in which position ``i``
    predicts token ``i + ahead``: the targets that count
    (:func:`kept_targets`) over their number; the row's last ``ahead``
    positions have no target and weigh 0."""
    targets = batch["tokens"][:, ahead:]
    kept = kept_targets(batch, ahead)
    if kept is None:
        kept, count = jnp.ones(targets.shape, jnp.float32), jnp.float32(targets.size)
    else:
        count = kept.sum()
    tail = ((0, 0), (0, ahead))
    return jnp.pad(targets, tail), jnp.pad(kept / jnp.maximum(count, 1.0), tail)


def own_token(batch: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """``(ids, weights)`` ``[B, S]`` of an objective whose position ``i``
    predicts token ``i`` itself and whose model weighs the targets: the real
    tokens (:func:`real_tokens`) over their number; the model multiplies its
    own weights in."""
    tokens, real = batch["tokens"], real_tokens(batch)
    if real is None:
        return tokens, jnp.full(tokens.shape, 1.0 / tokens.size, jnp.float32)
    real = real.astype(jnp.float32)
    return tokens, real / jnp.maximum(real.sum(), 1.0)


def block_rows(batch: int, seq: int, vocab: int, heads: int = 1, mesh=None) -> int:
    """The rows of the sequence a block of head and loss holds, from the
    float32 logits' bytes a device: ``seq``, one block, where the ``heads``
    heads' whole logits fit ``WHOLE_LOGITS_BYTES`` or the mesh shards the
    sequence or the vocabulary; else the largest power of two whose block of
    one head fits ``BLOCK_LOGITS_BYTES``."""
    if mesh is not None and (mesh_extent(mesh, AXIS_SEQ) > 1 or mesh_extent(mesh, AXIS_TENSOR) > 1):
        return seq
    shards = 1 if mesh is None else mesh_extent(mesh, (AXIS_SLICE,) + tuple(dict(DEFAULT_RULES)["batch"]))
    row_bytes = -(-batch // shards) * vocab * 4
    if heads * row_bytes * seq <= WHOLE_LOGITS_BYTES:
        return seq
    return min(seq, 1 << (max(BLOCK_LOGITS_BYTES // row_bytes, 1).bit_length() - 1))


def step_targets(model, batch, mesh=None) -> Dict[str, Targets]:
    """What a model's ``apply`` takes in a step beside the batch for its head
    to run inside the loss: ``{"targets": Targets}`` where the model offers
    that (its configuration's ``head_aheads``: how far ahead of its position
    each head predicts, 0 for a model that weighs its own targets) and
    :func:`block_rows` gives more than one block. Empty otherwise: the step
    then takes logits and its loss over them. One ``loss.blocks`` event a
    trace says which it was."""
    aheads = getattr(getattr(model, "cfg", None), "head_aheads", None)
    if aheads is None or not isinstance(batch, dict) or "tokens" not in batch:
        return {}
    from maggy_tpu import telemetry

    aheads, (b, s), vocab = aheads(), batch["tokens"].shape, model.cfg.vocab_size
    rows = block_rows(b, s, vocab, len(aheads), mesh)
    telemetry.get().event(
        "loss.blocks", blocks=-(-s // rows), rows=rows, batch=b, seq=s, vocab=vocab, heads=len(aheads),
        backward="in_forward" if rows < s else "autodiff",
    )
    if rows == s:
        return {}
    ids, weights = zip(*(next_token(batch, a) if a else own_token(batch) for a in aheads))
    return {"targets": Targets(jnp.stack(ids), jnp.stack(weights), rows)}


def _block(hidden, kernel, ids, weights, tied: bool, softcap: float):
    """``-(sum_i w_i log_softmax(h_i @ W)[t_i])`` over whole logits: the
    product in the operands' dtype under the scope ``lm_head``, the rest
    float32 under ``loss``."""
    with jax.named_scope("lm_head"):
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", hidden, kernel)
        else:
            logits = jax.lax.dot_general(hidden, kernel, (((hidden.ndim - 1,), (0,)), ((), ())))
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # the target's entry by a comparison inside the row's sum, the same number as a gather's:
        # a gather's transpose is a scatter into a float32 array of the logits' size
        hit = jax.lax.broadcasted_iota(jnp.int32, logp.shape, logp.ndim - 1) == ids[..., None]
        return -(jnp.sum(jnp.where(hit, logp, 0.0), axis=-1) * weights).sum()


def _in_blocks(form, hidden, kernel, ids, weights, with_grads: bool):
    """The loop over blocks of ``rows`` positions: the loss, and with
    ``with_grads`` the gradients of hidden states, kernel (summed in float32)
    and weights, each block's made beside its logits."""
    rows, tied, softcap, dtype = form
    s = hidden.shape[1]
    blocks = -(-s // rows)
    if blocks * rows > s:  # a last block of zero hidden states that weigh nothing
        hidden, ids, weights = (
            jnp.pad(a, ((0, 0), (0, blocks * rows - s)) + ((0, 0),) * (a.ndim - 2)) for a in (hidden, ids, weights)
        )
    h, w = hidden.astype(dtype), kernel.astype(dtype)

    def block(i, h_i, w, weights_i):
        return _block(h_i, w, jax.lax.dynamic_slice_in_dim(ids, i * rows, rows, 1), weights_i, tied, softcap)

    def taken(i):
        return jax.lax.dynamic_slice_in_dim(h, i * rows, rows, 1), w, jax.lax.dynamic_slice_in_dim(weights, i * rows, rows, 1)

    with jax.named_scope("loss"):
        if not with_grads:
            return jax.lax.scan(lambda total, i: (total + block(i, *taken(i)), None), jnp.float32(0), jnp.arange(blocks))[0]

        def body(carry, i):
            total, dh, dw = carry
            loss, vjp = jax.vjp(functools.partial(block, i), *taken(i))
            dh_i, dw_i, dweights_i = vjp(jnp.float32(1))
            dh = jax.lax.dynamic_update_slice_in_dim(dh, dh_i, i * rows, 1)
            return (total + loss, dh, dw + dw_i.astype(jnp.float32)), dweights_i

        (total, dh, dw), dweights = jax.lax.scan(
            body, (jnp.float32(0), jnp.zeros_like(h), jnp.zeros(kernel.shape, jnp.float32)), jnp.arange(blocks)
        )
        dweights = jnp.moveaxis(dweights, 0, 1).reshape(weights.shape)
        return total, (dh[:, :s], dw, dweights[:, :s])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blocked(form, hidden, kernel, ids, weights):
    return _in_blocks(form, hidden, kernel, ids, weights, with_grads=False)


def _blocked_fwd(form, hidden, kernel, ids, weights):
    total, (dh, dw, dweights) = _in_blocks(form, hidden, kernel, ids, weights, with_grads=True)
    return total, (dh.astype(hidden.dtype), dw.astype(kernel.dtype), dweights)


def _blocked_bwd(form, grads, g):
    dh, dw, dweights = grads
    with jax.named_scope("loss"):
        return dh * g.astype(dh.dtype), dw * g.astype(dw.dtype), np.zeros(dh.shape[:2], jax.dtypes.float0), dweights * g


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def loss(hidden, kernel, ids, weights, *, rows: int, dtype, tied: bool = False, softcap: float = 0.0):
    """One head's loss ``-(sum_i w_i log_softmax(h_i @ W)[t_i])``, float32:
    ``hidden`` ``[B, S, d]``, ``kernel`` ``[d, vocab]`` (``tied``: the
    embedding ``[vocab, d]``), ``ids`` and ``weights`` ``[B, S]``; the product
    in ``dtype``, ``softcap`` on its result. ``rows`` of ``S`` or more: whole
    logits and autodiff's backward. Fewer: a ``lax`` loop over blocks of
    ``rows`` positions whose forward pass, where a gradient is asked for, makes
    each block's gradients beside its logits (three products a block and no
    logits kept; the kernel's gradient is summed over the blocks in float32)."""
    if rows >= hidden.shape[1]:
        return _block(hidden.astype(dtype), kernel.astype(dtype), ids, weights, tied, softcap)
    return _blocked((rows, tied, softcap, jnp.dtype(dtype)), hidden, kernel, ids, weights)
