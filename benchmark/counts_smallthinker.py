"""Operations that a grouped-query decoder needs whose router reads a layer's
input before attention, over ReLU-gated experts, with global layers beside
windowed ones (``smallthinker-21ba3b-instruct``): from shapes, the documents
and the slots the run counted.

``cfg`` is the reference's sizes (``sizes`` of
``smallthinker-21ba3b-instruct.reference.py``), which carry ``layer_types``,
``heads_per_layer``, ``n_dense`` and ``window`` in ``counts_laguna``'s terms,
so that its pair counts and its readers of the two kinds of attention layer
take them unchanged: a windowed layer's attention is needed on the pairs inside
window, document and causal order (token ``p`` of a document sees
``min(p + 1, window)`` keys), a global layer's on every causal pair inside a
document. No gate a head, no shared expert, no dense layer. A slot is one
(token, chosen expert) pair whose expert this chip holds.
"""

from __future__ import annotations

from benchmark.counts_laguna import full_flops_forward, pairs, window_flops_forward  # noqa: F401


def attention_params(cfg: dict) -> int:
    """Weights a token's attention multiplies with: queries, keys, values, the output."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    return 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: each layer's attention and router, and the untied head.
    The embedding's lookup is no product."""
    d = cfg["d_model"]
    return len(cfg["layer_types"]) * (attention_params(cfg) + d * cfg["n_experts"]) + d * cfg["vocab"]


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all layers together): the backward pass
    costs twice the forward; recomputation, pairs a mask drops inside a tile,
    rows of a buffer that hold no slot and hidden activations that are zero
    do not change the count."""
    tokens = sum(int(n) for n in doc_lengths)
    return 3 * (
        2 * (matmul_params_per_token(cfg) * tokens + expert_params(cfg) * int(slots))
        + window_flops_forward(cfg, doc_lengths) + full_flops_forward(cfg, doc_lengths)
    )
