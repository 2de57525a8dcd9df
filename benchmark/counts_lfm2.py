"""Operations and bytes that a hybrid short-convolution / attention decoder
with an expert share layer needs, from shapes, the documents and the slots the
run counted.

``cfg`` is the reference's sizes (``sizes`` of ``lfm2-24b-a2b.reference.py``).
A slot is one (token, chosen expert) pair whose expert this chip holds: the
routed experts' products are needed for the slots that exist, which is data,
so the run's own counter gives their number. Products only: the convolution's
taps and gates (3 + 2 multiply-adds a channel and token) are bytes, not
operations, and are counted as such (``conv_mix_bytes``).
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> tuple:
    """``(conv layers, attention layers, expert layers)``."""
    kinds = cfg["layer_types"]
    n_conv = sum(k == "conv" for k in kinds)
    return n_conv, len(kinds) - n_conv, len(kinds) - cfg["n_dense"]


def conv_params(cfg: dict) -> int:
    """Weights of one conv operator's two products: ``d -> 3d`` in, ``d -> d`` out."""
    return 4 * cfg["d_model"] ** 2


def attention_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    return 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: each layer's operator, the dense layers' feed-forward,
    the expert layers' router, and the (tied) head. The embedding's lookup is
    no product."""
    d = cfg["d_model"]
    n_conv, n_attn, n_moe = layer_counts(cfg)
    return (
        n_conv * conv_params(cfg) + n_attn * attention_params(cfg)
        + cfg["n_dense"] * 3 * d * cfg["d_ff"] + n_moe * d * cfg["n_experts"] + d * cfg["vocab"]
    )


def attention_flops_forward(cfg: dict, doc_lengths) -> int:
    """Causal attention inside documents: token i of a document multiplies
    with i+1 keys and values, 2 products of ``head_dim`` a head, 2 operations
    a multiply-add, in every attention layer."""
    per_pair = 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * layer_counts(cfg)[1]
    return per_pair * sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all expert layers together): the backward
    pass costs twice the forward; recomputation, the sort and the rows of a
    buffer that hold no slot do not count."""
    tokens = sum(int(n) for n in doc_lengths)
    fwd = (
        2 * (matmul_params_per_token(cfg) * tokens + expert_params(cfg) * int(slots))
        + attention_flops_forward(cfg, doc_lengths)
    )
    return 3 * fwd


def flash_flops(cfg: dict, doc_lengths) -> int:
    """Forward and backward operations of the causal pairs inside documents
    alone (``train.flash_roofline``): the backward's four products a pair
    against the forward's two; the scores the backward kernels compute again
    do not count."""
    return 3 * attention_flops_forward(cfg, doc_lengths)


def conv_mix_bytes(cfg: dict, positions: int, itemsize: int = 2) -> int:
    """Bytes that the gates and taps between a conv operator's two products
    have to move for ``positions`` positions of a row (padding too: the
    operator runs on all of them), every conv layer, forward and backward, at
    ``itemsize`` bytes a number (bfloat16). Forward: read ``[T, 3d]``, write
    ``[T, d]``. Backward: read ``[T, 3d]`` and the result's cotangent
    ``[T, d]``, write ``[T, 3d]``. The taps' weights and their gradient
    (``K * d`` numbers) are nothing beside that; a recomputed forward does
    not count."""
    d = cfg["d_model"]
    return layer_counts(cfg)[0] * int(positions) * itemsize * (4 * d + 7 * d)
