"""Operations that a latent-attention, expert-share decoder needs, from
shapes, the documents and the slots the run counted.

``cfg`` is the reference's sizes (``sizes`` of ``glm-4.7-flash.reference.py``).
A slot is one (token, chosen expert) pair whose expert this chip holds: the
routed experts' products are needed for the slots that exist, which is data,
so the run's own counter gives their number.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """Weights of one layer's latent attention: query down and up, key-value
    down (with the rope key's columns) and up, output."""
    d, h = cfg["d_model"], cfg["n_heads"]
    dq = cfg["d_nope"] + cfg["d_rope"]
    return (
        d * cfg["q_rank"] + cfg["q_rank"] * h * dq
        + d * (cfg["kv_rank"] + cfg["d_rope"]) + cfg["kv_rank"] * h * (cfg["d_nope"] + cfg["d_v"])
        + h * cfg["d_v"] * d
    )


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: attention in every layer (the MTP module's too), the
    dense layers' feed-forward, router and shared experts of the expert
    layers, the MTP module's projection, and the head once a prediction.
    The embedding is a lookup, not a product."""
    d = cfg["d_model"]
    expert_layers = cfg["n_moe"] + cfg["mtp"]
    return (
        (cfg["n_dense"] + expert_layers) * attention_params(cfg)
        + cfg["n_dense"] * 3 * d * cfg["d_ff"]
        + expert_layers * (d * cfg["n_experts"] + cfg["n_shared"] * expert_params(cfg))
        + cfg["mtp"] * 2 * d * d
        + (1 + cfg["mtp"]) * d * cfg["vocab"]
    )


def attention_flops_forward(cfg: dict, doc_lengths) -> int:
    """Causal attention inside documents: token i of a document multiplies
    with i+1 keys (heads of d_nope + d_rope) and values (d_v), 2 operations a
    multiply-add, in every layer that has attention."""
    layers = cfg["n_dense"] + cfg["n_moe"] + cfg["mtp"]
    per_pair = 2 * cfg["n_heads"] * (cfg["d_nope"] + cfg["d_rope"] + cfg["d_v"]) * layers
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


def experts_flops(cfg: dict, slots: int) -> int:
    """Forward and backward operations of the routed experts' products alone
    (``train.moe_experts_roofline``)."""
    return 3 * 2 * expert_params(cfg) * int(slots)
