"""Operations and bytes that a hybrid Kimi-delta-attention / latent-attention
decoder over an expert share layer needs (``ling-3.0-flash``): from shapes, the
documents and the slots the run counted.

``cfg`` is the reference's sizes (``sizes`` of ``ling-3.0-flash.reference.py``).
A slot is one (token, chosen expert) pair whose expert this chip holds. The
latent attention's pairs are booked at the true widths (keys of ``d_nope +
d_rope``, values of ``d_v``): what the program's padding to one width costs
is not booked as needed work, so it shows as a lower ``train.mfu``.

``kda.scan`` is counted from shapes alone, whatever implements the scope: the
chunked delta rule at ``chunk`` positions a chunk, a token and head, forward,
in multiply-adds (the triangular halves of the chunk's square products, which
a kernel may skip, are not counted):

* the two masked products of a chunk, ``A`` (below the diagonal) and ``P`` (on
  and below it): ``chunk * d_k`` together;
* the unit lower-triangular system, by substitution: ``chunk^2 / 6``;
* ``W = T (beta K exp(g))`` and ``U~ = T (beta V)``, ``T`` lower triangular:
  ``(chunk + 1) / 2 * (d_k + d_v)``;
* against the state: ``W S``, ``Qg S`` and ``Kd^T U``, ``d_k * d_v`` each, and
  ``P U``, ``(chunk + 1) / 2 * d_v``;

the backward twice the forward, as everywhere in this benchmark. Its bytes are
``q, k, v`` (bfloat16), ``a`` (float32), ``beta`` (float32) and ``o`` (bfloat16)
once forward, and the same with every cotangent once backward.
"""

from __future__ import annotations


def kda_params(cfg: dict) -> int:
    """Weights a token's KDA operator multiplies with: q, k, v, the decay's f and the output
    (``d_model x heads x kda_dim`` each), beta and the gate a head. The taps are no product."""
    d, h = cfg["d_model"], cfg["n_heads"]
    return 5 * d * h * cfg["kda_dim"] + 2 * d * h


def mla_params(cfg: dict) -> int:
    """Weights a token's latent attention multiplies with: the full-rank query, key-value down
    (with the rope key's columns) and up, the gate a head, the output."""
    d, h = cfg["d_model"], cfg["n_heads"]
    return (
        d * h * (cfg["d_nope"] + cfg["d_rope"]) + d * (cfg["kv_rank"] + cfg["d_rope"])
        + cfg["kv_rank"] * h * (cfg["d_nope"] + cfg["d_v"]) + d * h + h * cfg["d_v"] * d
    )


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: every layer's operator, the dense layers' feed-forward,
    router and shared expert of the expert layers, the untied head."""
    d, kinds = cfg["d_model"], cfg["layer_types"]
    n_kda = sum(k == "kda" for k in kinds)
    n_expert = len(kinds) - cfg["n_dense"]
    return (
        n_kda * kda_params(cfg) + (len(kinds) - n_kda) * mla_params(cfg)
        + cfg["n_dense"] * 3 * d * cfg["d_ff"]
        + n_expert * (d * cfg["n_experts"] + cfg["n_shared"] * expert_params(cfg))
        + d * cfg["vocab"]
    )


def mla_flops_forward(cfg: dict, doc_lengths) -> int:
    """Causal attention inside documents in the latent-attention layers: token i of a document
    multiplies with i + 1 keys of ``d_nope + d_rope`` and values of ``d_v``, 2 operations a
    multiply-add: the true widths, not the padded ones."""
    layers = sum(k != "kda" for k in cfg["layer_types"])
    per_pair = 2 * cfg["n_heads"] * (cfg["d_nope"] + cfg["d_rope"] + cfg["d_v"]) * layers
    return per_pair * sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)


def kda_scan_flops_forward(cfg: dict, tokens: int) -> int:
    """``kda.scan`` of every KDA layer, forward, over ``tokens`` positions (the module docstring)."""
    c, dk = cfg["kda_chunk"], cfg["kda_dim"]
    dv = dk
    macs = c * dk + c * c / 6 + (c + 1) / 2 * (dk + dv) + 3 * dk * dv + (c + 1) / 2 * dv
    layers = sum(k == "kda" for k in cfg["layer_types"])
    return int(2 * macs * cfg["n_heads"] * layers * int(tokens))


def kda_scan_flops(cfg: dict, tokens: int) -> int:
    """Forward and backward (``train.kda_scan_roofline``)."""
    return 3 * kda_scan_flops_forward(cfg, tokens)


def kda_scan_bytes(cfg: dict, tokens: int) -> int:
    """What ``kda.scan`` has to move, forward and backward, every KDA layer: q, k, v and o in
    bfloat16, a and beta in float32, once forward; the five inputs again, the output's cotangent
    and the five cotangents once backward."""
    d = cfg["kda_dim"]
    inputs = 3 * 2 * d + 4 * d + 4
    layers = sum(k == "kda" for k in cfg["layer_types"])
    return (inputs + 2 * d + 2 * inputs + 2 * d) * cfg["n_heads"] * layers * int(tokens)


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all expert layers together): the backward
    pass costs twice the forward; recomputation, the padded columns of the
    latent attention's heads, the triangular halves of a chunk's products and
    the rows of a buffer that hold no slot do not count."""
    tokens = sum(int(n) for n in doc_lengths)
    fwd = (
        2 * (matmul_params_per_token(cfg) * tokens + expert_params(cfg) * int(slots))
        + mla_flops_forward(cfg, doc_lengths) + kda_scan_flops_forward(cfg, tokens)
    )
    return 3 * fwd
