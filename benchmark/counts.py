"""Operations that the algorithm needs, from shapes alone.

``cfg`` is a configuration's reference sizes (``vocab``, ``d_model``,
``n_layers``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``).
"""

from __future__ import annotations


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that one token's forward pass multiplies with: attention
    projections, the feed-forward and the output head. The embedding is a
    lookup, not a product."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hk = cfg["n_kv_heads"] * cfg["head_dim"]
    attn = d * hq + 2 * d * hk + hq * d
    return cfg["n_layers"] * (attn + 3 * d * f) + d * cfg["vocab"]


def attention_flops_forward(cfg: dict, doc_lengths) -> int:
    """Causal attention inside documents: token i of a document multiplies
    with i+1 keys and values, 2 products of ``head_dim`` a head, 2 operations
    a multiply-add."""
    per_pair = 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return per_pair * sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)


def train_flops(cfg: dict, doc_lengths) -> int:
    """Needed operations of forward and backward over these documents: the
    backward pass costs twice the forward; recomputation does not count."""
    tokens = sum(int(n) for n in doc_lengths)
    fwd = 2 * matmul_params_per_token(cfg) * tokens + attention_flops_forward(cfg, doc_lengths)
    return 3 * fwd
