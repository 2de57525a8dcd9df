"""Operations that a grouped-query decoder needs whose attention runs over
the keys an indexer selects, over an expert share layer: from shapes, the
documents and the slots the run counted.

``cfg`` is the reference's sizes (``sizes`` of
``keye-vl-2.0-30b-a3b.reference.py``). The attention is needed on the
**selected** pairs only (token ``t`` of a document attends ``min(t + 1, topk)``
keys); the index scores on every **causal** pair inside a document forward
(they decide the selection) and, backward, on the selected pairs (the indexer's
loss has no gradient elsewhere). The indexer's target, the heads' mean
probabilities on the selected keys, is what the attention's forward already
made: nothing more is needed for it. A slot is one (token, chosen expert) pair
whose expert this chip holds.
"""

from __future__ import annotations


def pairs(doc_lengths, topk: int) -> tuple:
    """``(selected, causal)`` pairs of one attention layer over these documents."""
    selected = causal = 0
    for n in doc_lengths:
        n = int(n)
        causal += n * (n + 1) // 2
        m = min(n, topk)
        selected += m * (m + 1) // 2 + (n - m) * topk
    return selected, causal


def attention_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    return 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def indexer_params(cfg: dict) -> int:
    """The indexer's three products: queries, the one key head, the heads' weights."""
    return cfg["d_model"] * (cfg["index_heads"] * cfg["index_head_dim"] + cfg["index_head_dim"] + cfg["index_heads"])


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: each layer's attention, indexer and router, and the
    untied head. The embedding's lookup is no product."""
    d = cfg["d_model"]
    per_layer = attention_params(cfg) + indexer_params(cfg) + d * cfg["n_experts"]
    return cfg["n_layers"] * per_layer + d * cfg["vocab"]


def attention_flops_forward(cfg: dict, doc_lengths) -> int:
    """Two products of ``head_dim`` a head and selected pair, 2 operations a
    multiply-add, every layer."""
    return 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"] * pairs(doc_lengths, cfg["topk"])[0]


def index_pair_flops(cfg: dict) -> int:
    """One pair's index score: ``index_heads`` products of ``index_head_dim``."""
    return 2 * cfg["index_heads"] * cfg["index_head_dim"]


def index_flops_forward(cfg: dict, doc_lengths) -> int:
    """``index_scores`` over the causal pairs inside documents, every layer, once."""
    return index_pair_flops(cfg) * cfg["n_layers"] * pairs(doc_lengths, cfg["topk"])[1]


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all layers together): the backward pass
    costs twice the forward, of the index scores on the selected pairs only;
    recomputation, pairs the selection masks, a second and third pass of the
    index scores and rows of a buffer that hold no slot do not count."""
    tokens = sum(int(n) for n in doc_lengths)
    selected, _ = pairs(doc_lengths, cfg["topk"])
    return (
        3 * (2 * (matmul_params_per_token(cfg) * tokens + expert_params(cfg) * int(slots))
             + attention_flops_forward(cfg, doc_lengths))
        + index_flops_forward(cfg, doc_lengths)
        + 2 * index_pair_flops(cfg) * cfg["n_layers"] * selected
    )


def flash_flops(cfg: dict, doc_lengths) -> int:
    """Forward and backward operations of the selected pairs alone
    (``train.sparse_attn_roofline``): the backward's four products a pair
    against the forward's two."""
    return 3 * attention_flops_forward(cfg, doc_lengths)


def flash_visited_flops(cfg: dict, doc_lengths) -> int:
    """The same over every **causal** pair inside a document
    (``train.sparse_flash_roofline``): what the flash kernels compute while
    the selection reaches them as a mask and no tile is without a selected
    pair."""
    causal = pairs(doc_lengths, cfg["topk"])[1]
    return 3 * 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"] * causal


def traced_documents(obs) -> list:
    """The documents of each traced step of a run (``obs`` of
    ``kinds/train_packed_ref.py``): the pool is cycled in order after the
    verify steps."""
    from benchmark import traffic

    mix = obs["cell"].mix
    pool, rows = traffic.packed_pool(mix, obs["cell"].seed, obs["sizes"]["vocab"], obs["chips"])
    per, k = len(pool[0]["tokens"]), int(mix["steps_per_chunk"])
    docs = [[n for row in rows[b * per:(b + 1) * per] for n in row] for b in range(len(pool))]
    traced = [i + j for i in obs["traced_steps"] for j in range(k)]
    return [docs[(int(mix["verify_steps"]) + i) % len(pool)] for i in traced]


def kernel_roofline(obs, kernel: str, needed) -> float | None:
    """``needed(sizes, documents)`` operations of the traced steps a second of
    device time in the operations whose name holds ``kernel``, over the
    chip's bf16 peak, in percent; None without a trace, without this
    architecture's sizes or without the kernel."""
    from benchmark.peaks import peaks_for

    tr, sizes = obs.get("trace"), obs.get("sizes", {})
    if tr is None or "topk" not in sizes or obs.get("cell") is None:
        return None
    t = sum(s for n, s in tr["device_ops"] if kernel in n)
    if not t:
        return None
    ops = sum(needed(sizes, docs) for docs in traced_documents(obs))
    return ops / t / obs["chips"] / peaks_for(obs["device_kind"])["bf16_flops_per_s"] * 100.0
