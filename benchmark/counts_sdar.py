"""Operations that a grouped-query decoder over an expert share layer needs
when it trains by diffusion over blocks (``sdar-30b-a3b-chat``): from shapes,
the documents and the slots the run counted.

``cfg`` is the reference's sizes (``sizes`` of
``sdar-30b-a3b-chat.reference.py``). A row of ``L`` tokens goes through the
layers as ``2L`` positions, a clean and a noised stream: the four projections,
the router and the counted slots are over both; the attention is needed on the
pairs the block-wise mask keeps (a token at position ``p`` of its document,
in a block that starts at ``p0`` and holds ``n`` tokens of it, sees ``p0 + n``
clean keys as a clean query and ``p0`` clean and ``n`` noised ones as a noised
query: about twice a causal stream's ``p + 1``); the head reads the ``L``
noised positions. A slot is one (position, chosen expert) pair, of either
stream, whose expert this chip holds. The second stream is cost: a real token
is counted once in ``train_tok_s_chip``, and these are the operations that
token needs under this objective.
"""

from __future__ import annotations


def pairs(doc_lengths, block: int) -> tuple:
    """``(pairs the mask keeps, one causal stream's pairs)`` of one attention
    layer over these documents."""
    kept = causal = 0
    for n in doc_lengths:
        n = int(n)
        causal += n * (n + 1) // 2
        whole, rest = divmod(n, block)
        # a whole block at p0 = j * block: its `block` tokens keep 2 * (p0 + block) each
        kept += 2 * block * (block * whole * (whole - 1) // 2 + block * whole)
        kept += 2 * rest * (whole * block + rest)  # the document's last, shorter block
    return kept, causal


def attention_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    return 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_position(cfg: dict) -> int:
    """Weights that every position of either stream multiplies with, the routed
    experts left out: each layer's attention and router."""
    return cfg["n_layers"] * (attention_params(cfg) + cfg["d_model"] * cfg["n_experts"])


def attention_flops_forward(cfg: dict, doc_lengths) -> int:
    """Two products of ``head_dim`` a head and kept pair, 2 operations a
    multiply-add, every layer."""
    return 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"] * pairs(doc_lengths, cfg["block"])[0]


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all layers, both streams): the backward
    pass costs twice the forward; recomputation, pairs a mask drops inside a
    tile and rows of a buffer that hold no slot do not count."""
    tokens = sum(int(n) for n in doc_lengths)
    return 3 * (
        2 * (
            matmul_params_per_position(cfg) * 2 * tokens + cfg["d_model"] * cfg["vocab"] * tokens
            + expert_params(cfg) * int(slots)
        )
        + attention_flops_forward(cfg, doc_lengths)
    )


def flash_flops(cfg: dict, doc_lengths) -> int:
    """Forward and backward operations of the kept pairs alone
    (``train.blockdiff_flash_roofline``): the backward's four products a pair
    against the forward's two. The own block's pairs (``block`` a noised
    query) are among them though plain XLA computes them: 4 of a token's
    ~1,200."""
    return 3 * attention_flops_forward(cfg, doc_lengths)


def scope_time_ns(obs, words, kernels_only: bool = False):
    """Busy nanoseconds of the traced window under any of ``words`` (flax
    module names or named scopes), a mean over the devices
    (``scopes.time_ns``); with ``kernels_only`` of the flash kernels there
    alone. ``(None, None)`` without a trace, without this architecture's sizes
    or without the scope, else ``(time, the timeline)``."""
    from benchmark import scopes

    if "noise_eps" not in obs.get("sizes", {}):
        return None, None
    t, tl = scopes.time_ns(obs, words)
    if t is None or not kernels_only:
        return t, tl
    return t - scopes.time_ns(obs, words, but_kernels=("flash_",))[0], tl


def scope_share(obs, words, kernels_only: bool = False):
    """The same over device busy time, in percent."""
    t, tl = scope_time_ns(obs, words, kernels_only)
    return None if t is None else t / tl.busy * 100.0


def flash_roofline(obs):
    """Needed operations of the pairs the mask keeps in the traced steps a
    second of device time in the flash kernels under the layers' ``attn``
    modules, over the chip's bf16 peak, in percent."""
    from benchmark.counts_keye import traced_documents
    from benchmark.peaks import peaks_for

    t, _tl = scope_time_ns(obs, ("attn",), kernels_only=True)
    if not t or obs.get("cell") is None:
        return None
    ops = sum(flash_flops(obs["sizes"], docs) for docs in traced_documents(obs))
    return ops / (t * 1e-9) / obs["chips"] / peaks_for(obs["device_kind"])["bf16_flops_per_s"] * 100.0
