"""Operations that a grouped-query decoder needs whose layers take full or
sliding-window attention by turns (each kind with its own query heads and a
gate a head) over an expert share layer beside a shared expert: from shapes,
the documents and the slots the run counted. And where the device time of the
two kinds of attention layer lies in a trace: the names of their modules.

``cfg`` is the reference's sizes (``sizes`` of ``laguna-s-2.1.reference.py``).
A sliding layer's attention is needed on the pairs inside window, document and
causal order (token ``p`` of a document sees ``min(p + 1, window)`` keys), a
full layer's on every causal pair inside a document. A slot is one (token,
chosen expert) pair whose expert this chip holds.
"""

from __future__ import annotations

from benchmark.counts_keye import traced_documents  # noqa: F401  (the same pool, cycled the same way)
from benchmark.counts_keye import pairs as _capped_pairs


def pairs(doc_lengths, window: int) -> tuple:
    """``(inside the window, causal)`` pairs of one attention layer over these
    documents: the query's own position counts as one of the window's, so a
    query sees ``min(p + 1, window)`` keys, the count a selection of
    ``window`` keys a query has (``counts_keye.pairs``)."""
    return _capped_pairs(doc_lengths, window)


def layer_modules(cfg: dict) -> list:
    """The flax module each layer's operations carry in their ``op_name``, in
    the order the layers run: ``dense_<i>``, then ``layer_<j>`` inside the
    scanned ``layers`` (``layer`` where the period is one layer), then
    ``tail_<i>``."""
    from benchmark.references.conv_moe import plan  # the period as the program's ``layer_plan`` finds it

    kinds, n_dense = cfg["layer_types"], cfg["n_dense"]
    period, n_periods, tail = plan(kinds, n_dense)
    inner = [f"layer_{j}" for j in range(len(period))] if len(period) > 1 else ["layer"]
    return [f"dense_{i}" for i in range(n_dense)] + inner * n_periods + [f"tail_{i}" for i in range(len(tail))]


def modules_of(cfg: dict, kind: str) -> frozenset:
    """The modules of the layers of one kind (``sliding_attention`` or ``full_attention``)."""
    return frozenset(m for m, k in zip(layer_modules(cfg), cfg["layer_types"]) if k == kind)


def layers_of(cfg: dict, kind: str) -> list:
    """The query heads of each layer of one kind."""
    return [h for h, k in zip(cfg["heads_per_layer"], cfg["layer_types"]) if k == kind]


def attention_params(cfg: dict, heads: int) -> int:
    """Weights a token's attention multiplies with: queries, keys, values, the gate a head, the output."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * cfg["n_kv_heads"] * hd + d * heads


def expert_params(cfg: dict) -> int:
    """Weights one slot multiplies with: one expert's three products."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every token's forward pass multiplies with, the routed
    experts left out: each layer's attention and gate, the dense layers'
    feed-forward, each expert layer's router and shared expert, and the untied
    head. The embedding's lookup is no product."""
    d, n_dense = cfg["d_model"], cfg["n_dense"]
    n_moe = len(cfg["layer_types"]) - n_dense
    return (
        sum(attention_params(cfg, h) for h in cfg["heads_per_layer"])
        + n_dense * 3 * d * cfg["d_ff"]
        + n_moe * (d * cfg["n_experts"] + 3 * d * cfg["shared_d_ff"])
        + d * cfg["vocab"]
    )


def window_flops_forward(cfg: dict, doc_lengths) -> int:
    """The sliding layers' attention: two products of ``head_dim`` a head and
    pair inside window, document and causal order, 2 operations a multiply-add."""
    inside, _ = pairs(doc_lengths, cfg["window"])
    return 2 * 2 * cfg["head_dim"] * sum(layers_of(cfg, "sliding_attention")) * inside


def full_flops_forward(cfg: dict, doc_lengths) -> int:
    """The full layers' attention: the same over every causal pair inside a document."""
    _, causal = pairs(doc_lengths, cfg["window"])
    return 2 * 2 * cfg["head_dim"] * sum(layers_of(cfg, "full_attention")) * causal


def train_flops(cfg: dict, doc_lengths, slots: int) -> int:
    """Needed operations of forward and backward over these documents with
    ``slots`` slots on held experts (all layers together): the backward pass
    costs twice the forward; recomputation, pairs a mask drops inside a tile
    and rows of a buffer that hold no slot do not count."""
    tokens = sum(int(n) for n in doc_lengths)
    return 3 * (
        2 * (matmul_params_per_token(cfg) * tokens + expert_params(cfg) * int(slots))
        + window_flops_forward(cfg, doc_lengths) + full_flops_forward(cfg, doc_lengths)
    )


def window_flash_flops(cfg: dict, doc_lengths) -> int:
    """Forward and backward operations of the sliding layers' pairs
    (``train.window_flash_roofline``): the backward's four products a pair
    against the forward's two."""
    return 3 * window_flops_forward(cfg, doc_lengths)


def full_flash_flops(cfg: dict, doc_lengths) -> int:
    """The same for the full layers' causal pairs (``train.full_flash_roofline``)."""
    return 3 * full_flops_forward(cfg, doc_lengths)


def attn_time_ns(obs, kind: str, kernels_only: bool = False):
    """Busy nanoseconds of the traced window under the ``attn`` modules of the
    layers of ``kind`` (projections, head norms, rotary, gate, kernels;
    forward, replay and backward), a mean over the devices; with
    ``kernels_only`` of the flash kernels there alone. ``(None, None)`` without
    a trace, without this architecture's sizes or without such a module in the
    trace, else ``(time, the timeline)``."""
    from benchmark import spans

    sizes = obs.get("sizes", {})
    if "heads_per_layer" not in sizes or "needed_flops" not in obs:
        return None, None
    tl = spans.load(obs)
    if tl is None:
        return None, None
    modules = modules_of(sizes, kind)
    total = found = 0
    for ops in tl.ops:
        for start, end, name, op_name in ops:
            if not op_name:
                continue
            *scopes, _primitive = op_name.rstrip(":").split("/")
            words = {w for part in scopes for w in spans._WORD.findall(part)}
            if "attn" in words and words & modules:
                found += 1
                if not kernels_only or "flash_" in name:
                    total += end - start
    if not found:
        return None, None
    return total / len(tl.ops), tl


def attn_share(obs, kind: str, kernels_only: bool = False):
    """The same over device busy time, in percent."""
    t, tl = attn_time_ns(obs, kind, kernels_only)
    return None if t is None else t / tl.busy * 100.0


def flash_roofline(obs, kind: str, needed):
    """``needed(sizes, documents)`` operations of the traced steps a second of
    device time in the flash kernels under the ``attn`` modules of the layers
    of ``kind``, over the chip's bf16 peak, in percent; None where
    ``attn_time_ns`` finds nothing or the kernels took no time."""
    from benchmark.peaks import peaks_for

    t, _tl = attn_time_ns(obs, kind, kernels_only=True)
    if not t or obs.get("cell") is None:
        return None
    ops = sum(needed(obs["sizes"], docs) for docs in traced_documents(obs))
    return ops / (t * 1e-9) / obs["chips"] / peaks_for(obs["device_kind"])["bf16_flops_per_s"] * 100.0
