"""Plain reference of smallthinker-21ba3b-instruct, and everything of the
benchmark that knows this architecture: the decoder of
``benchmark/references/prerouted_window_moe.py`` (a router that reads the
layer's input before attention, ReLU-gated experts, global layers with no
rotary embedding beside windowed ones that rotate; float32 at
``Precision.HIGHEST``), the sizes it takes from the configuration's keys, the
fields the program's config class takes, the names of the program's leaves in
the reference's terms, and the needed operations
(``benchmark/counts_smallthinker.py``). ``benchmark/kinds/train_packed_ref.py``
asks this file and nothing else about the model."""

import re

from benchmark import counts_smallthinker
from benchmark.configs import _as_run
from benchmark.references.prerouted_window_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401

KINDS = ("full_attention", "sliding_attention")  # by ``sliding_window_layout``: 0, 1


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them.
    ``layer_types``, ``heads_per_layer``, ``n_dense``, ``window`` and
    ``shared_d_ff`` are ``counts_laguna``'s terms for its readers of the two
    kinds of attention layer."""
    n_layers = _as_run(cfg, "num_hidden_layers", kind)
    window_layout = list(_as_run(cfg, "sliding_window_layout", kind))
    rope_layout = list(_as_run(cfg, "rope_layout", kind))
    if not (len(window_layout) == len(rope_layout) == n_layers) or set(window_layout + rope_layout) - {0, 1}:
        raise ValueError("sliding_window_layout and rope_layout name every layer, 0 or 1")
    if not (cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]):
        raise ValueError("the reference's router takes a softmax over all the experts and normalises the chosen")
    if cfg["tie_word_embeddings"] or cfg["rope_scaling"] is not None:
        raise ValueError("the reference has an untied head and the default rotary form")
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "moe_d_ff": cfg["moe_ffn_hidden_size"],
        "shared_d_ff": 0,
        "n_heads": cfg["num_attention_heads"],
        "heads_per_layer": [cfg["num_attention_heads"]] * n_layers,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "layer_types": [KINDS[w] for w in window_layout],
        "window_layout": window_layout,
        "rope_layout": rope_layout,
        "window": cfg["sliding_window_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "n_dense": 0,
        "n_experts": cfg["moe_num_primary_experts"]["published"],
        "top_k": cfg["moe_num_active_primary_experts"],
        "held": _as_run(cfg, "moe_num_primary_experts", kind),
        "offset": cfg[kind]["share"]["offset"],
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    if s["window_layout"] != s["rope_layout"]:
        raise ValueError("the program rotates its windowed layers and no global one: the two layouts coincide")
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": len(s["layer_types"]),
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"], "head_width": s["head_dim"],
        "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "layer_types": tuple(s["layer_types"]), "sliding_window": s["window"],
        "rope_theta": s["rope_theta"], "sliding_rope_theta": s["rope_theta"], "full_rope": False,
        "n_dense_layers": 0, "n_experts": s["n_experts"], "top_k": s["top_k"],
        "experts_held": s["held"], "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"],
        "n_shared_experts": 0, "router": "softmax", "route_from": "layer_input", "expert_act": "relu",
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("attn_norm", "scale"): "attn_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wo", "kernel"): "wo",
    ("router", "kernel"): "router",
    ("moe", "w_gate"): "experts_gate", ("moe", "w_up"): "experts_up", ("moe", "w_down"): "experts_down",
}
_TOP = {"embedding": "embed", "final_norm": "final_norm", "lm_head": "lm_head"}


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves):
    ``layers/layer_<j>`` is the period's ``p<j>`` (``layers/layer`` where the
    period is one layer), ``tail_<i>`` ``t<i>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys[0] in _TOP:
        return _TOP[keys[0]]
    tail = re.fullmatch(r"tail_(\d+)", keys[0])
    if tail:
        prefix = f"t{tail.group(1)}"
    elif keys[0] == "layers":
        inner = re.fullmatch(r"layer_(\d+)", keys[1])
        prefix = f"p{inner.group(1)}" if inner else "p0"
    else:
        raise KeyError(f"no reference leaf for the program's {keys}")
    if keys[-2:] in _LEAVES:
        return f"{prefix}.{_LEAVES[keys[-2:]]}"
    raise KeyError(f"no reference leaf for the program's {keys}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_smallthinker.train_flops(s, doc_lengths, slots)
