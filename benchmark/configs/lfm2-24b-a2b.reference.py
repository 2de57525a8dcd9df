"""Plain reference of lfm2-24b-a2b, and everything of the benchmark that
knows this architecture: the hybrid short-convolution / grouped-query-attention
decoder over sigmoid-routed experts of ``benchmark/references/conv_moe.py``
(float32 at ``Precision.HIGHEST``), the sizes it takes from the configuration's
keys, the fields the program's config class takes, the names of the program's
leaves in the reference's terms, and the needed operations and bytes
(``benchmark/counts_lfm2.py``). ``benchmark/kinds/train_packed_ref.py`` asks
this file and nothing else about the model."""

import re

from benchmark import counts_lfm2
from benchmark.configs import _as_run
from benchmark.references.conv_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them."""
    section = cfg[kind]
    kinds = list(_as_run(cfg, "layer_types", kind))
    if len(kinds) != _as_run(cfg, "num_hidden_layers", kind):
        raise ValueError("layer_types names every layer")
    if not cfg["norm_topk_prob"] or not cfg["use_expert_bias"] or cfg["conv_bias"]:
        raise ValueError("the reference normalises the chosen scores, biases the selection and has no conv bias")
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "conv_kernel": cfg["conv_L_cache"],
        "layer_types": kinds,
        "n_dense": _as_run(cfg, "num_dense_layers", kind),
        "n_experts": cfg["num_experts"]["published"],
        "top_k": cfg["num_experts_per_tok"],
        "held": _as_run(cfg, "num_experts", kind),
        "offset": section["share"]["offset"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "route_eps": float(section["route_norm_eps"]),
        "bias_std": float(section["selection_bias"]["std"]),
        "bias_seed": int(section["selection_bias"]["seed"]),
        "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
        "norm_eps": float(cfg["norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": len(s["layer_types"]),
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"], "d_ff": s["d_ff"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "layer_types": tuple(s["layer_types"]), "conv_kernel": s["conv_kernel"], "qk_norm": True,
        "tie_embeddings": True,
        "n_dense_layers": s["n_dense"], "n_experts": s["n_experts"], "top_k": s["top_k"],
        "experts_held": s["held"], "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"],
        "n_shared_experts": 0, "routed_scaling": s["routed_scaling"], "route_norm_eps": s["route_eps"],
        "select_bias_std": s["bias_std"], "select_bias_seed": s["bias_seed"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("attn_norm", "scale"): "attn_norm", ("conv_norm", "scale"): "conv_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("q_norm", "scale"): "q_norm", ("k_norm", "scale"): "k_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wo", "kernel"): "wo",
    ("in_proj", "kernel"): "conv_in", ("conv", "conv"): "conv_w", ("out_proj", "kernel"): "conv_out",
    ("router", "kernel"): "router",
    ("mlp", "w_gate", "kernel"): "w_gate", ("mlp", "w_up", "kernel"): "w_up", ("mlp", "w_down", "kernel"): "w_down",
    ("moe", "w_gate"): "experts_gate", ("moe", "w_up"): "experts_up", ("moe", "w_down"): "experts_down",
}


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves):
    ``dense_<i>`` is ``d<i>``, ``layers/layer_<j>`` the period's ``p<j>``
    (``layers/layer`` where the period is one layer), ``tail_<i>`` ``t<i>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys[0] == "embedding":
        return "embed"
    if keys[0] == "final_norm":
        return "final_norm"
    top = re.fullmatch(r"(dense|tail)_(\d+)", keys[0])
    if top:
        prefix = f"{top.group(1)[0]}{top.group(2)}"
    elif keys[0] == "layers":
        inner = re.fullmatch(r"layer_(\d+)", keys[1])
        prefix = f"p{inner.group(1)}" if inner else "p0"
    else:
        raise KeyError(f"no reference leaf for the program's {keys}")
    for n in (3, 2):
        if keys[-n:] in _LEAVES:
            return f"{prefix}.{_LEAVES[keys[-n:]]}"
    raise KeyError(f"no reference leaf for the program's {keys}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_lfm2.train_flops(s, doc_lengths, slots)
