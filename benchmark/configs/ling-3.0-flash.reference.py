"""Plain reference of ling-3.0-flash, and everything of the benchmark that
knows this architecture: the hybrid Kimi-delta-attention / latent-attention
decoder over group-limited sigmoid-routed experts of
``benchmark/references/kda_mla_moe.py`` (float32 at ``Precision.HIGHEST``, the
delta rule token by token), the sizes it takes from the configuration's keys,
the fields the program's config class takes, the names of the program's leaves
in the reference's terms, and the needed operations and bytes
(``benchmark/counts_ling.py``). ``benchmark/kinds/train_packed_ref.py`` asks
this file and nothing else about the model."""

import re

from benchmark import counts_ling
from benchmark.configs import _as_run
from benchmark.references.kda_mla_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401


def layer_kinds(cfg: dict, kind: str) -> list:
    """``kda`` or ``mla`` for each layer the cut keeps: published layer ``i``
    is latent attention where ``(i + 1) % layer_group_size == 0``."""
    first = cfg[kind]["first_published_layer"]
    n = _as_run(cfg, "num_hidden_layers", kind)
    return ["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda" for i in range(first, first + n)]


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them."""
    section = cfg[kind]
    if cfg["q_lora_rank"] is not None or not cfg["norm_topk_prob"] or cfg["score_function"] != "sigmoid":
        raise ValueError("the reference has a full-rank query, a sigmoid router and normalises the chosen scores")
    if not cfg["kda_safe_gate"] or cfg["use_kda_lora"] or cfg["num_kv_heads_for_linear_attn"] or cfg["value_norm"]:
        raise ValueError("the reference's KDA has the bounded gate, full-rank projections, one key head a query head, no value norm")
    if _as_run(cfg, "num_nextn_predict_layers", kind) or cfg["mtp_loss_scaling_factor"]:
        raise ValueError("the reference has no further-token module: the published loss weight is 0")
    if any(_as_run(cfg, "expert_swiglu_limit_list", kind)) or any(_as_run(cfg, "share_expert_swiglu_limit_list", kind)):
        raise ValueError("the reference writes no clamp: the kept layers' limits are 0")
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "kda_dim": cfg["head_dim"],
        "kda_chunk": section["kda_chunk"],
        "conv_kernel": cfg["short_conv_kernel_size"],
        "decay_floor": float(cfg["kda_lower_bound"]),
        "kv_rank": cfg["kv_lora_rank"],
        "d_nope": cfg["qk_nope_head_dim"],
        "d_rope": cfg["qk_rope_head_dim"],
        "d_v": cfg["v_head_dim"],
        "layer_types": layer_kinds(cfg, kind),
        "n_dense": _as_run(cfg, "first_k_dense_replace", kind),
        "n_experts": cfg["num_experts"]["published"],
        "top_k": cfg["num_experts_per_tok"],
        "n_group": cfg["n_group"],
        "topk_group": cfg["topk_group"],
        "held": _as_run(cfg, "num_experts", kind),
        "offset": section["share"]["offset"],
        "n_shared": cfg["num_shared_experts"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "bias_std": float(section["selection_bias"]["std"]),
        "bias_seed": int(section["selection_bias"]["seed"]),
        "router_std": float(section.get("router_std", 0.02)),
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": len(s["layer_types"]),
        "n_heads": s["n_heads"], "n_kv_heads": cfg["num_key_value_heads"], "d_ff": s["d_ff"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "layer_types": tuple("kda" if k == "kda" else "latent_attention" for k in s["layer_types"]),
        "kda_head_dim": s["kda_dim"], "kda_conv_kernel": s["conv_kernel"], "kda_decay_floor": s["decay_floor"],
        "kda_chunk": s["kda_chunk"],
        "q_lora_rank": 0, "kv_lora_rank": s["kv_rank"], "qk_nope_head_dim": s["d_nope"],
        "qk_rope_head_dim": s["d_rope"], "v_head_dim": s["d_v"], "attn_gate": True,
        "n_dense_layers": s["n_dense"], "n_experts": s["n_experts"], "top_k": s["top_k"],
        "n_group": s["n_group"], "topk_group": s["topk_group"],
        "experts_held": s["held"], "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"],
        "n_shared_experts": s["n_shared"], "routed_scaling": s["routed_scaling"],
        "select_bias_std": s["bias_std"], "select_bias_seed": s["bias_seed"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("kda_norm", "scale"): "kda_norm", ("attn_norm", "scale"): "attn_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("o_norm", "scale"): "o_norm", ("kv_norm", "scale"): "kv_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wf", "kernel"): "wf",
    ("w_beta", "kernel"): "w_beta", ("w_head_gate", "kernel"): "head_gate", ("wo", "kernel"): "wo",
    ("kda", "q_conv"): "q_conv", ("kda", "k_conv"): "k_conv", ("kda", "v_conv"): "v_conv",
    ("kda", "A_log"): "A_log", ("kda", "dt_bias"): "dt_bias",
    ("wkv_a", "kernel"): "wkv_a", ("wkv_b", "kernel"): "wkv_b",
    ("router", "kernel"): "router",
    ("mlp", "w_gate", "kernel"): "w_gate", ("mlp", "w_up", "kernel"): "w_up", ("mlp", "w_down", "kernel"): "w_down",
    ("shared", "w_gate", "kernel"): "shared_gate", ("shared", "w_up", "kernel"): "shared_up",
    ("shared", "w_down", "kernel"): "shared_down",
    ("moe", "w_gate"): "experts_gate", ("moe", "w_up"): "experts_up", ("moe", "w_down"): "experts_down",
}

def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves):
    ``l<i>.<leaf>`` for layer ``i`` of the cut, whose layers run unrolled
    (``scan_layers`` false): ``dense_0``, then ``layers_<i - 1>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys[0] in ("embedding", "final_norm", "lm_head"):
        return "embed" if keys[0] == "embedding" else keys[0]
    top = re.fullmatch(r"(dense|layers)_(\d+)", keys[0])
    if not top:
        raise KeyError(f"no reference leaf for the program's {keys}")
    layer = int(top.group(2)) + (top.group(1) == "layers")
    for n in (3, 2):
        if keys[-n:] in _LEAVES:
            return f"l{layer}.{_LEAVES[keys[-n:]]}"
    raise KeyError(f"no reference leaf for the program's {keys}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_ling.train_flops(s, doc_lengths, slots)
