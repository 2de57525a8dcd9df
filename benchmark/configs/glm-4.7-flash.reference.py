"""Plain reference of glm-4.7-flash, and everything of the benchmark that
knows this architecture: the latent-attention, sigmoid-routed expert and
multi-token-prediction decoder of ``benchmark/references/mla_moe.py`` (float32
at ``Precision.HIGHEST``), the sizes it takes from the configuration's keys,
the fields the program's config class takes, the names of the program's leaves
in the reference's terms, and the needed operations
(``benchmark/counts_mla_moe.py``). ``benchmark/kinds/train_packed_ref.py``
asks this file and nothing else about the model."""

from benchmark import counts_mla_moe
from benchmark.configs import _as_run
from benchmark.references.mla_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them."""
    section = cfg[kind]
    n_dense = cfg["first_k_dense_replace"]
    if n_dense != 1:
        raise ValueError("the leaf names below are written for one leading dense layer")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("the reference routes without a group limit and normalises the chosen scores")
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "d_nope": cfg["qk_nope_head_dim"],
        "d_rope": cfg["qk_rope_head_dim"],
        "d_v": cfg["v_head_dim"],
        "n_dense": n_dense,
        "n_moe": _as_run(cfg, "num_hidden_layers", kind) - n_dense,
        "n_experts": cfg["n_routed_experts"]["published"],
        "top_k": cfg["num_experts_per_tok"],
        "held": _as_run(cfg, "n_routed_experts", kind),
        "offset": section["share"]["offset"],
        "n_shared": cfg["n_shared_experts"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "bias_std": float(section["selection_bias"]["std"]),
        "bias_seed": int(section["selection_bias"]["seed"]),
        "mtp": cfg["num_nextn_predict_layers"],
        "mtp_weight": float(section["mtp_weight"]),
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": s["n_dense"] + s["n_moe"],
        "n_heads": s["n_heads"], "n_kv_heads": cfg["num_key_value_heads"], "d_ff": s["d_ff"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "q_lora_rank": s["q_rank"], "kv_lora_rank": s["kv_rank"], "qk_nope_head_dim": s["d_nope"],
        "qk_rope_head_dim": s["d_rope"], "v_head_dim": s["d_v"],
        "n_dense_layers": s["n_dense"], "n_experts": s["n_experts"], "top_k": s["top_k"],
        "experts_held": s["held"], "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"],
        "n_shared_experts": s["n_shared"], "routed_scaling": s["routed_scaling"],
        "select_bias_std": s["bias_std"], "select_bias_seed": s["bias_seed"],
        "mtp_depth": s["mtp"], "mtp_weight": s["mtp_weight"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


_IN_LAYER = ("attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "router")
_MTP_OWN = ("enorm", "hnorm", "eh_proj")


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves)."""
    import jax

    s = jax.tree_util.keystr(path)
    has = lambda n: f"'{n}'" in s
    if has("embedding"):
        return "embed"
    if has("lm_head"):
        return "lm_head"
    group = "mtp" if has("mtp") else "dense" if has("dense_0") else "moe" if has("layers") else None
    if has("final_norm"):
        return "mtp.final_norm" if group == "mtp" else "final_norm"
    if group is None:
        raise KeyError(f"no reference leaf for the program's {s}")
    for n in _MTP_OWN:
        if has(n):
            return f"mtp.{n}"
    for n in _IN_LAYER:
        if has(n):
            return f"{group}.{n}"
    for n in ("w_gate", "w_up", "w_down"):
        if has(n):
            what = n[2:]
            if has("shared"):
                return f"{group}.shared_{what}"
            return f"{group}.experts_{what}" if has("moe") else f"{group}.{n}"
    raise KeyError(f"no reference leaf for the program's {s}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_mla_moe.train_flops(s, doc_lengths, slots)
