"""Plain reference of sdar-30b-a3b-chat, and everything of the benchmark that
knows this architecture: the grouped-query decoder over softmax-routed experts
under SDAR's block-diffusion training step of
``benchmark/references/blockdiff_gqa_moe.py`` (float32 at
``Precision.HIGHEST``), the sizes it takes from the configuration's keys, the
fields the program's config class takes, the names of the program's leaves in
the reference's terms, and the needed operations (``benchmark/counts_sdar.py``).
``benchmark/kinds/train_packed_ref.py`` asks this file and nothing else about
the model."""

import re

from benchmark import counts_sdar
from benchmark.configs import _as_run
from benchmark.references.blockdiff_gqa_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys, the cuts beside them and
    the objective's constants (``block_diffusion``: ``assumed`` (a) to (d))."""
    if not cfg["norm_topk_prob"] or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("the reference normalises the chosen probabilities and has an expert layer everywhere")
    if cfg["tie_word_embeddings"] or cfg["use_sliding_window"] or cfg["rope_scaling"] is not None:
        raise ValueError("the reference has an untied head, no window and the default rotary embedding")
    noise = cfg["block_diffusion"]
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "n_layers": _as_run(cfg, "num_hidden_layers", kind),
        "n_experts": cfg["num_experts"]["published"],
        "top_k": cfg["num_experts_per_tok"],
        "held": _as_run(cfg, "num_experts", kind),
        "offset": cfg[kind]["share"]["offset"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
        "block": noise["block_length"], "noise_seed": noise["noise_seed"],
        "noise_eps": float(noise["noise_eps"]), "mask_token_id": noise["mask_token_id"],
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": s["n_layers"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"], "head_width": s["head_dim"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "qk_norm": True,
        "n_dense_layers": 0, "n_experts": s["n_experts"], "top_k": s["top_k"], "experts_held": s["held"],
        "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"], "n_shared_experts": 0, "router": "softmax",
        "block_diffusion": True, "block": s["block"], "noise_seed": s["noise_seed"],
        "noise_eps": s["noise_eps"], "mask_token_id": s["mask_token_id"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("attn_norm", "scale"): "attn_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("q_norm", "scale"): "q_norm", ("k_norm", "scale"): "k_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wo", "kernel"): "wo",
    ("router", "kernel"): "router",
    ("moe", "w_gate"): "experts_gate", ("moe", "w_up"): "experts_up", ("moe", "w_down"): "experts_down",
}
_TOP = {"embedding": "embed", "mask_embedding": "mask_embed", "final_norm": "final_norm", "lm_head": "lm_head"}


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves): the
    scanned stack's ``layers/layer/...`` is ``moe.<leaf>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys[0] in _TOP:
        return _TOP[keys[0]]
    if keys[0] == "layers" and keys[-2:] in _LEAVES:
        return f"moe.{_LEAVES[keys[-2:]]}"
    raise KeyError(f"no reference leaf for the program's {keys}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_sdar.train_flops(s, doc_lengths, slots)
