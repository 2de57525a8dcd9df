"""Plain reference of laguna-s-2.1, and everything of the benchmark that knows
this architecture: the decoder of full and sliding-window grouped-query
attention layers (72 or 48 gated query heads over 8, a rotary form a kind)
over softmax-routed experts beside a shared one of
``benchmark/references/window_gqa_moe.py`` (float32 at ``Precision.HIGHEST``),
the sizes it takes from the configuration's keys, the fields the program's
config class takes, the names of the program's leaves in the reference's terms,
and the needed operations (``benchmark/counts_laguna.py``).
``benchmark/kinds/train_packed_ref.py`` asks this file and nothing else about
the model."""

import re

from benchmark import counts_laguna
from benchmark.configs import _as_run
from benchmark.references.window_gqa_moe import GRAD_SAMPLE, leaf_spec, train_steps  # noqa: F401

KINDS = ("full_attention", "sliding_attention")


def rotary_form(published: dict, head_dim: int) -> dict:
    """One kind's ``rope_parameters`` in the reference's terms."""
    form = {"theta": float(published["rope_theta"]), "width": int(head_dim * published["partial_rotary_factor"])}
    if published["rope_type"] == "yarn":
        form["yarn"] = {
            "factor": float(published["factor"]), "original": int(published["original_max_position_embeddings"]),
            "beta_fast": float(published["beta_fast"]), "beta_slow": float(published["beta_slow"]),
            "attention_factor": float(published["attention_factor"]),
        }
    elif published["rope_type"] != "default":
        raise ValueError("the reference knows the default rotary form and YaRN's")
    return form


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them."""
    kinds = list(_as_run(cfg, "layer_types", kind))
    heads = list(_as_run(cfg, "num_attention_heads_per_layer", kind))
    n_layers = _as_run(cfg, "num_hidden_layers", kind)
    n_dense = len(cfg["mlp_only_layers"])
    if not (len(kinds) == len(heads) == n_layers) or set(kinds) - set(KINDS):
        raise ValueError("layer_types and num_attention_heads_per_layer name every layer, full or sliding")
    if list(_as_run(cfg, "mlp_layer_types", kind)) != ["dense"] * n_dense + ["sparse"] * (n_layers - n_dense):
        raise ValueError("the leading mlp_only_layers are dense and every other layer sparse")
    if cfg["mlp_only_layers"] != list(range(n_dense)) or cfg["decoder_sparse_step"] != 1:
        raise ValueError("the dense layers lead and an expert layer follows everywhere")
    if set(_as_run(cfg, "gating_types", kind)) != {"per_head"} or cfg["gating"] != "per-head":
        raise ValueError("the reference gates every layer's attention a head")
    if not cfg["norm_topk_prob"] or cfg["moe_router_logit_softcapping"] or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("the reference normalises the chosen probabilities, caps no logit and weights the output")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("the reference has an untied head and no attention bias")
    full = {h for h, k in zip(heads, kinds) if k == "full_attention"}
    sliding = {h for h, k in zip(heads, kinds) if k == "sliding_attention"}
    if full != {cfg["num_attention_heads"]} or len(sliding) != 1:
        raise ValueError("a full layer takes num_attention_heads, the sliding layers one count of their own")
    return {
        "vocab": _as_run(cfg, "vocab_size", kind),
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "shared_d_ff": cfg["shared_expert_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "sliding_heads": sliding.pop(),
        "heads_per_layer": heads,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "layer_types": kinds,
        "window": cfg["sliding_window"],
        "rope": {k: rotary_form(cfg["rope_parameters"][k], cfg["head_dim"]) for k in KINDS},
        "n_dense": n_dense,
        "n_experts": cfg["num_experts"]["published"],
        "top_k": cfg["num_experts_per_tok"],
        "held": _as_run(cfg, "num_experts", kind),
        "offset": cfg[kind]["share"]["offset"],
        "routed_scaling": float(cfg["moe_routed_scaling_factor"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``MoEConfig``."""
    s = sizes(cfg, kind)
    full, sliding = s["rope"]["full_attention"], s["rope"]["sliding_attention"]
    if sliding["width"] != s["head_dim"] or "yarn" in sliding or s["shared_d_ff"] % s["moe_d_ff"]:
        raise ValueError("the program's sliding layers rotate the whole head by the default form; "
                         "its shared expert is a whole number of routed ones wide")
    yarn = full.get("yarn")
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": len(s["layer_types"]),
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"], "head_width": s["head_dim"], "d_ff": s["d_ff"],
        "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "layer_types": tuple(s["layer_types"]), "qk_norm": True, "attn_gate": True,
        "sliding_window": s["window"], "sliding_heads": s["sliding_heads"], "sliding_rope_theta": sliding["theta"],
        "rope_theta": full["theta"], "rope_share": full["width"] / s["head_dim"],
        "rope_yarn": (
            (yarn["factor"], yarn["original"], yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"])
            if yarn else ()
        ),
        "n_dense_layers": s["n_dense"], "n_experts": s["n_experts"], "top_k": s["top_k"],
        "experts_held": s["held"], "expert_offset": s["offset"], "moe_d_ff": s["moe_d_ff"],
        "n_shared_experts": s["shared_d_ff"] // s["moe_d_ff"], "router": "softmax",
        "routed_scaling": s["routed_scaling"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("attn_norm", "scale"): "attn_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("q_norm", "scale"): "q_norm", ("k_norm", "scale"): "k_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wo", "kernel"): "wo",
    ("w_head_gate", "kernel"): "head_gate", ("router", "kernel"): "router",
    ("mlp", "w_gate", "kernel"): "w_gate", ("mlp", "w_up", "kernel"): "w_up", ("mlp", "w_down", "kernel"): "w_down",
    ("shared", "w_gate", "kernel"): "shared_gate", ("shared", "w_up", "kernel"): "shared_up",
    ("shared", "w_down", "kernel"): "shared_down",
    ("moe", "w_gate"): "experts_gate", ("moe", "w_up"): "experts_up", ("moe", "w_down"): "experts_down",
}
_TOP = {"embedding": "embed", "final_norm": "final_norm", "lm_head": "lm_head"}


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves):
    ``dense_<i>`` is ``d<i>``, ``layers/layer_<j>`` the period's ``p<j>``
    (``layers/layer`` where the period is one layer), ``tail_<i>`` ``t<i>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys[0] in _TOP:
        return _TOP[keys[0]]
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
    return counts_laguna.train_flops(s, doc_lengths, slots)
