"""Reads a configuration file (published Mistral-style ``config.json`` keys
with the cuts beside them) into the sizes the reference uses and the fields
the program's config classes take. One mapping, kept here with the yardstick."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _as_run(cfg: dict, key: str, kind: str):
    """A reduced key holds ``{"published": x, "<kind>": y}``; others a value."""
    v = cfg[key]
    return v[kind] if isinstance(v, dict) else v


def reference_sizes(cfg: dict, kind: str) -> dict:
    return {
        "vocab": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "n_layers": _as_run(cfg, "num_hidden_layers", kind),
        "max_positions": _as_run(cfg, "max_position_embeddings", kind),
    }


def program_fields(cfg: dict, kind: str) -> dict:
    """Keyword arguments of the program's ``DecoderConfig``."""
    s = reference_sizes(cfg, kind)
    if s["d_model"] != s["n_heads"] * s["head_dim"]:
        raise ValueError("the program's decoder takes head_dim = d_model / n_heads only")
    fields = {
        "vocab_size": s["vocab"], "d_model": s["d_model"], "n_layers": s["n_layers"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"], "d_ff": s["d_ff"],
        "rope_theta": s["rope_theta"], "norm_eps": s["norm_eps"],
        "max_seq_len": s["max_positions"],
    }
    fields.update(cfg[kind].get("program_fields", {}))
    return fields


def load_reference(cfg: dict):
    """The configuration's plain reference, a file beside the configuration."""
    import importlib.util

    path = os.path.join(ROOT, cfg["reference"])
    spec = importlib.util.spec_from_file_location("benchmark_reference_" + cfg["name"].replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF_NAMES = (
    "embedding", "lm_head", "final_norm", "attn_norm", "mlp_norm",
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
)


def ref_name(path) -> str:
    """The reference's name of a leaf of the program's parameter tree (the one
    place that knows how the program's flax modules name their leaves)."""
    import jax

    s = jax.tree_util.keystr(path)
    for n in REF_NAMES:
        if f"'{n}'" in s:
            return "embed" if n == "embedding" else n
    raise KeyError(f"no reference leaf for the program's {s}")


def named_leaves(tree) -> dict:
    import jax

    return {ref_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
