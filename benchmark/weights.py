"""Seeded weights, made by the benchmark and handed to both sides.

Every leaf is named as the plain reference names it and drawn from
``(seed, name, layer)`` alone, so the program's stacked leaf, the reference's
stacked leaf and one layer's slice regenerated by itself are the same numbers.
Nothing here imports the program.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def leaf_spec(cfg: dict) -> dict:
    """name -> (per-layer shape or whole shape, stacked over layers?, std, mean).

    ``cfg`` carries the reference's sizes: vocab, d_model, n_layers, n_heads,
    n_kv_heads, head_dim, d_ff."""
    d, v, f = cfg["d_model"], cfg["vocab"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hk = cfg["n_kv_heads"] * cfg["head_dim"]
    return {
        "embed": ((v, d), False, 1.0, 0.0),
        "final_norm": ((d,), False, 0.1, 1.0),
        "lm_head": ((d, v), False, 0.02, 0.0),
        "attn_norm": ((d,), True, 0.1, 1.0),
        "mlp_norm": ((d,), True, 0.1, 1.0),
        "wq": ((d, hq), True, 0.02, 0.0),
        "wk": ((d, hk), True, 0.02, 0.0),
        "wv": ((d, hk), True, 0.02, 0.0),
        "wo": ((hq, d), True, 0.02, 0.0),
        "w_gate": ((d, f), True, 0.02, 0.0),
        "w_up": ((d, f), True, 0.02, 0.0),
        "w_down": ((f, d), True, 0.02, 0.0),
    }


def base_key(seed):
    """A key from any whole number: the low 31 bits seed it, the rest fold in
    (``--seed`` may be larger than 32 signed bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def one(key, name: str, spec: dict, layer=None):
    """One leaf (``layer`` None: a whole unstacked leaf; else that layer's
    slice of a stacked one), float32."""
    shape, stacked, std, mean = spec[name]
    k = _name_key(key, name)
    if stacked:
        k = jax.random.fold_in(k, layer)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def stacked(key, name: str, spec: dict, n_layers: int):
    """A whole leaf as the reference holds it: ``[n_layers, ...]`` where the
    leaf lives in every layer."""
    if not spec[name][1]:
        return one(key, name, spec)
    return jax.vmap(lambda l: one(key, name, spec, l))(jnp.arange(n_layers))
