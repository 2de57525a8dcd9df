"""Factory-free model ablation.

The reference ablates layers of *any* user Keras model by JSON surgery —
``model_from_json`` after deleting named layers (reference loco.py:82-136) —
with zero user plumbing. The flax-idiomatic counterpart here is a three-tier
:func:`auto_ablate` the ablation driver applies when the study has no model
factory:

1. the model's config has a ``without()`` method (DecoderConfig and friends):
   rebuild from ``cfg.without(components)`` — forward-pass gating, unchanged
   param tree;
2. the config carries an ``ablated`` field (BertConfig): rebuild with the
   component names merged in — the model drops those submodules itself;
3. any other flax module: :class:`ParamMaskedModel` zeros the parameter
   subtrees whose path matches the component names on every ``apply`` — a
   residual block with a zeroed output projection reduces to the identity,
   and the masked params receive zero gradients, so the component stays
   ablated through training.
"""

from __future__ import annotations

import dataclasses
from typing import Any, FrozenSet, Iterable, Tuple


class ParamMaskedModel:
    """Generic factory-free fallback: delegates to a base flax module but
    zeros matching param subtrees on ``init`` and every ``apply``.

    A component name matches a parameter whose key path contains it as a
    contiguous segment sequence — ``"mlp"`` masks every ``.../mlp/...``
    subtree, ``"encoder.layer_0"`` only that nested one. Raises at mask time
    if a name matches nothing (a typo must not silently train the full
    model)."""

    def __init__(self, base: Any, ablated: Iterable[str]):
        self.base = base
        self.ablated: FrozenSet[str] = frozenset(ablated)
        self._patterns: Tuple[Tuple[str, ...], ...] = tuple(
            tuple(c.split(".")) for c in sorted(self.ablated)
        )

    def _matched_pattern(self, path_names: Tuple[str, ...]):
        """The first ablated pattern occurring as a contiguous segment
        sequence in ``path_names``, or None."""
        for pat in self._patterns:
            k = len(pat)
            if any(
                tuple(path_names[i : i + k]) == pat
                for i in range(len(path_names) - k + 1)
            ):
                return pat
        return None

    def _mask(self, variables):
        import jax
        import jax.numpy as jnp

        hit = set()

        def one(path, leaf):
            names = tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)
            pat = self._matched_pattern(names)
            if pat is not None:
                hit.add(pat)
                return jnp.zeros_like(leaf)
            return leaf

        masked = jax.tree_util.tree_map_with_path(one, variables)
        missing = [".".join(p) for p in self._patterns if p not in hit]
        if missing:
            raise ValueError(
                f"Ablated component(s) {missing} match no parameter subtree; "
                "check the names against the model's param tree."
            )
        return masked

    def init(self, *args, **kwargs):
        return self._mask(self.base.init(*args, **kwargs))

    def apply(self, variables, *args, **kwargs):
        return self.base.apply(self._mask(variables), *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.base, name)

    def __repr__(self):
        return f"ParamMaskedModel({self.base!r}, ablated={sorted(self.ablated)})"


def _rebuild(model: Any, new_cfg: Any) -> Any:
    """Variant of ``model`` with ``cfg`` swapped; flax ``Module.clone``
    preserves every other constructor attribute (a bare
    ``type(model)(cfg)`` would silently reset them)."""
    if hasattr(model, "clone"):
        return model.clone(cfg=new_cfg)
    return type(model)(new_cfg)


def auto_ablate(model: Any, ablated: FrozenSet[str]) -> Any:
    """Build the ablated variant of ``model`` with zero user plumbing."""
    cfg = getattr(model, "cfg", None)
    if cfg is not None and hasattr(cfg, "without"):
        return _rebuild(model, cfg.without(ablated))
    if (
        cfg is not None
        and dataclasses.is_dataclass(cfg)
        and any(f.name == "ablated" for f in dataclasses.fields(cfg))
    ):
        merged = frozenset(getattr(cfg, "ablated", frozenset())) | frozenset(ablated)
        return _rebuild(model, dataclasses.replace(cfg, ablated=merged))
    return ParamMaskedModel(model, ablated)
