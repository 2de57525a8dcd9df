#!/usr/bin/env python
"""Lint: every chaos kind fired or scripted must be in the checked-in registry.

A typo'd chaos kind never errors at the seam — ``Chaos.fire("slice_dorp")``
simply never matches a rule, and ``MAGGY_TPU_CHAOS="slice_dorp:..."`` would
arm a fault that never fires — so a chaos acceptance test can silently stop
injecting anything and pass vacuously. This lint closes the kind set the
same way ``check_telemetry_names`` closes the metric set:

* ``maggy_tpu/resilience/chaos.py`` declares the registry: the ``KINDS``
  frozenset (``Chaos.parse`` also rejects unknown kinds at runtime; this
  tool catches the static sites, including ``.fire`` calls that bypass
  parse).
* This tool AST-walks ``maggy_tpu/`` and ``tests/`` for
  - ``.fire("kind", ...)`` calls on chaos-ish receivers (an identifier in
    the chain containing ``chaos``, or ``self``/``ch`` — the codebase's
    spellings), whose literal first argument must be a declared kind;
  - chaos *spec strings*: the literal argument of ``Chaos.parse(...)``,
    ``setenv("MAGGY_TPU_CHAOS", ...)``, ``environ["MAGGY_TPU_CHAOS"] = ...``
    assignments and ``{"MAGGY_TPU_CHAOS": ...}`` dict entries — every
    ``kind:`` head in the spec must be declared.
  Non-literal names/specs are skipped (statically uncheckable).

Usage: ``python tools/check_chaos_kinds.py [root ...]`` — exits nonzero
listing violations. Built on the shared ``tools/analysis`` framework
(docs/static_analysis.md); wired into tier-1 via
``tests/test_elastic_membership.py``, beside the telemetry-name,
host-sync, and exception-hygiene lints.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Optional, Set, Tuple

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

from analysis import report, repo_root, walk_sources  # noqa: E402

ENV_VAR = "MAGGY_TPU_CHAOS"


def load_kinds(repo: str) -> Set[str]:
    """Extract the ``KINDS`` literal from chaos.py by AST (no package
    import — the lint must not pull jax into a bare interpreter)."""
    path = os.path.join(repo, "maggy_tpu", "resilience", "chaos.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "KINDS" for t in node.targets
        ):
            kinds = ast.literal_eval(
                node.value.args[0]
                if isinstance(node.value, ast.Call) and node.value.args
                else node.value
            )
            return set(kinds)
    raise RuntimeError(f"no KINDS registry found in {path}")


def _spec_kinds(spec: str) -> List[str]:
    """The ``kind`` heads of a chaos spec string (same split as
    ``Chaos.parse``, minus validation)."""
    out = []
    for rule in spec.split(";"):
        rule = rule.strip()
        if rule:
            out.append(rule.partition(":")[0].strip())
    return out


def _chain_names(expr: ast.AST) -> List[str]:
    names = []
    for node in ast.walk(expr):
        if isinstance(node, ast.Name):
            names.append(node.id)
        elif isinstance(node, ast.Attribute):
            names.append(node.attr)
    return names


def _receiver_is_chaos(expr: ast.AST) -> bool:
    return any(
        "chaos" in n.lower() or n in ("self", "ch") for n in _chain_names(expr)
    )


def _literal_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def check_source(source: str, path: str, kinds: Set[str]) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    tree = ast.parse(source, filename=path)

    def bad_spec(node: ast.AST, spec: str, where: str) -> None:
        for k in _spec_kinds(spec):
            if k not in kinds:
                out.append(
                    (
                        node.lineno,
                        f"{where}: unknown chaos kind {k!r} — declare it in "
                        "resilience/chaos.py KINDS or fix the typo",
                    )
                )

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            fn = node.func
            if fn.attr == "fire" and node.args and _receiver_is_chaos(fn.value):
                name = _literal_str(node.args[0])
                if name is not None and name not in kinds:
                    out.append(
                        (
                            node.lineno,
                            f"fire({name!r}) is not a declared chaos kind — "
                            "add it to resilience/chaos.py KINDS",
                        )
                    )
            elif fn.attr == "parse" and node.args and any(
                "Chaos" in n for n in _chain_names(fn.value)
            ):
                spec = _literal_str(node.args[0])
                if spec is not None:
                    bad_spec(node, spec, "Chaos.parse")
            elif fn.attr in ("setenv", "setdefault") and len(node.args) >= 2:
                if _literal_str(node.args[0]) == ENV_VAR:
                    spec = _literal_str(node.args[1])
                    if spec is not None:
                        bad_spec(node, spec, ENV_VAR)
        elif isinstance(node, ast.Assign):
            # os.environ["MAGGY_TPU_CHAOS"] = "<spec>"
            for tgt in node.targets:
                if (
                    isinstance(tgt, ast.Subscript)
                    and _literal_str(tgt.slice) == ENV_VAR
                ):
                    spec = _literal_str(node.value)
                    if spec is not None:
                        bad_spec(node, spec, ENV_VAR)
        elif isinstance(node, ast.Dict):
            # {"MAGGY_TPU_CHAOS": "<spec>"} env dicts (subprocess launches)
            for key, val in zip(node.keys, node.values):
                if key is not None and _literal_str(key) == ENV_VAR:
                    spec = _literal_str(val)
                    if spec is not None:
                        bad_spec(node, spec, ENV_VAR)
    return out


def check_tree(roots: List[str], kinds: Set[str]) -> List[Tuple[str, int, str]]:
    return walk_sources(
        roots, lambda source, path: check_source(source, path, kinds)
    )


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    repo = repo_root()
    roots = args or [
        os.path.join(repo, "maggy_tpu"),
        os.path.join(repo, "tests"),
    ]
    kinds = load_kinds(repo)
    return report(check_tree(roots, kinds))


if __name__ == "__main__":
    sys.exit(main())
