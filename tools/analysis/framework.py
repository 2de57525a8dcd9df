"""Core helpers shared by every ``tools/check_*.py`` lint.

Stdlib-only by construction: the lints run on the bare runtime image and
load checked-in registries (metrics, alerts, knobs) by path precisely so
they never import ``maggy_tpu`` (which would pull in jax).
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import tokenize
from typing import Callable, Dict, Iterable, Iterator, List, NamedTuple, Set, Tuple, Union

#: Directory names pruned from every tree walk. ``.``-prefixed (VCS,
#: venvs), sphinx/mkdocs build output, and bytecode caches.
PRUNE_PREFIXES = (".", "_build", "__pycache__")


class Violation(NamedTuple):
    """One lint finding. A plain tuple subclass so existing self-tests that
    compare against ``(path, line, what)`` tuples keep passing."""

    path: str
    line: int
    what: str


def repo_root() -> str:
    """The repo checkout containing ``tools/analysis/``."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def comment_lines(source: str) -> Dict[int, str]:
    """line -> comment text, tolerating partial tokenization."""
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(iter(source.splitlines(True)).__next__):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except tokenize.TokenError:
        pass
    return out


def marker_lines(
    comments: Union[str, Dict[int, str]], pattern: "re.Pattern[str]"
) -> Set[int]:
    """Line numbers whose comment matches ``pattern``.

    ``comments`` is either raw source (tokenized here) or a map already
    built by :func:`comment_lines` — lints matching several markers build
    the map once and call this per marker.
    """
    if isinstance(comments, str):
        comments = comment_lines(comments)
    return {ln for ln, text in comments.items() if pattern.search(text)}


def iter_py_files(roots: Union[str, Iterable[str]]) -> Iterator[str]:
    """Every ``.py`` file under ``roots`` (deterministic order).

    A root that is itself a file is yielded as-is; directories are walked
    with :data:`PRUNE_PREFIXES` applied at every level.
    """
    if isinstance(roots, str):
        roots = [roots]
    for root in roots:
        if os.path.isfile(root):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [
                d for d in dirnames if not d.startswith(PRUNE_PREFIXES)
            ]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def walk_sources(
    roots: Union[str, Iterable[str]],
    check: Callable[[str, str], List[Tuple[int, str]]],
    *,
    skip: Callable[[str], bool] = lambda path: False,
) -> List[Violation]:
    """Run ``check(source, path) -> [(line, what), ...]`` over every
    ``.py`` file under ``roots``.

    Unreadable files are skipped (tree races with editors/builds); a file
    that fails to parse is itself a violation so a syntax error can never
    silently shrink a lint's coverage.
    """
    violations: List[Violation] = []
    for path in iter_py_files(roots):
        if skip(path):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except OSError:
            continue
        try:
            hits = check(source, path)
        except SyntaxError as e:
            violations.append(Violation(path, e.lineno or 0, f"syntax error: {e.msg}"))
            continue
        violations.extend(Violation(path, line, what) for line, what in hits)
    return violations


def report(violations: Iterable[Tuple[str, int, str]], *, stream=None) -> int:
    """Print ``path:line: what`` per violation plus a count; return the
    process exit code (the shared tail of every lint's ``main``)."""
    stream = stream if stream is not None else sys.stderr
    violations = list(violations)
    for path, line, what in violations:
        print(f"{path}:{line}: {what}", file=stream)
    if violations:
        print(f"{len(violations)} violation(s)", file=stream)
        return 1
    return 0


def load_module_from_path(name: str, path: str):
    """Load a checked-in registry module by file path.

    No package import — registries (metrics, alerts, knobs) must stay
    stdlib-only so lints run on a bare interpreter. The module is placed
    in ``sys.modules`` first: dataclass processing resolves field types
    through ``sys.modules[cls.__module__]``.
    """
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
