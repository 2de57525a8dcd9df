#!/usr/bin/env python
"""Lint: every ``docs/*.md`` page must appear in the mkdocs nav, and every
``.py`` file the documents name must exist.

A page missing from ``mkdocs.yml``'s ``nav:`` builds fine but is
unreachable from the rendered site — docs rot silently (the exact failure
mode that orphaned earlier satellite pages). The nav is parsed with a
line regex rather than a YAML library so the lint runs on the bare runtime
image (pyyaml is not vendored).

A backticked path ending in ``.py`` in ``README.md`` or ``docs/*.md`` must
resolve from the root, from ``maggy_tpu/``, or by its last components
somewhere in the tree (the documents write ``ops/flash.py`` for
``maggy_tpu/ops/flash.py``): a PR that deletes a file brings along the
documents that still send a reader to it.

Usage: ``python tools/check_docs_nav.py [repo_root]`` — exits nonzero
listing every orphaned page and dangling path. Built on the shared ``tools/analysis``
framework (docs/static_analysis.md); wired into the tier-1 run via
``tests/test_telemetry.py`` alongside ``check_no_bare_print.py``.
"""

from __future__ import annotations

import os
import re
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

from analysis import iter_py_files, report, repo_root  # noqa: E402

# "  - Title: file.md" (any indent level, quoted or not)
_NAV_ENTRY = re.compile(r"^\s*-\s+(?:[^:]+:\s*)?['\"]?([\w./-]+\.md)['\"]?\s*$")


def nav_pages(mkdocs_yml: str):
    """Every .md path referenced from the nav section of mkdocs.yml."""
    pages = set()
    in_nav = False
    with open(mkdocs_yml, encoding="utf-8") as f:
        for line in f:
            stripped = line.rstrip("\n")
            if re.match(r"^nav\s*:", stripped):
                in_nav = True
                continue
            if in_nav:
                # nav block ends at the next top-level key
                if stripped and not stripped[0].isspace() and not stripped.startswith("-"):
                    break
                m = _NAV_ENTRY.match(stripped)
                if m:
                    pages.add(m.group(1))
    return pages


def orphaned_docs(repo: str):
    """docs/*.md files absent from the mkdocs nav."""
    mkdocs_yml = os.path.join(repo, "mkdocs.yml")
    docs_dir = os.path.join(repo, "docs")
    if not os.path.isfile(mkdocs_yml) or not os.path.isdir(docs_dir):
        return []
    pages = nav_pages(mkdocs_yml)
    missing = []
    for name in sorted(os.listdir(docs_dir)):
        if name.endswith(".md") and name not in pages:
            missing.append(os.path.join("docs", name))
    return missing


# `pkg/mod.py`, also `pkg/mod.py:12-30` and `tests/test_x.py::test_name`;
# commands (`python tools/check_all.py`) and globs are not paths
_PY_PATH = re.compile(r"`([\w./-]+\.py)(?::[\w:.-]*)?`")
# what a chip run leaves in a working tree (.gitignore): copies of other commits
_OTHER_TREES = ("chiprun_out", "_archive")


def dangling_paths(repo: str):
    """``(document, line, path)`` for every backticked ``.py`` path in
    README.md and docs/*.md that names no file of the tree."""
    docs_dir = os.path.join(repo, "docs")
    documents = [os.path.join(repo, "README.md")]
    if os.path.isdir(docs_dir):
        documents += [
            os.path.join(docs_dir, name)
            for name in sorted(os.listdir(docs_dir))
            if name.endswith(".md")
        ]
    tree = [
        "/" + rel.replace(os.sep, "/")
        for rel in (os.path.relpath(p, repo) for p in iter_py_files(repo))
        if rel.split(os.sep)[0] not in _OTHER_TREES
    ]
    missing = []
    for document in documents:
        if not os.path.isfile(document):
            continue
        with open(document, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for m in _PY_PATH.finditer(line):
                    path = m.group(1)
                    # from the root, from maggy_tpu/ or deeper: one test
                    if not any(t.endswith("/" + path) for t in tree):
                        missing.append(
                            (os.path.relpath(document, repo), lineno, path)
                        )
    return missing


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    repo = args[0] if args else repo_root()
    violations = [
        (
            path,
            0,
            "not referenced from mkdocs.yml nav — add a nav entry or the "
            "page is unreachable from the docs site",
        )
        for path in orphaned_docs(repo)
    ] + [
        (document, lineno, f"`{path}` names no file of the tree")
        for document, lineno, path in dangling_paths(repo)
    ]
    return report(violations)


if __name__ == "__main__":
    sys.exit(main())
