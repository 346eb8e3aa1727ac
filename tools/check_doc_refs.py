#!/usr/bin/env python
"""Docs-consistency check: every documentation cross-reference resolves.

Scans Python sources (docstrings + comments included — the whole file
text) and the markdown tree for references to documentation files
(``DESIGN.md``, ``README.md``, ``docs/api.md``, ``ROADMAP.md``, ...) and
section anchors (``DESIGN.md §3``), then verifies:

  1. every referenced file exists in the repository;
  2. every ``DESIGN.md §N`` reference has a matching ``## §N`` heading;
  3. every module promising "documented with runnable examples in
     docs/api.md" delivers: each ``:func:``/``:class:``/``:meth:``
     entry point its docstring names must appear in a ``docs/api.md``
     heading whose section carries a ```` ```python ```` example block
     (this is what keeps the engine's and the serving layer's entry
     point lists honest).

Run directly (CI: .github/workflows/ci.yml) or through
``tests/test_docs.py``::

    python tools/check_doc_refs.py          # exit 1 + report on failure
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import List

# all-caps markdown names anywhere (with the directory path they are
# given under, if any), or an explicit docs/*.md path
FILE_REF = re.compile(r"(?<![\w/.-])(docs/[a-z_]+\.md|(?:[\w.-]+/)*[A-Z][A-Z_]*\.md)\b")
SECTION_REF = re.compile(r"\bDESIGN\.md\s+§(\d+)")
API_PROMISE = re.compile(r"documented with runnable examples in "
                         r"docs/api\.md")
ROLE_REF = re.compile(r":(?:func|class|meth):`~?([\w.]+)`")
HEADING = re.compile(r"^#{1,6}\s")
SCAN_DIRS = ("src", "benchmarks", "tests", "examples", "tools", "docs")


def _api_sections(api_text: str):
    """Split docs/api.md into (heading-line, section-body) pairs; a
    section runs to the next heading of any level."""
    sections = []
    heading, body = None, []
    for line in api_text.splitlines():
        if HEADING.match(line):
            if heading is not None:
                sections.append((heading, "\n".join(body)))
            heading, body = line, []
        else:
            body.append(line)
    if heading is not None:
        sections.append((heading, "\n".join(body)))
    return sections


def _check_api_promises(path, text, sections, problems):
    """Rule 3: promised entry points have an example-backed heading."""
    for name in sorted(set(ROLE_REF.findall(text))):
        short = name.rsplit(".", 1)[-1]
        word = re.compile(rf"(?<!\w){re.escape(short)}(?!\w)")
        hits = [(h, b) for h, b in sections if word.search(h)]
        if not hits:
            problems.append(
                f"{path}: promises docs/api.md coverage of {short!r} "
                f"(:…:`{name}`), but docs/api.md has no heading for it")
        elif not any("```python" in b for _, b in hits):
            problems.append(
                f"{path}: docs/api.md section for {short!r} has no "
                f"runnable ```python example")


def _sources(root: Path):
    for d in SCAN_DIRS:
        yield from (root / d).rglob("*.py")
        yield from (root / d).rglob("*.md")
    yield from root.glob("*.md")


def check(root: Path) -> List[str]:
    """Returns a list of human-readable problems (empty == consistent)."""
    problems: List[str] = []
    design = root / "DESIGN.md"
    design_text = design.read_text() if design.exists() else ""
    sections = set(re.findall(r"^#+\s*§(\d+)", design_text, re.MULTILINE))
    api = root / "docs" / "api.md"
    api_sections = _api_sections(api.read_text()) if api.exists() else []
    for path in sorted(set(_sources(root))):
        if not path.exists():
            continue
        text = path.read_text(errors="replace")
        rel = path.relative_to(root)
        for ref in sorted(set(FILE_REF.findall(text))):
            if ref in ("CHANGES.md", "ISSUE.md") and not (root / ref).exists():
                continue   # per-PR working files, untracked by design
            if not (root / ref).exists():
                problems.append(f"{rel}: references missing file {ref}")
        for sec in sorted(set(SECTION_REF.findall(text))):
            if sec not in sections:
                problems.append(
                    f"{rel}: references DESIGN.md §{sec}, no such heading")
        if path.suffix == ".py" and API_PROMISE.search(text):
            _check_api_promises(rel, text, api_sections, problems)
    return problems


def main() -> int:
    root = Path(__file__).resolve().parents[1]
    problems = check(root)
    if problems:
        print(f"docs-consistency: {len(problems)} unresolved reference(s)")
        for p in problems:
            print(f"  {p}")
        return 1
    print("docs-consistency: all documentation cross-references resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
